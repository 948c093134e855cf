"""START controller host time per interval (ms): the decision (policy
``observe`` + ``decide``) less the ``predict_interval`` span: feature
assembly, the adaptive k and the Algorithm-1 trigger."""


def read(ctx):
    if ctx.get("kind") != "simulator" or not ctx["intervals"]:
        return None
    return (ctx["decision_s"] - ctx["predict_s"]) / ctx["intervals"] * 1e3
