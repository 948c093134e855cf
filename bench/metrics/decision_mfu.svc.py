"""The service's decisions' share of the chip's peak (%): the model
flops each answered tenant interval needed (``bench.flops.decision_flops``
at its real job count) over the window's seconds and the bf16 peak."""


def read(ctx):
    if ctx.get("kind") != "service" or not ctx["decision_flops"]:
        return None
    return (ctx["decision_flops"] / ctx["window_s"]
            / ctx["peaks"]["bf16_flops_per_s"] * 100.0)
