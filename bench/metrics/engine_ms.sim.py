"""Simulator engine time per interval (ms): the window's host-clock time
less the benchmark-timed policy decisions, over the intervals run."""


def read(ctx):
    if ctx.get("kind") != "simulator" or not ctx["intervals"]:
        return None
    return (ctx["window_s"] - ctx["decision_s"]) / ctx["intervals"] * 1e3
