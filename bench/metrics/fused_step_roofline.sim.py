"""``_fused_step``'s share of its roofline (%): the least time the chip
needs for the window's decisions (per call the larger of its matmul
flops over the peak and its bytes over the memory bandwidth, from
``bench.flops`` at the real job count) over the program's device time
in the trace.  Every call of these cells is bound by bytes (the
weights)."""


def read(ctx):
    tr = ctx.get("trace")
    if ctx.get("kind") != "simulator" or not tr:
        return None
    calls, ns = tr["programs"]["_fused_step"]
    if not calls or ns <= 0:
        return None
    return ctx["fused_least_s"] / (ns * 1e-9) * 100.0
