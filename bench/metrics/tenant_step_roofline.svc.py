"""The tenant step's share of its roofline (%): the least time the chip
needs for the window's tenant-step launches (per launch the larger of
its matmul flops over the peak and its bytes over the memory bandwidth,
``bench.tenantflops`` at each launch's tenants and rows) over the
program's device time in the trace."""


def read(ctx):
    if ctx.get("kind") != "service" or not ctx.get("trace"):
        return None
    calls, ns = ctx["tenant_device"]
    if not calls or ns <= 0 or not ctx["tenant_least_s"]:
        return None
    return ctx["tenant_least_s"] / (ns * 1e-9) * 100.0
