"""The tenant step per tick (ms): the program's ``predictor.tenants``
span (the launch and its blocking readback) over the window, per
``service.tick``."""


def read(ctx):
    prog = ctx.get("program")
    if ctx.get("kind") != "service" or not prog \
            or "predictor.tenants" not in prog or "service.tick" not in prog:
        return None
    return (prog["predictor.tenants"]["total_ns"]
            / prog["service.tick"]["count"] * 1e-6)
