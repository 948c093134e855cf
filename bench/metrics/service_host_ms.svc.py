"""The service's host time per tick (ms): ``service.tick`` less its
``predictor.tenants`` (ingest, packing, triggers and answers), plus every
``service.submit`` (sanitize and enqueue) of the window, per tick."""


def read(ctx):
    prog = ctx.get("program")
    if ctx.get("kind") != "service" or not prog or "service.tick" not in prog:
        return None
    tick = prog["service.tick"]
    ns = (tick["total_ns"]
          - prog.get("predictor.tenants", {}).get("total_ns", 0.0)
          + prog.get("service.submit", {}).get("total_ns", 0.0))
    return ns / tick["count"] * 1e-6
