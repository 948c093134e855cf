"""Padded share of the tenant step's dispatched rows (%), from the
predictor's ``tenant_rows_real`` and ``tenant_rows_dispatched`` over the
window."""


def read(ctx):
    c = ctx.get("counters") or {}
    if ctx.get("kind") != "service" or not c.get("tenant_rows_dispatched"):
        return None
    return (100.0 * (c["tenant_rows_dispatched"] - c["tenant_rows_real"])
            / c["tenant_rows_dispatched"])
