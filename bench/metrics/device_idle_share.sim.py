"""Share of the traced window (%) in which no operation ran on the
device: 1 - union of the device's op intervals / window."""


def read(ctx):
    tr = ctx.get("trace")
    if ctx.get("kind") != "simulator" or not tr or not tr["n_ops"]:
        return None
    return (1.0 - tr["busy_ns"] / tr["window_ns"]) * 100.0
