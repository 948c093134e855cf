"""Predictor time per interval (ms): the benchmark's span around
``StragglerPredictor.predict_interval`` (staging upload, dispatch,
blocking readback), summed over the window, over the intervals run."""


def read(ctx):
    if ctx.get("kind") != "simulator" or not ctx["predict_calls"]:
        return None
    return ctx["predict_s"] / ctx["intervals"] * 1e3
