"""Share of the traced training window in which no operation ran on the
device: 1 − (union of the ``XLA Ops`` intervals) / window, averaged over
the chips (``trace.py``)."""


def read(ctx):
    red = ctx.reduction
    if not red or not red["window_s"]:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
