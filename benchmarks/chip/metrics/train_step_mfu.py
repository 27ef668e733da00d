"""Model FLOP/s utilisation of the traced window: the operations the
model requires per useful token (``flops/<config>.py``) times the useful
tokens the window completed, over the window's wall time, the chips and
the chip's bf16 peak (``peaks.json``)."""


def read(ctx):
    v = ctx.values
    if not ctx.window_s or "useful_tokens" not in v:
        return None
    peak = ctx.peaks()["bf16_flops_per_s"]
    return 100.0 * v["flops_per_token"] * v["useful_tokens"] / (
        ctx.window_s * ctx.chips * peak)
