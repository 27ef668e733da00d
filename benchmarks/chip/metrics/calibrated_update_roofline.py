"""Share of its roofline that the calibrated-update kernel reaches: for
every call, the larger of its required operations over the bf16 peak and
its bytes over the HBM bandwidth (``flops/kernels.py``, from the call's
shape in the trace), summed, over the summed device time of the
``calibrated_update`` events of the traced window."""


def read(ctx):
    red = ctx.reduction or {}
    k = red.get("kernels", {}).get("calibrated_update")
    if not k or not k["seconds"] or not k["shapes"]:
        return None
    costs = ctx.module(".", "flops/kernels")
    tr = ctx.module(".", "trace")
    pk = ctx.peaks()
    need = 0.0
    for key, calls in k["shapes"].items():
        flops, byts = costs.calibrated_update(*tr.parse_shape(key))
        need += calls * max(flops / pk["bf16_flops_per_s"],
                            byts / pk["hbm_bytes_per_s"])
    return 100.0 * need / k["seconds"]
