"""Share of their roofline that the flash-attention kernels reach
together: for every ``flash_fwd``, ``flash_bwd_dq`` and ``flash_bwd_dkv``
call, the larger of its required operations (causal, recomputation not
counted) over the bf16 peak and its bytes over the HBM bandwidth, from the
call's shape and the model's true head size (``flops/kernels.py``),
summed, over the summed device time of those events."""


def read(ctx):
    red = ctx.reduction or {}
    att = ctx.values.get("attention")
    if not att:
        return None
    costs = ctx.module(".", "flops/kernels")
    tr = ctx.module(".", "trace")
    pk = ctx.peaks()
    need, spent = 0.0, 0.0
    for name, cost in costs.FLASH.items():
        k = red.get("kernels", {}).get(name)
        if not k:
            continue
        spent += k["seconds"]
        for key, calls in k["shapes"].items():
            flops, byts = cost(*tr.parse_shape(key), **att)
            need += calls * max(flops / pk["bf16_flops_per_s"],
                                byts / pk["hbm_bytes_per_s"])
    return 100.0 * need / spent if spent else None
