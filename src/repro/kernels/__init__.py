"""Pallas TPU kernels for the compute hot-spots (checked against their jnp
references in interpret mode on the CPU, compiled for a described v5e by
tests/test_tpu_compile.py, run on the chip by chip_smoke.py):

* ``calibrated_update`` — fused FedaGrac local step x ← x − η(g + λc)
* ``flash_attention``   — blocked online-softmax attention, forward +
                          custom_vjp backward kernels (training path)
* ``quantize``          — wire-compression codecs on the flat client rows
* ``ssd_scan``          — chunked Mamba2 SSD scan, state carried in VMEM
                          across the sequential chunk grid axis

``backend.on_tpu`` decides where they run.
"""
from repro.kernels.calibrated_update.ops import calibrated_update_tree
from repro.kernels.flash_attention.ops import (flash_attention,
                                               flash_attention_diff)
from repro.kernels.ssd_scan.ops import ssd_scan

__all__ = ["calibrated_update_tree", "flash_attention",
           "flash_attention_diff", "ssd_scan"]
