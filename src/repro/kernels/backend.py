"""Where the kernels run: the one backend switch every wrapper reads.

On a TPU the Pallas kernels compile for the chip (``interpret=False``) and
the round and model code take them; elsewhere the wrappers run interpret
mode or their jnp oracles.  A program the launch layer spreads over a
multi-device mesh passes the XLA paths explicitly (XLA cannot partition a
Mosaic call); that choice is the caller's, not made here.
"""
from __future__ import annotations

import jax


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"
