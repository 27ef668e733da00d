"""Public op: fused calibrated update over arbitrary pytrees.

Leaves are flattened, concatenated and lane-padded to (rows, 128) so ONE
kernel launch covers the whole parameter vector (instead of one tiny
launch per leaf — important for models with hundreds of small tensors).
Off the TPU the kernel runs in interpret mode; ``use_pallas=False`` falls
back to the jnp oracle for A/B benchmarks.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.kernels import backend
from repro.kernels.calibrated_update import ref
from repro.kernels.calibrated_update.kernel import (LANES,
                                                    calibrated_update_2d,
                                                    calibrated_update_prox_2d)

PyTree = Any


def flatten_to_2d(tree: PyTree) -> tuple[jax.Array, list, Any, int]:
    """Concat all leaves (as f32) into (rows, LANES); returns
    (mat, shapes/dtypes, treedef, true_size)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    metas = [(lv.shape, lv.dtype, lv.size) for lv in leaves]
    flat = jnp.concatenate([lv.astype(jnp.float32).reshape(-1)
                            for lv in leaves])
    n = flat.shape[0]
    rows = -(-n // LANES)
    pad = rows * LANES - n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(rows, LANES), metas, treedef, n


def unflatten_from_2d(mat: jax.Array, metas, treedef, n: int) -> PyTree:
    flat = mat.reshape(-1)[:n]
    leaves = []
    off = 0
    for shape, dtype, size in metas:
        leaves.append(flat[off:off + size].reshape(shape).astype(dtype))
        off += size
    return jax.tree_util.tree_unflatten(treedef, leaves)


def calibrated_update_tree(x: PyTree, g: PyTree, c: PyTree, eta, lam, *,
                           use_pallas: bool = True,
                           interpret: bool | None = None) -> PyTree:
    """x ← x − η (g + λ c) fused over the whole pytree."""
    if not use_pallas:
        return jax.tree.map(
            lambda xx, gg, cc: ref.calibrated_update(xx, gg, cc, eta, lam),
            x, g, c)
    if interpret is None:
        interpret = not backend.on_tpu()
    xm, metas, treedef, n = flatten_to_2d(x)
    gm, _, _, _ = flatten_to_2d(g)
    cm, _, _, _ = flatten_to_2d(c)
    om = calibrated_update_2d(xm, gm, cm, eta, lam, interpret=interpret)
    return unflatten_from_2d(om, metas, treedef, n)


def calibrated_update_prox_tree(x: PyTree, g: PyTree, c: PyTree, x0: PyTree,
                                eta, lam, mu, *, use_pallas: bool = True,
                                interpret: bool | None = None) -> PyTree:
    """FedProx variant fused over the whole pytree:
    x ← x − η (g + λ c + μ (x − x₀))."""
    if not use_pallas:
        return jax.tree.map(
            lambda xx, gg, cc, aa: ref.calibrated_update_prox(
                xx, gg, cc, aa, eta, lam, mu), x, g, c, x0)
    if interpret is None:
        interpret = not backend.on_tpu()
    xm, metas, treedef, n = flatten_to_2d(x)
    gm, _, _, _ = flatten_to_2d(g)
    cm, _, _, _ = flatten_to_2d(c)
    am, _, _, _ = flatten_to_2d(x0)
    om = calibrated_update_prox_2d(xm, gm, cm, am, eta, lam, mu,
                                   interpret=interpret)
    return unflatten_from_2d(om, metas, treedef, n)
