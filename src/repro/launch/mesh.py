"""Production mesh construction + logical-axis rules.

TPU v5e target: single pod = 16×16 = 256 chips, multi-pod = 2 pods = 512.
``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state — callers (dryrun.py) set
``xla_force_host_platform_device_count`` before the first jax call.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """``jax.make_mesh`` with ``Auto`` axis types: the model code's
    ``with_sharding_constraint`` calls (dist.constrain) are layout hints
    the partitioner propagates from, not the type-level assertions
    ``Explicit`` axes (jax.make_mesh's default) would make them."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False, variant: str = "tp16"):
    """Same 256/512 chips, two logical factorizations:

    tp16 (baseline contract): (data=16, model=16) — 16-way tensor
        parallelism inside each client slice.
    2d   (§Perf #4): (data=16, batch=4, model=4) — the 16 chips of a client
        slice split into 4-way per-client batch parallelism × 4-way tensor
        parallelism; Megatron-style activation all-reduces shrink 4× in
        group width AND 4× in payload (batch-sharded activations).
    """
    if variant == "tp16":
        shape = (2, 16, 16) if multi_pod else (16, 16)
        axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    elif variant == "2d":
        shape = (2, 16, 4, 4) if multi_pod else (16, 4, 4)
        axes = (("pod", "data", "batch", "model") if multi_pod
                else ("data", "batch", "model"))
    else:
        raise ValueError(variant)
    return make_mesh(shape, axes)


def recommended_variant(cfg) -> str:
    """Per-family mesh factorization (EXPERIMENTS.md §Perf #4 negative
    finding): MoE archs need the WIDE model axis for expert parallelism
    (tp16); dense/MQA/SSM trainers gain 1.2–11.7× from the 2d variant."""
    return "tp16" if cfg.moe is not None else "2d"


def make_local_mesh(data: int = 2, model: int = 2):
    """Small mesh over host devices for tests (set device_count first)."""
    return make_mesh((data, model), ("data", "model"))


def data_axes(mesh) -> tuple[str, ...]:
    """All mesh axes that carry batch/client parallelism."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a == "model")


def mesh_rules(mesh, *, kind: str) -> dict[str, tuple[str, ...]]:
    """Logical→physical rules per step kind (see dist/sharding.py).

    train   : client axis is consumed by vmap(spmd_axis_name=data axes);
              inside the per-client function dp is unmapped.  ``cl``
              names the flat round's client rows (over the data axes).
    prefill/decode : batch over data axes, tensor over model.
    long    : batch=1 ⇒ dp unmapped, KV-cache sequence over data ("sp").
    """
    batch = ("batch",) if "batch" in mesh.axis_names else ()
    if kind == "train":
        return {"dp": batch, "mp": model_axes(mesh), "sp": (),
                "cl": data_axes(mesh)}
    if kind in ("prefill", "decode"):
        return {"dp": data_axes(mesh) + batch, "mp": model_axes(mesh),
                "sp": ()}
    if kind == "long":
        return {"dp": batch, "mp": model_axes(mesh), "sp": data_axes(mesh)}
    raise ValueError(kind)


def n_clients(mesh) -> int:
    """Training clients = product of data-like axes (one client per slice)."""
    out = 1
    for a in data_axes(mesh):
        out *= mesh.shape[a]
    return out
