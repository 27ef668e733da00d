"""Logical-axis sharding constraints (the model-side half of the mesh story).

``launch/mesh.py`` decides *which physical mesh axes* implement each logical
axis per step kind (``mesh_rules``); this module holds that decision while
the launch layer's program is traced (``traced_under``), so model code can
annotate intermediates with logical names only:

    constrain(h, "dp", None, "mp")     # (batch, seq, hidden)

Logical names: ``dp`` (batch/data parallel), ``mp`` (tensor/model parallel),
``sp`` (sequence parallel — long-decode KV caches), ``cl`` (the flat round's
client rows: spread over more than one device, its clients run in parallel,
core/flat.py).  Outside any mesh (unit tests, CPU simulation) every call is
a no-op, so the model zoo runs unchanged on a single device.

Two deliberate behaviours (relied on by the model code):

* an axis whose physical size does not evenly divide the dimension is
  *dropped* (stays replicated) — e.g. KV heads on meshes wider than Hkv
  (attention.py), vocab on odd vocab sizes;
* rules may map a logical name to ``()`` (train mode maps ``dp`` to nothing
  because vmap already consumed the client axis) — also replicated.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Callable, Optional, Sequence

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

# the mesh + logical→physical rules of the program being traced; installed
# only while the launch layer's round / serve step is traced
# (``traced_under``), so nothing outlives the trace
_MESH = None
_RULES: dict[str, tuple[str, ...]] = {}


@contextlib.contextmanager
def mesh_rules(mesh, rules: dict[str, Sequence[str]]):
    """Install ``mesh`` and logical→physical ``rules`` for the ``constrain``
    calls inside the block; the previous ones come back on exit."""
    global _MESH, _RULES
    prev = _MESH, _RULES
    _MESH, _RULES = mesh, {k: tuple(v) for k, v in rules.items()}
    try:
        yield
    finally:
        _MESH, _RULES = prev


def traced_under(mesh, rules: dict[str, Sequence[str]],
                 fn: Callable) -> Callable:
    """``fn`` with ``mesh_rules(mesh, rules)`` installed while it runs —
    under ``jax.jit`` that is while it is traced, which is when the model
    code reads them."""
    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with mesh_rules(mesh, rules):
            return fn(*args, **kwargs)
    return wrapped


def partitioned() -> bool:
    """Whether the program being traced spreads over a multi-device mesh."""
    return _MESH is not None and _MESH.size > 1


def axis_size(name: str) -> int:
    """Total device count implementing logical axis ``name`` (1 if unmapped
    or no mesh is installed)."""
    if _MESH is None:
        return 1
    out = 1
    for ax in _RULES.get(name, ()):
        out *= _MESH.shape[ax]
    return out


def _physical(name: Optional[str], dim: int):
    """Physical axes for one tensor dimension, or None to replicate."""
    if name is None or _MESH is None:
        return None
    axes = _RULES.get(name, ())
    size = 1
    for ax in axes:
        size *= _MESH.shape[ax]
    if not axes or size <= 1:
        return None
    if dim % size != 0:              # non-dividing axis: keep replicated
        return None
    return axes if len(axes) > 1 else axes[0]


def constrain(x: jax.Array, *names: Optional[str]) -> jax.Array:
    """``with_sharding_constraint`` by logical axis names, one per dim.

    No-op when no mesh is installed.  Under ``vmap(spmd_axis_name=...)``
    (the round engine's client axis) ``x`` is the per-client view and
    ``names`` describe its per-client dims only.
    """
    if _MESH is None:
        return x
    if len(names) != x.ndim:
        raise ValueError(
            f"constrain: {len(names)} axis names for rank-{x.ndim} value")
    spec = P(*(_physical(n, d) for n, d in zip(names, x.shape)))
    return jax.lax.with_sharding_constraint(x, NamedSharding(_MESH, spec))
