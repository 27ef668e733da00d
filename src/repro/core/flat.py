"""Flat-parameter execution: single-buffer rounds (DESIGN.md §11).

The layered round (core/stages.py) runs every elementwise stage as a
``jax.tree.map`` chain — one XLA op group *per leaf* for the local update,
the aggregation einsum, the orientation recovery and the server step.  At
paper scale (small leaves, many of them) the round is op-count-bound, not
FLOP-bound, and the fused Pallas calibrated-update kernels
(kernels/calibrated_update/) were dead code in training.

This module collapses the model pytree to ONE contiguous lane-padded
buffer and runs the *entire* round on flat state:

* server vectors (params, ν, server_m/v) are ``(P,)`` buffers, per-client
  state (ν⁽ⁱ⁾, round-local x⁽ⁱ⁾/g₀⁽ⁱ⁾) are ``(M, P)`` matrices, with
  ``P = ceil(n / 128) · 128`` so the matrices feed the Pallas kernels
  directly (``kernel.LANES`` lane padding, zeros in the tail — every stage
  below is padding-preserving, so the tail stays exactly zero);
* the client update calls ``calibrated_update_2d`` /
  ``calibrated_update_prox_2d`` once per local step on flat rows — one
  fused launch instead of ``num_leaves`` tree_map dispatches — with the
  ν/g₀ accumulation as flat row ops; each client runs exactly its own K_i
  steps where the rows sit on one device, a masked k_max-step scan where
  they are spread over the mesh;
* aggregation, orientation, ν mass-mix and the server optimizer REUSE the
  stage registries verbatim: the stage functions are pytree-polymorphic
  (``jax.tree.map`` over a bare array is the identity traversal), so a
  ``(M, P)`` matrix flows through ``AGGREGATORS`` / ``SELECTORS`` /
  ``SERVER_OPTIMIZERS`` as a one-leaf tree and every per-leaf einsum
  becomes a single ``(M, P)``-row einsum;
* the loss boundary is **flat-native** (DESIGN.md §13): the model apply
  consumes per-leaf *views* of the buffer — ``view_tree`` slices each leaf
  at its spec offset (``FlatSpec.offsets``, the view table) and casts to
  the leaf dtype — and ``flat_value_and_grad`` differentiates with respect
  to the views, accumulating the leaf cotangents straight back into ONE
  ``(P,)`` buffer (``flat_cotangent``, a region-write chain).  The round
  never holds the parameter tree as a value: the caller sees only the
  buffer, and a mixed-precision run (``master_dtype``) keeps the master
  buffer in f32 while every view — and therefore all model compute — is
  bf16, the cast riding the boundary slice instead of a separate pass.

Numerics: every stage performs the same elementwise arithmetic in the
same order as the tree round, only on a different memory layout.  The
agreement is golden-pinned by tests/test_flat_layout.py for all nine
algorithms on both engines at ULP scale: XLA contracts ``x − η·g`` into
an FMA (one rounding) in one program layout and not the other — an
LLVM fusion-context decision no jnp-level structuring controls — so f32
trajectories agree to ~1 ulp per local step rather than bit-for-bit
(verified: the tree path matches the fused-multiply-add reference, the
flat path the two-rounding one; same asymmetry test_calibrated_update_2d
documents).  In bf16 the kernels additionally accumulate in f32 and round
once at the end where the tree path rounds per op — one bf16 ulp.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import dist
from repro.core import compress, stages
from repro.core import robust as robust_mod
from repro.core.fedopt import Algorithm
from repro.kernels import backend
from repro.kernels.calibrated_update import ref as cu_ref
from repro.kernels.calibrated_update.kernel import (LANES,
                                                    calibrated_update_2d,
                                                    calibrated_update_prox_2d)
from repro.kernels.quantize import ops as qops

PyTree = Any


# ---------------------------------------------------------------------------
# layout spec + ravel / unravel
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FlatSpec:
    """Static description of the tree ↔ flat-buffer bijection.

    ``n`` true elements, lane-padded to ``p`` (multiple of kernel.LANES);
    ``dtype`` is the shared buffer dtype — the common leaf dtype when the
    tree is uniform (bf16 state stays bf16-sized), f32 otherwise, or the
    explicit ``master_dtype`` override (mixed precision: f32 master buffer
    over bf16 leaves, DESIGN.md §13).

    ``(offsets, shapes, dtypes, sizes)`` together form the **view table**:
    leaf *i* of the tree is ``flat[…, offsets[i] : offsets[i] + sizes[i]]``
    reshaped to ``shapes[i]`` and cast to ``dtypes[i]``.  Offsets are
    static, lane-padding lives entirely in the tail ``[n, p)`` — no view
    ever overlaps the pad, so padding-preserving stages keep it zero.
    """
    shapes: tuple[tuple[int, ...], ...]
    dtypes: tuple[Any, ...]
    sizes: tuple[int, ...]
    treedef: Any
    n: int
    p: int
    dtype: Any
    offsets: tuple[int, ...] = ()

    def __post_init__(self):
        if not self.offsets and self.sizes:
            # derive the view table for specs built positionally (older
            # call sites / tests): cumulative leaf offsets
            object.__setattr__(
                self, "offsets",
                tuple(int(o) for o in
                      np.concatenate([[0], np.cumsum(self.sizes)[:-1]])))


def make_flat_spec(tree: PyTree,
                   master_dtype: Optional[Any] = None) -> FlatSpec:
    """Build the spec from a concrete or abstract (eval_shape'd) tree.

    ``master_dtype`` overrides the buffer dtype (the *master* copy all
    round state lives in) without touching the per-leaf view dtypes — the
    mixed-precision configuration is bf16 leaves + f32 master: views read
    bf16, updates apply at f32, one rounding per boundary crossing."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    shapes = tuple(tuple(lv.shape) for lv in leaves)
    dtypes = tuple(jnp.dtype(lv.dtype) for lv in leaves)
    sizes = tuple(int(np.prod(s, dtype=np.int64)) for s in shapes)
    offsets = tuple(int(o) for o in
                    np.concatenate([[0], np.cumsum(sizes)[:-1]])
                    ) if sizes else ()
    n = int(sum(sizes))
    p = -(-max(n, 1) // LANES) * LANES
    if master_dtype is not None:
        dtype = jnp.dtype(master_dtype)
    else:
        dtype = dtypes[0] if all(d == dtypes[0] for d in dtypes) \
            else jnp.dtype(jnp.float32)
    return FlatSpec(shapes, dtypes, sizes, treedef, n, p, dtype, offsets)


def ravel(spec: FlatSpec, tree: PyTree, client_dims: int = 0) -> jax.Array:
    """Concat all leaves into ``(*lead, P)`` — ``client_dims`` leading axes
    (client / round stacking) are preserved; the tail pads with zeros."""
    leaves = jax.tree_util.tree_flatten(tree)[0]
    lead = tuple(leaves[0].shape[:client_dims])
    flat = jnp.concatenate(
        [lv.astype(spec.dtype).reshape(lead + (-1,)) for lv in leaves],
        axis=-1)
    if spec.p != spec.n:
        pad = jnp.zeros(lead + (spec.p - spec.n,), spec.dtype)
        flat = jnp.concatenate([flat, pad], axis=-1)
    return flat


def ravel_rows(spec: FlatSpec, tree: PyTree) -> jax.Array:
    """``ravel(spec, tree, client_dims=1)`` for the in-scan hot path,
    built from a ``dynamic_update_slice`` chain instead of one
    ``concatenate``: XLA:CPU fuses a multi-operand concat with its
    producers into per-element multi-way index selection (~5× the memcpy
    cost, measured on the round benchmark), while the DUS chain aliases
    the output buffer and lowers to one region write per leaf."""
    leaves = jax.tree_util.tree_flatten(tree)[0]
    m = leaves[0].shape[0]
    buf = jnp.zeros((m, spec.p), spec.dtype)
    off = 0
    for lv in leaves:
        rows = lv.astype(spec.dtype).reshape(m, -1)
        buf = jax.lax.dynamic_update_slice(buf, rows, (0, off))
        off += rows.shape[1]
    return buf


def unravel(spec: FlatSpec, flat: jax.Array, client_dims: int = 0) -> PyTree:
    """Inverse of ``ravel``: static slices + reshapes back to leaf dtypes
    (free at the loss boundary — XLA fuses slices of a contiguous buffer)."""
    lead = tuple(flat.shape[:client_dims])
    leaves, off = [], 0
    for shape, dtype, size in zip(spec.shapes, spec.dtypes, spec.sizes):
        piece = jax.lax.slice_in_dim(flat, off, off + size, axis=-1)
        leaves.append(piece.reshape(lead + shape).astype(dtype))
        off += size
    return jax.tree_util.tree_unflatten(spec.treedef, leaves)


# ---------------------------------------------------------------------------
# view table: flat-native model apply (DESIGN.md §13)
# ---------------------------------------------------------------------------

def leaf_view(spec: FlatSpec, flat: jax.Array, i: int,
              client_dims: int = 0) -> jax.Array:
    """Leaf ``i`` as a view of the buffer: ``dynamic_slice`` at the view
    table's static offset, reshaped to the leaf shape and cast to the leaf
    dtype.  A contiguous slice of a contiguous buffer reshapes without
    moving data, so XLA folds the view into its consumer."""
    lead = tuple(flat.shape[:client_dims])
    piece = jax.lax.dynamic_slice_in_dim(flat, spec.offsets[i],
                                         spec.sizes[i], axis=-1)
    return piece.reshape(lead + spec.shapes[i]).astype(spec.dtypes[i])


def view_tree(spec: FlatSpec, flat: jax.Array,
              client_dims: int = 0) -> PyTree:
    """The model pytree as per-leaf VIEWS of the flat buffer — what the
    apply function consumes in place of real parameters.  Numerically this
    is ``unravel``; structurally it is the read half of the flat-native
    loss boundary: ``flat_value_and_grad`` differentiates with respect to
    these views (never through the slices), so the round's only tree is
    the transient one inside the loss jaxpr."""
    leaves = [leaf_view(spec, flat, i, client_dims)
              for i in range(len(spec.sizes))]
    return jax.tree_util.tree_unflatten(spec.treedef, leaves)


def flat_cotangent(spec: FlatSpec, tree: PyTree,
                   client_dims: int = 0) -> jax.Array:
    """Accumulate per-leaf cotangents into ONE ``(*lead, P)`` buffer at the
    master dtype — the write half of the flat-native boundary.  A
    ``dynamic_update_slice`` chain (one region write per leaf, the
    ``ravel_rows`` rationale) rather than the slice-transpose pad+add
    chain ``jax.grad``-through-``view_tree`` would emit; the pad tail
    stays exactly zero."""
    leaves = jax.tree_util.tree_flatten(tree)[0]
    lead = tuple(leaves[0].shape[:client_dims])
    buf = jnp.zeros(lead + (spec.p,), spec.dtype)
    zeros = (0,) * len(lead)
    for lv, off in zip(leaves, spec.offsets):
        rows = lv.astype(spec.dtype).reshape(lead + (-1,))
        buf = jax.lax.dynamic_update_slice(buf, rows, zeros + (off,))
    return buf


def flat_apply(spec: FlatSpec, apply_fn: Callable, flat_params: jax.Array,
               *args, client_dims: int = 0, **kwargs):
    """Run a tree-signature model function on the flat buffer:
    ``apply_fn(params_tree, *args, **kwargs)`` with ``params_tree`` the
    view table's slices of ``flat_params`` — e.g.
    ``flat_apply(spec, functools.partial(lm_loss, cfg=cfg), buf, batch)``.
    The caller never materializes or owns the tree."""
    return apply_fn(view_tree(spec, flat_params, client_dims), *args,
                    **kwargs)


def flat_value_and_grad(spec: FlatSpec,
                        loss_fn: Callable[[PyTree, PyTree], jax.Array]):
    """``vag(flat_row, batch) -> (loss, flat_grad_row)`` — the flat-native
    ``value_and_grad``: loss evaluated on buffer views, gradient returned
    as one ``(P,)`` cotangent buffer.

    Differentiation is with respect to the *views* (the tree), not the
    buffer: the boundary slices/casts sit outside the differentiated
    function, so their transposes (per-leaf pad+add on the full buffer)
    never appear; the cotangent re-enters the flat layout through
    ``flat_cotangent``'s region writes.  With leaf dtype == master dtype
    this is op-for-op the classic unravel→grad→ravel boundary (the golden
    pins hold bit-for-bit); under ``master_dtype`` mixed precision the
    view cast is the ONLY f32→bf16 crossing and the cotangent accumulates
    at master (f32) precision."""
    vag = jax.value_and_grad(loss_fn)

    def run(flat_row: jax.Array, batch: PyTree):
        with jax.named_scope("fed.flat_boundary"):
            views = view_tree(spec, flat_row)
        loss, g = vag(views, batch)
        with jax.named_scope("fed.flat_boundary"):
            return loss, flat_cotangent(spec, g)

    return run


def quantize_int8_flat(spec: FlatSpec, mat: jax.Array) -> jax.Array:
    """``stages.quantize_int8`` natively on ``(M, P)`` rows: the scale is
    per-client-per-LEAF, so each view-table segment quantizes against its
    own row-wise amax — segment slices in, region writes out, keeping the
    exact tree semantics (amax is order-exact; the round/scale arithmetic
    runs in f32 and re-rounds through the leaf dtype) without the
    unravel→quantize→ravel tree round-trip the flat transmit used to pay.
    The pad tail is untouched (zeros), and each segment's amax runs through
    the shared masked reduction (``qops.row_scales``) so no scale can ever
    see a column outside its leaf's true extent."""
    m = mat.shape[0]
    out = jnp.zeros((m, spec.p), spec.dtype)
    for off, size, dtype in zip(spec.offsets, spec.sizes, spec.dtypes):
        seg = jax.lax.dynamic_slice_in_dim(mat, off, size, axis=-1)
        a = seg.astype(dtype)                       # the tree path's leaf
        af = a.astype(jnp.float32)
        scale = qops.row_scales(af, size, 127)
        q = (jnp.round(af / scale) * scale).astype(dtype)
        out = jax.lax.dynamic_update_slice(
            out, q.astype(spec.dtype), (0, off))
    return out


def flatten_state(spec: FlatSpec, state: dict) -> dict:
    """Tree round state → flat round state (same keys; params/ν/server
    moments become (P,) buffers, ν⁽ⁱ⁾ an (M, P) matrix).  Compression
    residuals / broadcast carries (``compress.FLAT_STATE_KEYS``) are
    flat-NATIVE on both layouts — the tree round compresses through the
    view table — so they pass through unchanged; the (M,) client-health
    vectors (``robust.ROBUST_STATE_KEYS``) are layout-independent and do
    the same."""
    out = {}
    for k, v in state.items():
        if (k == "round" or k in compress.FLAT_STATE_KEYS
                or k in robust_mod.ROBUST_STATE_KEYS):
            out[k] = v
        elif k == "nu_i":
            out[k] = ravel(spec, v, client_dims=1)
        else:
            out[k] = ravel(spec, v)
    return out


def unflatten_state(spec: FlatSpec, state: dict) -> dict:
    out = {}
    for k, v in state.items():
        if (k == "round" or k in compress.FLAT_STATE_KEYS
                or k in robust_mod.ROBUST_STATE_KEYS):
            out[k] = v
        elif k == "nu_i":
            out[k] = unravel(spec, v, client_dims=1)
        else:
            out[k] = unravel(spec, v)
    return out


def _use_pallas_default(use_pallas: Optional[bool]) -> bool:
    """The Pallas kernels are the TPU hot path; elsewhere the flat update
    runs the kernels package's jnp oracle — ONE fused XLA op on the flat
    buffer, bitwise-equal to the kernel (same convention as
    ``ops.calibrated_update_tree``; interpret-mode Pallas lowers to ~19
    HLO ops of grid bookkeeping, pure overhead inside a scanned round)."""
    return backend.on_tpu() if use_pallas is None else use_pallas


# ---------------------------------------------------------------------------
# stage 1 (flat): the kernel-backed client k-step scan
# ---------------------------------------------------------------------------

def make_flat_client_update(spec: FlatSpec,
                            loss_fn: Callable[[PyTree, PyTree], jax.Array],
                            algo: Algorithm, *, lr: float, k_max: int,
                            track_nu: str = "delta",
                            use_pallas: Optional[bool] = None,
                            interpret: Optional[bool] = None,
                            per_client_anchor: bool = False):
    """Flat analogue of ``stages.make_client_update``: ``f(anchor, c_all,
    batches, k_steps, lam) -> (x_i, g0_i, acc_i, loss0, client_steps)`` on
    (M, P) rows, ``client_steps`` the local steps computed.  ``c_all`` is
    ignored for algorithms without ν.

    Each local step is ONE fused calibrated-update launch on a flat row
    instead of ``num_leaves`` tree_map dispatches — the Pallas kernel on
    TPU (``use_pallas``), its jnp oracle elsewhere (interpret-mode Pallas
    lowers to ~19 HLO ops of grid bookkeeping, pure overhead inside a
    loop).  The per-step loss boundary is flat-native:
    ``flat_value_and_grad`` evaluates the loss on view-table slices of the
    row and returns the gradient as a (P,) cotangent buffer — the tree
    exists only inside the loss jaxpr (DESIGN.md §13).

    Step asynchronism takes one of two forms, chosen from where the client
    rows live (DESIGN.md §3):

    * rows on one device: the clients run one after another, each a
      ``while_loop`` of exactly its own K_i steps — Σ K_i client-steps a
      round, nothing computed and thrown away, no mask;
    * rows spread over the mesh's data axes (``dist.axis_size("cl") > 1``,
      the SPMD launch round): a ``k_max``-step scan vmapped over the
      clients, rows past their K_i masked — M · k_max client-steps, the
      devices running their clients in parallel.

    Either way a mid-round dropout's k′ < K_i (partial-work recovery,
    fed/scenarios.py, DESIGN.md §12) arrives as ``k_steps``: it bounds the
    client's loop, or masks its row past k′ — the flat path needs no
    separate fault machinery.
    """
    use_pallas = _use_pallas_default(use_pallas)
    needs_first = algo.selector in ("fedagrac", "first", "reverse")
    uses_nu = algo.uses_nu
    explicit_nu = track_nu == "explicit" and uses_nu
    # the tree path adds the prox term into g BEFORE the g₀ select and the
    # explicit-ν accumulation (stages.make_client_update); when either
    # consumer exists the flat path must augment g the same way and use
    # the PLAIN update — fusing prox into the kernel is only valid when
    # nothing downstream reads the gradient (the FedProx-style baselines)
    fuse_prox = bool(algo.prox_mu) and not (needs_first or explicit_nu)
    if use_pallas:
        interpret = not backend.on_tpu() if interpret is None else interpret

    def update(x, g, c, anchors, eta, lam):
        """``x − η(g + λc [+ μ(x − x₀)])`` on flat rows, ``eta`` a scalar
        or one step size per row (η_i = 0 leaves row i exactly as it is,
        for finite operands); f32 arithmetic, one rounding to the row
        dtype — the kernel's."""
        xf = x.astype(jnp.float32)
        t = g.astype(jnp.float32)
        if uses_nu:
            t = t + lam * c.astype(jnp.float32)
        if fuse_prox:
            t = t + algo.prox_mu * (xf - anchors.astype(jnp.float32))
        return (xf - eta * t).astype(x.dtype)

    @jax.named_scope("fed.local_step")
    def local_step(x, g, c, anchor, lam):
        """One client's unmasked step on its (P,) row.  The kernel sees
        the row as (P / 128, 128) — a bitcast of the contiguous row whose
        (512, 128) tiles stream whole, where a (1, P) row would pad every
        tile to 8 sublanes."""
        if not use_pallas:
            return update(x, g, c, anchor, jnp.float32(lr), lam)
        rows = lambda a: a.reshape(-1, LANES)
        if fuse_prox:
            out = calibrated_update_prox_2d(rows(x), rows(g), rows(c),
                                            rows(anchor), lr, lam,
                                            algo.prox_mu,
                                            interpret=interpret)
        else:
            out = calibrated_update_2d(rows(x), rows(g), rows(c), lr, lam,
                                       interpret=interpret)
        return out.reshape(x.shape)

    @jax.named_scope("fed.local_step")
    def masked_step(x, g, c, anchors, k, k_steps, lam):
        """All (M, P) rows at step k, rows with k ≥ K_i left unchanged: the
        mask folds into a per-row step size η_i ∈ {η, 0}, no separate
        (M, P) select.  Always the oracle — the rows are spread over the
        mesh here, and XLA cannot partition a Mosaic call."""
        eta = jnp.where(k < k_steps, jnp.float32(lr), 0.0)[:, None]
        return update(x, g, c, anchors, eta, lam)

    # flat-native loss boundary (DESIGN.md §13): losses on buffer VIEWS,
    # gradients straight back as (P,) cotangent rows — the round never
    # holds the parameter tree, and under master_dtype mixed precision the
    # view cast is the only master→compute crossing
    grad_row = flat_value_and_grad(spec, loss_fn)

    def serial(anchor, c_k, batches, k_steps, lam_k):
        """Clients in turn (an outer scan over the rows), each running a
        while_loop of exactly min(K_i, k_max) steps."""
        zero_row = jnp.zeros((spec.p,), spec.dtype)

        def client(_, xs):
            anchor_i = xs["anchor"] if per_client_anchor else anchor
            c_i = xs["c"] if uses_nu else zero_row
            batch_i, k_i = xs["batch"], xs["k"]
            w = 1.0 / k_i.astype(jnp.float32)

            def step(carry):
                k, x, g0, acc, loss0 = carry
                batch = jax.tree.map(
                    lambda b: jax.lax.dynamic_index_in_dim(b, k, 0, False),
                    batch_i)
                loss, g = grad_row(x, batch)
                if algo.prox_mu and not fuse_prox:
                    g = g + algo.prox_mu * (x - anchor_i)
                x = local_step(x, g, c_i, anchor_i, lam_k)
                first = k == 0
                loss0 = jnp.where(first, loss.astype(jnp.float32), loss0)
                if needs_first:
                    g0 = jax.lax.cond(first, lambda: g, lambda: g0)
                if explicit_nu:
                    acc = acc + w * g
                return k + 1, x, g0, acc, loss0

            n_i = jnp.minimum(k_i, k_max)
            carry = (jnp.int32(0), anchor_i,
                     zero_row if needs_first else jnp.zeros(()),
                     zero_row if explicit_nu else jnp.zeros(()),
                     jnp.float32(0.0))
            k, x, g0, acc, loss0 = jax.lax.while_loop(
                lambda c: c[0] < n_i, step, carry)
            return None, (x, g0, acc, loss0, k)

        xs = {"batch": batches, "k": k_steps}
        if per_client_anchor:
            xs["anchor"] = anchor
        if uses_nu:
            xs["c"] = c_k
        _, (x, g0, acc, loss0, ran) = jax.lax.scan(client, None, xs)
        return (x, g0 if needs_first else jnp.zeros(()),
                acc if explicit_nu else jnp.zeros(()), loss0, jnp.sum(ran))

    grad_rows = jax.vmap(grad_row)

    def masked(anchor, c_k, batches, k_steps, lam_k):
        """The k_max-step scan over the whole client axis, rows past their
        K_i masked (same order the vmapped tree scan lowers to)."""
        m = k_steps.shape[0]
        anchors = (anchor if per_client_anchor
                   else jnp.broadcast_to(anchor[None], (m, spec.p)))
        if not uses_nu:
            c_k = jnp.zeros((m, spec.p), spec.dtype)
        bk = jax.tree.map(lambda b: jnp.swapaxes(b, 0, 1), batches)

        def step(carry, xs):
            k, batch_k = xs
            x, g0, acc = carry
            loss, g = grad_rows(x, batch_k)
            if algo.prox_mu and not fuse_prox:
                g = g + algo.prox_mu * (x - anchors)
            x = masked_step(x, g, c_k, anchors, k, k_steps, lam_k)
            if needs_first:
                g0 = jnp.where(k == 0, g, g0)
            if explicit_nu:
                w = jnp.where(k < k_steps,
                              1.0 / k_steps.astype(jnp.float32), 0.0)
                acc = acc + w[:, None] * g
            return (x, g0, acc), loss

        zeros = jnp.zeros((m, spec.p), spec.dtype)
        (x, g0, acc), losses = jax.lax.scan(
            step, (anchors, zeros if needs_first else jnp.zeros(()),
                   zeros if explicit_nu else jnp.zeros(())),
            (jnp.arange(k_max), bk))
        return x, g0, acc, losses[0], jnp.int32(m * k_max)

    @jax.named_scope("fed.client_update")
    def run(anchor, c_all, batches, k_steps, lam):
        # λ multiplies a zero c for ν-free algorithms — bake λ = 0 so the
        # kernel's λ·c term vanishes exactly (x − η(g + 0) ≡ x − ηg)
        lam_k = lam if uses_nu else 0.0
        path = masked if dist.axis_size("cl") > 1 else serial
        return path(anchor, c_all, batches, k_steps, lam_k)

    return run


@jax.named_scope("fed.orientation")
def _flat_transmit(spec: FlatSpec, algo: Algorithm, params0, x_i, g0_i,
                   acc_i, c_all, kf, kbar, lr, lam, *,
                   track_nu: str = "delta", quantize_transmit: bool = False,
                   anchor_i=None):
    """``stages.orientation_transmit`` on flat matrices.  The stage
    functions are array-polymorphic so this is a thin wrapper — except
    int8 fake-quantization, whose scale is per-client-per-LEAF:
    ``quantize_int8_flat`` runs it segment-wise on the view table (exact
    tree semantics, no unravel→ravel round-trip)."""
    if quantize_transmit:
        if track_nu == "explicit":
            avg_g = acc_i
        else:
            avg_g = stages.recover_avg_grad(params0, x_i, c_all, kf, lr,
                                            lam, anchor_i=anchor_i)
        transmit = stages.SELECTORS[algo.selector](
            g0_i, avg_g, stages.fast_mask(kf, kbar))
        transmit = quantize_int8_flat(spec, transmit)
        return transmit, avg_g
    return stages.orientation_transmit(
        algo, params0, x_i, g0_i, acc_i, c_all, kf, kbar, lr, lam,
        track_nu=track_nu, anchor_i=anchor_i)


# ---------------------------------------------------------------------------
# composition: the flat synchronous round
# ---------------------------------------------------------------------------

def make_flat_round(spec: FlatSpec,
                    loss_fn: Callable[[PyTree, PyTree], jax.Array],
                    algo: Algorithm, *, lr: float, k_max: int,
                    track_nu: str = "delta",
                    quantize_transmit: bool = False,
                    compression=None, robust=None, attack=None,
                    use_pallas: Optional[bool] = None,
                    param_constraint: Optional[Callable[[jax.Array, int],
                                                        jax.Array]] = None):
    """Flat twin of ``stages.make_layered_round``: same signature
    ``round_fn(state, batches, k_steps, weights, lam=None)``, state leaves
    flat (``flatten_state``).  Aggregation / orientation / server-opt call
    the SAME registry functions as the tree round — on one (M, P) leaf.
    The compression stage (core/compress.py) — and likewise the
    corruption/defense bracket (``attack``/``robust``, DESIGN.md §16) —
    is flat-NATIVE here: every transmitted quantity already lives on
    (rows, P), so the codecs apply with no ravel bridge."""
    client_update = make_flat_client_update(
        spec, loss_fn, algo, lr=lr, k_max=k_max, track_nu=track_nu,
        use_pallas=use_pallas)
    aggregate = stages.AGGREGATORS[algo.aggregator]
    cs = compress.build_stages(compression, spec, algo.uses_nu,
                               use_pallas=use_pallas)
    rb = robust_mod.build_round_robust(robust, spec, algo.uses_nu)
    atk = attack if (attack is not None
                     and attack.corrupts_payload) else None
    wire = cs is not None or rb is not None or atk is not None
    down_on = cs is not None and cs.down is not None
    up_on = cs is not None and cs.up is not None

    def constrain(arr, client_dims):
        if param_constraint is None:
            return arr
        return param_constraint(arr, client_dims)

    def round_fn(state: dict, batches: PyTree, k_steps: jax.Array,
                 weights: jax.Array, lam=None):
        if lam is None:
            lam = algo.lam
        params0 = state["params"]                          # (P,)
        kbar = jnp.dot(weights, k_steps.astype(jnp.float32))
        new_state = dict(state)

        if down_on:
            anchor = cs.down(params0, state, new_state)
            nu_bc = (cs.down_nu(state["nu"], state, new_state)
                     if algo.uses_nu else None)
        else:
            anchor = params0
            nu_bc = state["nu"] if algo.uses_nu else None

        c_all = (stages.corrections(nu_bc, state["nu_i"])
                 if algo.uses_nu else None)                # (M, P)

        x_i, g0_i, acc_i, loss0, client_steps = client_update(
            anchor, c_all, batches, k_steps, lam)
        x_i = constrain(x_i, 1)
        kf = k_steps.astype(jnp.float32)

        w_agg = weights
        if wire:
            d = x_i - anchor[None]
            if atk is not None:
                d = atk.corrupt_delta(state["round"], d, spec.n,
                                      ids=jnp.arange(x_i.shape[0],
                                                     dtype=jnp.int32))
            if up_on:
                d = cs.up(d, state, new_state)
            if rb is not None:
                d, w_agg, qcount = rb.model(
                    d, weights, state, new_state, state["round"],
                    jnp.arange(x_i.shape[0], dtype=jnp.int32))
            x_srv = anchor[None] + d
        else:
            x_srv = x_i

        agg = aggregate(anchor, x_srv, kf, w_agg, kbar)
        if down_on:
            # clients averaged around the broadcast x̂; re-base the result
            # onto the TRUE master so downlink error never accumulates
            # into the server trajectory: x⁺ = x + (agg − x̂)
            agg = (params0.astype(jnp.float32) + agg.astype(jnp.float32)
                   - anchor.astype(jnp.float32)).astype(spec.dtype)
        new_params = stages.server_update(algo, state, params0, agg,
                                          new_state)
        new_params = constrain(new_params, 0)
        new_state["params"] = new_params
        new_state["round"] = state["round"] + 1

        if algo.uses_nu:
            transmit, avg_g = _flat_transmit(
                spec, algo, anchor, x_i, g0_i, acc_i, c_all, kf, kbar, lr,
                lam, track_nu=track_nu,
                quantize_transmit=quantize_transmit)
            w_nu = weights
            if atk is not None:
                transmit = atk.corrupt_nu(
                    state["round"], transmit, spec.n,
                    ids=jnp.arange(x_i.shape[0], dtype=jnp.int32))
            if up_on:
                transmit = cs.up_nu(transmit, state, new_state)
            if rb is not None:
                transmit, w_nu = rb.nu(
                    transmit, weights, state, state["round"],
                    jnp.arange(x_i.shape[0], dtype=jnp.int32))
            new_state["nu"] = constrain(stages.transmit_mix(w_nu, transmit),
                                        0)
            new_state["nu_i"] = constrain(avg_g, 1)

        if rb is not None:
            new_state["params"] = rb.guard(new_state["params"], params0)
            if algo.uses_nu:
                new_state["nu"] = rb.guard(new_state["nu"], state["nu"])
                new_state["nu_i"] = rb.guard(new_state["nu_i"],
                                             state["nu_i"])

        metrics = {"loss": jnp.dot(weights, loss0), "kbar": kbar,
                   "client_steps": client_steps}
        if rb is not None:
            metrics["quarantined"] = qcount
        return new_state, metrics

    return round_fn


# ---------------------------------------------------------------------------
# composition: the flat cohort round (partial participation)
# ---------------------------------------------------------------------------

def make_flat_cohort_round(spec: FlatSpec,
                           loss_fn: Callable[[PyTree, PyTree], jax.Array],
                           algo: Algorithm, *, lr: float, k_max: int,
                           nu_decay: float = 0.0,
                           track_nu: str = "delta",
                           quantize_transmit: bool = False,
                           compression=None, robust=None, attack=None,
                           use_pallas: Optional[bool] = None,
                           param_constraint: Optional[Callable] = None):
    """Flat twin of ``stages.make_cohort_round``: the cohort's ν⁽ⁱ⁾ gather
    and the post-round scatter are pure ROW indexing on the (M_pop, P)
    matrix — no per-leaf gather chains (DESIGN.md §10, §11).  Uplink
    error-feedback rows gather/scatter at the cohort ids, so absentees'
    residuals wait untouched for their next report."""
    client_update = make_flat_client_update(
        spec, loss_fn, algo, lr=lr, k_max=k_max, track_nu=track_nu,
        use_pallas=use_pallas)
    aggregate = stages.BUFFERED_AGGREGATORS[algo.aggregator]
    cs = compress.build_stages(compression, spec, algo.uses_nu,
                               use_pallas=use_pallas)
    rb = robust_mod.build_round_robust(robust, spec, algo.uses_nu)
    atk = attack if (attack is not None
                     and attack.corrupts_payload) else None
    wire = cs is not None or rb is not None or atk is not None
    down_on = cs is not None and cs.down is not None
    up_on = cs is not None and cs.up is not None

    def constrain(arr, client_dims):
        if param_constraint is None:
            return arr
        return param_constraint(arr, client_dims)

    def round_fn(state: dict, batches: PyTree, cohort: jax.Array,
                 k_steps: jax.Array, cweights: jax.Array, lam=None):
        if lam is None:
            lam = algo.lam
        params0 = state["params"]
        kf = k_steps.astype(jnp.float32)
        mass = jnp.sum(cweights)
        kbar = jnp.dot(cweights, kf) / mass
        new_state = dict(state)

        if down_on:
            anchor = cs.down(params0, state, new_state)
            nu_bc = (cs.down_nu(state["nu"], state, new_state)
                     if algo.uses_nu else None)
        else:
            anchor = params0
            nu_bc = state["nu"] if algo.uses_nu else None

        c_all = (stages.corrections(nu_bc, state["nu_i"], cohort)
                 if algo.uses_nu else None)                # (C, P) rows

        x_i, g0_i, acc_i, loss0, client_steps = client_update(
            anchor, c_all, batches, k_steps, lam)
        x_i = constrain(x_i, 1)

        w_agg = cweights
        if wire:
            d = x_i - anchor[None]
            if atk is not None:
                d = atk.corrupt_delta(state["round"], d, spec.n, ids=cohort)
            if up_on:
                d = cs.up(d, state, new_state, ids=cohort)
            if rb is not None:
                d, w_agg, qcount = rb.model(d, cweights, state, new_state,
                                            state["round"], cohort)
            x_srv = anchor[None] + d
        else:
            x_srv = x_i

        # buffered aggregator takes base and anchors separately: base is
        # the TRUE master, deltas measured vs the broadcast — no re-base
        agg = aggregate(params0, anchor[None], x_srv, kf, w_agg, kbar)
        new_params = stages.server_update(algo, state, params0, agg,
                                          new_state)
        new_params = constrain(new_params, 0)
        new_state["params"] = new_params
        new_state["round"] = state["round"] + 1

        if algo.uses_nu:
            transmit, avg_g = _flat_transmit(
                spec, algo, anchor, x_i, g0_i, acc_i, c_all, kf, kbar, lr,
                lam, track_nu=track_nu,
                quantize_transmit=quantize_transmit)
            w_nu = cweights
            if atk is not None:
                transmit = atk.corrupt_nu(state["round"], transmit, spec.n,
                                          ids=cohort)
            if up_on:
                transmit = cs.up_nu(transmit, state, new_state, ids=cohort)
            if rb is not None:
                transmit, w_nu = rb.nu(transmit, cweights, state,
                                       state["round"], cohort)
            contrib = stages.transmit_mix(w_nu, transmit)
            new_nu = stages.nu_mass_mix(state["nu"], contrib, mass)
            new_state["nu"] = constrain(new_nu, 0)
            new_state["nu_i"] = constrain(
                stages.scatter_nu_rows(state["nu_i"], new_nu, avg_g,
                                       cohort, nu_decay), 1)

        if rb is not None:
            new_state["params"] = rb.guard(new_state["params"], params0)
            if algo.uses_nu:
                new_state["nu"] = rb.guard(new_state["nu"], state["nu"])
                new_state["nu_i"] = rb.guard(new_state["nu_i"],
                                             state["nu_i"])

        metrics = {"loss": jnp.dot(cweights, loss0) / mass, "kbar": kbar,
                   "mass": mass, "client_steps": client_steps}
        if rb is not None:
            metrics["quarantined"] = qcount
        return new_state, metrics

    return round_fn
