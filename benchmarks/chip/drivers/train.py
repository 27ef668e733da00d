"""Training driver: the FedaGrac LM round under step asynchronism, driven
through ``FederatedSimulation.run`` on the flat layout.

Set-up: the model's weights and each client's token stream from
``--seed``; the K_i schedule of the mix (fixed by its ``k_seed``, so
k_max and every program shape are the same for every seed); one
simulation, whose first chunk of rounds compiles (or loads) the chunk
program and is the first steps the check compares.  Window: whole chunks
of the same simulation, each ending in ``block_until_ready`` inside
``run``, for as long as one more chunk still ends within ``--seconds`` (at
least one); with ``--trace 1`` one chunk, traced.

``train_tokens_per_s`` counts useful tokens only: Σ_i K_i · batch · seq
per round — the tokens of the K_i steps each client keeps, not of the
k_max steps every client computes — over the window's whole wall time.

Check, after the window and with the program's state freed: the plain
reference (``reference/fedagrac.py`` over ``reference/<config>.py``)
runs the first chunk's rounds from the same weights on the same rows, and
the numbers that ``limits/<cell>.json`` names are compared with their
limits (``compare`` reads them all):

* ``loss_gap_r<t>``: |program − reference| of round t's loss;
* ``*_gap``: for ν and for the parameters' change over the chunk, a
  leaf's | ‖program‖ − ‖reference‖ | over the larger of the reference
  leaf's norm and the median leaf's norm;
* ``*_diff``: the same with ‖program − reference‖, the norm of the
  difference, which sees a change of direction as well as of size; also of
  the clients' ν⁽ⁱ⁾ rows;

each at the worst leaf and at the median leaf (``_median``).  Leaves whose
reference ν is under a thousandth of the median leaf's are left out.
"""
from __future__ import annotations

import functools
import gc
import json
import sys
import time

import numpy as np

class Feed:
    """The simulation's batch source: ``chunk_batches`` gathers the next
    rounds' rows from the device token table; every chunk takes fresh rows
    (its own round counter), the same for the program and the reference."""

    def __init__(self, table, rows):
        import jax
        import jax.numpy as jnp
        self.table = table
        self.rows = rows
        self.next_round = 0
        m = table.shape[0]

        def gather(table, idx):
            ci = jnp.arange(m)[None, :, None, None]
            seqs = table[ci, idx]                 # (r, M, k_max, B, S + 1)
            return {"tokens": seqs[..., :-1], "labels": seqs[..., 1:]}

        self._gather = jax.jit(gather)

    def rows_for(self, t0: int, r: int) -> np.ndarray:
        return self.rows[np.arange(t0, t0 + r) % len(self.rows)]

    def chunk_batches(self, t0: int, r: int, k_max: int) -> dict:
        import jax.numpy as jnp
        idx = self.rows_for(self.next_round, r)
        self.next_round += r
        return self._gather(self.table, jnp.asarray(idx))


def leaf_gaps(prog: list, ref: list, keep: np.ndarray) -> np.ndarray:
    """Per kept leaf, |‖p‖ − ‖r‖| / max(‖r‖, median ‖r‖)."""
    pn = np.array([np.linalg.norm(p) for p in prog])
    rn = np.array([np.linalg.norm(r) for r in ref])
    return (np.abs(pn - rn) / np.maximum(rn, np.median(rn)))[keep]


def leaf_diffs(prog: list, ref: list, keep: np.ndarray) -> np.ndarray:
    """Per kept leaf, ‖p − r‖ / max(‖r‖, median ‖r‖)."""
    dn = np.array([np.linalg.norm(p - r) for p, r in zip(prog, ref)])
    rn = np.array([np.linalg.norm(r) for r in ref])
    return (dn / np.maximum(rn, np.median(rn)))[keep]


def split(flat: np.ndarray, sizes: list) -> list:
    out, off = [], 0
    for n in sizes:
        out.append(flat[off:off + n])
        off += n
    return out


def build(ctx) -> dict:
    """What a run is made of, from the cell's files and ``--seed``: the
    program's model config, the K schedule, the weights (on the device)
    and the feed.  Shared by the run and by the control."""
    import generate

    tr = ctx.traffic
    mc = ctx.model_config()
    ks = generate.k_schedule(tr)
    k_max = int(ks.max())
    r = tr["chunk_rounds"]
    if r % len(ks):
        raise ValueError("a chunk must hold whole K schedules")
    table = generate.token_table(generate_key(ctx.seed), tr, mc.vocab)
    return {"mc": mc, "ks": ks, "k_max": k_max, "r": r,
            "params": ctx.make_weights(mc),
            "feed": Feed(table, generate.round_rows(ctx.seed, tr, k_max, 64)),
            "useful_per_chunk": int(ks.sum()) * (r // len(ks)) * tr["batch"]
            * tr["seq"]}


def drive(ctx) -> dict:
    import jax

    from repro.configs.base import FedConfig
    from repro.fed import FederatedSimulation
    from repro.models import model as model_lib

    tr, cfg = ctx.traffic, ctx.config
    run = build(ctx)
    mc, ks, k_max, r, feed = (run[k] for k in ("mc", "ks", "k_max", "r",
                                               "feed"))
    params = run.pop("params")
    fed = FedConfig(algorithm=tr["algorithm"], n_clients=tr["clients"],
                    lr=tr["lr"], calibration_rate=tr["calibration_rate"],
                    param_layout="flat", master_dtype=cfg["master_dtype"])
    sim = FederatedSimulation(functools.partial(model_lib.lm_loss, cfg=mc),
                              params, fed, feed, k_schedule=ks)
    host0 = jax.device_get(params)
    del params
    # the first chunk: compiles (or loads) the chunk program; its rounds are
    # the steps the check follows
    with ctx.span("bench.chunk"):
        hist = sim.run(r, eval_every=r)
    first = {key: np.asarray(jax.device_get(sim.state[key]))
             for key in ("params", "nu", "nu_i")}
    first["losses"] = list(hist.loss)
    setup_s = time.perf_counter() - ctx.t0

    chunks = 0
    with ctx.window():
        tic = time.perf_counter()
        while True:
            with ctx.span("bench.chunk"):
                sim.run(r, eval_every=r)
            chunks += 1
            spent = time.perf_counter() - tic
            # stop where one more chunk would overrun the window
            if ctx.trace or spent * (chunks + 1) / chunks > ctx.seconds:
                break
    useful = chunks * run["useful_per_chunk"]
    peak = ctx.memory_peak()
    print(f"memory_stats: {json.dumps(jax.devices()[0].memory_stats())}",
          file=sys.stderr, flush=True)
    ctx.values.update(
        useful_tokens=useful, chunks=chunks, k_max=k_max,
        useful_share=float(ks.sum()) / (ks.size * k_max),
        flops_per_token=ctx.module("flops").train_per_token(cfg,
                                                             tr["seq"]),
        attention={"n_heads": mc.n_heads, "n_kv_heads": mc.n_kv_heads,
                   "head_dim": mc.resolved_head_dim})
    del sim, hist
    gc.collect()

    checks = check(ctx, host0, first, feed, ks, r)
    return {"e2e": {"train_tokens_per_s": useful / ctx.window_s,
                    "setup_s": setup_s},
            "attempted": chunks * r, "failed": 0,
            "memory_peak_bytes": peak, "checks": checks}


def generate_key(seed: int):
    import jax
    import harness
    return jax.random.fold_in(harness.seed_key(seed), 1)


def reference_run(ctx, host0, feed, ks, r, **kw) -> dict:
    """The reference's rounds over the first chunk's rows."""
    import jax
    import jax.numpy as jnp
    fedagrac = ctx.module("reference", "fedagrac")
    model = ctx.module("reference")
    table = np.asarray(jax.device_get(feed.table))
    idx = feed.rows_for(0, r)
    batches = [{"tokens": table[np.arange(table.shape[0])[:, None, None],
                                idx[t]][..., :-1],
                "labels": table[np.arange(table.shape[0])[:, None, None],
                                idx[t]][..., 1:]} for t in range(r)]
    p0 = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), host0)
    ks_r = ks[np.arange(r) % len(ks)]
    return fedagrac.rounds(model, ctx.config, p0, batches, ks_r,
                           lr=ctx.traffic["lr"],
                           lam=ctx.traffic["calibration_rate"], **kw)


def _leaves(tree) -> list:
    import jax
    return [np.asarray(a, np.float32).ravel() for a in jax.tree.leaves(tree)]


def compare(host0, prog: dict, ref: dict) -> dict:
    """Every reading of the program (or a stand-in) against the reference:
    each round's loss gap, and the worst and the median leaf's norm gap and
    difference of ν and of the parameters' change, and the difference of
    the clients' ν⁽ⁱ⁾ rows.  The cell's limits file names the ones the
    check holds to a limit."""
    import jax
    leaves0 = _leaves(host0)
    sizes = [a.size for a in leaves0]
    ref_nu, ref_x = _leaves(ref["nu"]), _leaves(ref["params"])
    ref_nu_i = [_leaves(t) for t in ref["nu_i"]]
    rn = np.array([np.linalg.norm(v) for v in ref_nu])
    keep = rn >= 1e-3 * np.median(rn)
    if isinstance(prog["params"], np.ndarray):
        p_nu, p_x = split(prog["nu"], sizes), split(prog["params"], sizes)
        p_nu_i = [split(row, sizes) for row in prog["nu_i"]]
    else:
        p_nu, p_x = _leaves(prog["nu"]), _leaves(prog["params"])
        p_nu_i = [_leaves(t) for t in prog["nu_i"]]
    out = {f"loss_gap_r{t}": abs(float(a) - float(b)) for t, (a, b) in
           enumerate(zip(prog["losses"], ref["losses"]))}
    p_dx = [p - x0 for p, x0 in zip(p_x, leaves0)]
    r_dx = [r - x0 for r, x0 in zip(ref_x, leaves0)]
    per_leaf = {"nu_gap": leaf_gaps(p_nu, ref_nu, keep),
                "update_gap": leaf_gaps(p_dx, r_dx, keep),
                "nu_diff": leaf_diffs(p_nu, ref_nu, keep),
                "update_diff": leaf_diffs(p_dx, r_dx, keep)}
    for name, v in per_leaf.items():
        out[name] = float(v.max())
        out[f"{name}_median"] = float(np.median(v))
    out["nu_i_diff_median"] = max(
        float(np.median(leaf_diffs(p, r, keep)))
        for p, r in zip(p_nu_i, ref_nu_i))
    out["left_out"] = int((~keep).sum())
    out = {k: (v if v == v else float("inf")) for k, v in out.items()}
    names = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(host0)[0]]
    names = [n for n, k in zip(names, keep) if k]
    out["leaves"] = {n: [round(float(v[j]), 6) for v in per_leaf.values()]
                     for j, n in enumerate(names)}
    return out


def check(ctx, host0, first, feed, ks, r) -> dict:
    ref = reference_run(ctx, host0, feed, ks, r)
    got = compare(host0, first, ref)
    print(f"losses: program {first['losses']} reference {ref['losses']}",
          file=sys.stderr, flush=True)
    leaves = got.pop("leaves")
    print(f"readings: {json.dumps(got)}", file=sys.stderr, flush=True)
    print(f"leaves (nu gap, update gap, nu diff, update diff): "
          f"{json.dumps(leaves)}",
          file=sys.stderr, flush=True)
    return {name: {"value": got[name], "limit": limit}
            for name, limit in ctx.limits.items()}
