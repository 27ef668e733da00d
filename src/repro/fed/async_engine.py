"""Buffered semi-asynchronous federated execution (DESIGN.md §5, §9).

The synchronous engine (fed/simulation.py) advances in lock-step rounds —
the straggler defines the round clock.  This engine drops the barrier:
clients train continuously, each report arrives after its simulated duration
(fed/clock.py), and the server updates once a **buffer** of M' ≤ M reports
has accumulated (Nguyen et al., FedBuff).  Arrived updates may be **stale**
— computed against a model version τ updates old — and are discounted by a
staleness weight s(τ) (Xie et al., FedAsync):

    constant : s(τ) = 1
    hinge    : s(τ) = 1                 τ ≤ b,   else 1 / (1 + a (τ − b))
    poly     : s(τ) = (1 + τ)^(−a)

The buffered server update on arrivals B with global weights ω and
discounts s_i = s(τ_i), w̃_i = ω_i s_i:

    x       ← serveropt( x,  Σ_{i∈B} w̃_i (x⁽ⁱ⁾ − x_{v_i}) )       (pseudo-deltas)
    ν       ← (1 − Σ_{i∈B} w̃_i) ν  +  Σ_{i∈B} w̃_i transmitᵢ      (mass-mixed)
    ν⁽ⁱ⁾    ← ν̄⁽ⁱ⁾   for i ∈ B only                              (row scatter)

All three reuse the synchronous stages verbatim (core/stages.py): the
client-update scan runs with *per-client anchors* (the stale model version
each client was dispatched with), aggregation uses the pseudo-delta
variants, and orientation recovers ν̄⁽ⁱ⁾ against the same stale anchor.
With buffer = M, identical client speeds and zero staleness, every quantity
above reduces to the synchronous round — FedaGrac-vs-FedAsync-vs-FedBuff is
one config switch (``FedConfig.buffer_size`` / ``staleness``).

Execution is device-resident (DESIGN.md §9).  The event ordering is
deterministic given ``(k_schedule, clock, buffer_size)``, so the whole
heapq simulation is precomputed by ``fed/clock.py::simulate_timeline`` into
numpy arrays; ``run`` then executes updates in scanned chunks.  Stale
anchors come from a bounded device-resident **anchor buffer** of M + 1
model versions — one row per client (its dispatch-time ``(params, ν)``,
rewritten at each re-dispatch) plus a scratch row that absorbs the masked
writes of duplicate same-buffer reporters — replacing the host-side
version→pytree dict.  Reports dispatched *within* the update that consumes
them (duplicate reporters, version == update index) read the live model
instead of the buffer.
"""
from __future__ import annotations

import warnings
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import FedConfig
from repro.core import compress, flat, robust, rounds, stages
from repro.core.fedopt import get_algorithm
from repro.data.partition import gaussian_k_schedule
from repro.fed.clock import ClientClock, Timeline, make_clock, \
    simulate_timeline
from repro.fed.population import ClientPopulation
from repro.fed.scenarios import Scenario, make_scenario
from repro.fed.simulation import History, _check_finite_metric

PyTree = Any


def staleness_weight(tau, mode: str = "constant", a: float = 0.5,
                     b: int = 4) -> np.ndarray:
    """Staleness discount s(τ) ≥ 0, s(0) = 1 (FedAsync §5 shapes)."""
    tau = np.asarray(tau, np.float64)
    if mode == "constant":
        return np.ones_like(tau)
    if mode == "poly":
        return (1.0 + tau) ** (-a)
    if mode == "hinge":
        return 1.0 / (1.0 + a * np.maximum(tau - b, 0.0))
    raise ValueError(f"unknown staleness mode {mode!r}")


class BufferedAsyncSimulation:
    """``run(T)`` executes T buffered server updates of ``fed.algorithm``.

    Mirrors ``FederatedSimulation``'s constructor so benchmarks can switch
    engines on ``fed.buffer_size`` alone.  ``clock`` defaults to the
    ``fed.speed_dist`` wall-clock model; ``k_schedule`` rows index per-client
    *dispatches* (client *i*'s d-th task uses row d), so with buffer = M and
    identical speeds the data stream matches the synchronous engine's.

    Each ``run`` call simulates a fresh timeline from the CURRENT model
    (every client re-dispatched at simulated t = 0 on version 0, anchors
    reset to the current state).
    """

    def __init__(self, loss_fn: Callable[[PyTree, PyTree], jax.Array],
                 params: PyTree, fed: FedConfig, batcher,
                 eval_fn: Optional[Callable[[PyTree], float]] = None,
                 k_schedule: Optional[np.ndarray] = None,
                 lam_schedule: Optional[Callable[[int], float]] = None,
                 clock: Optional[ClientClock] = None,
                 population: Optional[ClientPopulation] = None,
                 scenario: Optional[Scenario] = None,
                 t_max: int = 10_000):
        m = fed.n_clients
        self.fed = fed
        self.algo = get_algorithm(fed.algorithm, fed)
        self.batcher = batcher
        self.eval_fn = eval_fn
        self.lam_schedule = lam_schedule
        self.buffer = fed.buffer_size if fed.buffer_size > 0 else m
        if not 1 <= self.buffer <= m:
            raise ValueError(f"buffer_size {self.buffer} not in [1, {m}]")
        if k_schedule is None:
            k_schedule = gaussian_k_schedule(
                m, fed.k_mean, fed.k_var, t_max,
                mode=fed.k_mode, seed=fed.seed)
        self.k_schedule = k_schedule
        self.k_max = int(k_schedule.max())
        self.clock = clock if clock is not None else make_clock(
            m, dist=fed.speed_dist, sigma=fed.speed_sigma,
            latency=fed.comm_latency, seed=fed.seed)
        self.weights = (np.asarray(batcher.weights)
                        if fed.weights == "data"
                        else np.full((m,), 1.0 / m, np.float32))
        # partial participation (fed/population.py, DESIGN.md §10): the
        # timeline keeps only C = cohort_size tasks in flight, re-filling
        # each freed slot by the population sampler; sampler "all" (C = M)
        # reproduces the legacy always-in-flight stream bit-for-bit
        self.population = (population if population is not None
                           else ClientPopulation.from_config(
                               fed, m=m, weights=self.weights))
        if self.population is not None:
            if self.population.m != m:
                raise ValueError(
                    f"population of {self.population.m} clients does not "
                    f"match fed.n_clients={m}")
            c = self.population.cohort_size
            if not self.population.full_participation:
                # only C tasks are in flight: the buffer must not span more
                # than one concurrency sweep, or Σ w̃ ≈ B/C > 1 and the raw
                # pseudo-delta step overshoots by that factor
                if fed.buffer_size <= 0:
                    self.buffer = c
                elif self.buffer > c:
                    raise ValueError(
                        f"buffer_size {self.buffer} exceeds the population "
                        f"concurrency C={c}; use buffer_size ≤ C (0 "
                        f"defaults to C under partial participation)")
            if clock is None and np.any(self.population.step_rate != 1.0):
                # the population's step-rate profile modulates the clock
                self.clock = ClientClock(
                    speeds=self.clock.speeds * self.population.step_rate,
                    latency=self.clock.latency)
        # failure scenario (fed/scenarios.py, DESIGN.md §12): perturbs the
        # timeline (k′ aborts, slowdowns, latency bursts, rejoin downtime)
        # and scales report weights by the delivered fraction k′/K; None
        # ("baseline") leaves the whole pipeline untouched
        self.scenario = (scenario if scenario is not None
                         else make_scenario(fed))
        if self.scenario is not None:
            if self.scenario.m != m:
                raise ValueError(
                    f"scenario for {self.scenario.m} clients does not "
                    f"match fed.n_clients={m}")
            if (self.scenario.availability_fn is not None
                    and self.population is not None):
                self.population.availability_fn = \
                    self.scenario.availability_fn
        # robust aggregation (core/robust.py, DESIGN.md §16): payload
        # corruption brackets the same wire boundary as compression, the
        # defense + quarantine sit just before the buffered aggregator
        self._attack = (self.scenario
                        if self.scenario is not None
                        and self.scenario.corrupts_payload else None)
        self.robust = robust.RobustConfig.from_fed(fed)
        # private copy: the scanned chunk donates its carry (state + anchor
        # buffers), which would delete a caller-owned params tree
        params = jax.tree.map(jnp.array, params)
        # param_layout="flat" (core/flat.py, DESIGN.md §11): state vectors
        # and BOTH anchor buffers become flat (M+1, P) matrices, so the
        # stale-anchor gather and the re-dispatch scatter are pure row
        # indexing — the gather/scatter closures below are already
        # array-polymorphic, only the client update swaps implementations
        if fed.param_layout not in ("tree", "flat"):
            raise ValueError(f"unknown param_layout {fed.param_layout!r}; "
                             f"choose 'tree' or 'flat'")
        self.layout = fed.param_layout
        # wire compression (core/compress.py, DESIGN.md §14): uplink EF
        # rows follow the REPORTING ids; the downlink broadcast is carried
        # in state ("bc_params"/"bc_nu") so chunk boundaries and resumes
        # see the same anchors the clients were dispatched with
        self.compression = compress.CompressionConfig.from_fed(fed)
        self._down_on = (self.compression is not None
                         and self.compression.down_active)
        if self.layout == "flat":
            self._spec = flat.make_flat_spec(
                params, master_dtype=fed.master_dtype or None)
        elif (self.compression is not None or self.robust is not None
                or self._attack is not None):
            self._spec = flat.make_flat_spec(params)
        else:
            self._spec = None
        self._n_true = (self._spec.n if self._spec is not None else
                        int(sum(int(np.prod(lv.shape, dtype=np.int64))
                                for lv in jax.tree.leaves(params))))
        self._wire = compress.wire_cost(self._n_true, self.algo.uses_nu,
                                        self.compression)
        if self.layout == "flat":
            params = flat.ravel(self._spec, params)
        self.state = rounds.init_state(params, m, self.algo,
                                       compression=self.compression,
                                       spec=self._spec,
                                       robust=self.robust)
        self.version = 0
        self._device_sampler = callable(getattr(batcher, "sample_row", None))
        self._loss_fn = loss_fn
        self._chunk: Optional[Callable] = None
        self._anchors: Optional[PyTree] = None
        self._nu_anchors: Optional[PyTree] = None
        # host-sampler wave cache: per-wave index tensors, dropped after
        # their last in-timeline consumer and LRU-capped at M + 1 waves —
        # under heavy speed skew a straggler's wave can be re-requested
        # thousands of updates after the fast clients consumed it, and an
        # unbounded first-to-last-consumer residency would grow O(horizon);
        # an evicted wave is simply regenerated (the pre-refactor engine
        # made the same bounded-memory-for-regeneration trade)
        self._wave_cache: dict[int, Any] = {}
        self._wave_left: Optional[np.ndarray] = None

    # -- device-resident anchor buffer --------------------------------------

    def _bridge(self):
        """(ravel, ravel_rows, unravel, unravel_rows) — identities on the
        flat layout, view-table crossings on the tree layout."""
        if self.layout == "flat":
            ident = lambda a: a
            return ident, ident, ident, ident
        spec = self._spec
        return (lambda t: flat.ravel(spec, t),
                lambda t: flat.ravel(spec, t, client_dims=1),
                lambda a: flat.unravel(spec, a),
                lambda a: flat.unravel(spec, a, client_dims=1))

    def _broadcast_init(self) -> None:
        """The t = 0 dispatch ships a genuine compressed broadcast: one
        codec event through ``ef_down``(/``ef_down_nu``), persisted as the
        ``bc_params``/``bc_nu`` state carry the chunk body reads."""
        cs = compress.build_stages(self.compression, self._spec,
                                   self.algo.uses_nu)
        _rv = self._bridge()[0]
        uses_nu = self.algo.uses_nu

        def bcast(state):
            new_state = dict(state)
            new_state["bc_params"] = cs.down(_rv(state["params"]), state,
                                             new_state)
            if uses_nu:
                new_state["bc_nu"] = cs.down_nu(_rv(state["nu"]), state,
                                                new_state)
            return new_state

        self.state = jax.jit(bcast)(self.state)

    def _reset_anchors(self) -> None:
        """(M+1)-row anchor buffer: rows 0…M-1 hold each client's
        dispatch-time (params, ν); row M is the duplicate-write scratch.
        Under downlink compression the dispatch-time model is the
        COMPRESSED broadcast, not the raw master."""
        rows = self.clock.m + 1
        _ur = self._bridge()[2]
        p0 = (_ur(self.state["bc_params"]) if self._down_on
              else self.state["params"])
        nu0 = ((_ur(self.state["bc_nu"]) if self._down_on
                else self.state["nu"]) if self.algo.uses_nu else None)
        self._anchors = jax.tree.map(
            lambda p: jnp.broadcast_to(p[None], (rows,) + p.shape), p0)
        self._nu_anchors = (jax.tree.map(
            lambda p: jnp.broadcast_to(p[None], (rows,) + p.shape),
            nu0) if self.algo.uses_nu else jnp.zeros(()))

    # -- the jitted scanned chunk (one trace per chunk length) --------------

    def _chunk_fn(self) -> Callable:
        """One jitted chunk serves every chunk length (jit re-specializes
        on the stacked leading dim; equal-length chunks reuse the trace)."""
        if self._chunk is None:
            self._chunk = self._make_chunk()
        return self._chunk

    def _make_chunk(self):
        algo, lr, buffer = self.algo, self.fed.lr, self.buffer
        uses_nu = algo.uses_nu
        device = self._device_sampler
        batcher, k_max = self.batcher, self.k_max
        # stale-ν⁽ⁱ⁾ decay is a PARTIAL-participation rule (DESIGN.md §10):
        # with every client in flight each row refreshes on its own report
        nu_decay = (self.fed.cohort_nu_decay
                    if self.population is not None
                    and not self.population.full_participation else 0.0)
        if self.layout == "flat":
            client_update = flat.make_flat_client_update(
                self._spec, self._loss_fn, algo, lr=lr, k_max=k_max,
                per_client_anchor=True)
        else:
            tree_update = stages.make_client_update(
                self._loss_fn, algo, lr=lr, k_max=k_max,
                per_client_anchor=True)

            def client_update(*args):
                # the tree scan computes k_max steps for every report
                return (*tree_update(*args), jnp.int32(buffer * k_max))
        aggregate = stages.BUFFERED_AGGREGATORS[algo.aggregator]
        cs = compress.build_stages(self.compression, self._spec, uses_nu)
        down_on = cs is not None and cs.down is not None
        up_on = cs is not None and cs.up is not None
        rb = robust.build_round_robust(self.robust, self._spec, uses_nu)
        atk = self._attack
        wire = cs is not None or rb is not None or atk is not None
        if wire:
            _rv, _rvr, _ur, _urr = self._bridge()
            n_true = self._spec.n

        def body(carry, xs):
            state, A, N = carry
            ids, k_steps, sw = xs["ids"], xs["k"], xs["sw"]
            cur, fresh, wids = xs["cur"], xs["fresh"], xs["write_ids"]
            lam = xs["lam"]
            params = state["params"]
            new_state = dict(state)
            # what a client dispatched on THIS version actually received:
            # the compressed broadcast carried in state, or the raw model
            cur_p = _ur(state["bc_params"]) if down_on else params
            cur_nu = ((_ur(state["bc_nu"]) if down_on else state["nu"])
                      if uses_nu else None)

            def gather(buf, current):
                # dispatch-time anchors; reports dispatched within THIS
                # update (cur: version == update index) read the live model
                return jax.tree.map(
                    lambda b, c: jnp.where(
                        cur.reshape((buffer,) + (1,) * c.ndim),
                        jnp.broadcast_to(c[None], (buffer,) + c.shape),
                        b[ids]),
                    buf, current)

            anchor_i = gather(A, cur_p)
            if device:
                batches = jax.vmap(
                    lambda d, i: batcher.sample_row(d, i, k_max))(
                        xs["waves"], ids)
            else:
                batches = xs["batches"]

            kf = k_steps.astype(jnp.float32)
            # Σ w̃ — usually in (0, 1], but a high-weight fast client
            # reporting twice into one buffer can push it past 1
            mass = jnp.sum(sw)
            kbar = jnp.dot(sw, kf) / mass            # buffer-local K̄

            if uses_nu:
                # correction each client ran with: c⁽ⁱ⁾ = ν_{v_i} − ν⁽ⁱ⁾.
                # With nu_decay = 0 the current ν⁽ⁱ⁾ row IS the
                # dispatch-time value (rows change only when their client
                # reports); with decay the row has drifted toward ν by
                # (1 − (1−d)^τ) since dispatch — an accepted approximation
                # (the drift shrinks the correction, never grows it) that
                # avoids a second (M+1)-row snapshot buffer
                nu_anchor = gather(N, cur_nu)
                c_b = jax.tree.map(lambda na, nui: na - nui[ids],
                                   nu_anchor, state["nu_i"])
            else:
                c_b = stages.zero_corrections(params, buffer)

            x_b, g0_b, acc_b, loss0, client_steps = client_update(
                anchor_i, c_b, batches, k_steps, lam)

            # uplink wire path at the REPORTING ids: each reporter's
            # error-feedback row rides its own reports (a duplicate
            # same-buffer reporter resolves last-wins, the nu_i caveat);
            # payload corruption lands on the same pseudo-delta rows the
            # codec sees, and the defense screens what reaches the
            # buffered aggregator
            sw_eff = sw
            if wire:
                a_rows = _rvr(anchor_i)
                d = _rvr(x_b) - a_rows
                if atk is not None:
                    d = atk.corrupt_delta(state["round"], d, n_true,
                                          ids=ids)
                if up_on:
                    d = cs.up(d, state, new_state, ids=ids)
                if rb is not None:
                    d, sw_eff, qcount = rb.model(d, sw, state, new_state,
                                                 state["round"], ids)
                x_srv = _urr(a_rows + d)
            else:
                x_srv = x_b

            agg = aggregate(params, anchor_i, x_srv, kf, sw_eff, kbar)
            new_params = stages.server_update(algo, state, params, agg,
                                              new_state)
            if rb is not None:
                # final non-finite guard BEFORE the broadcast / re-dispatch
                # anchors read the new model: a defended run never ships a
                # poisoned version to any client
                new_params = rb.guard(new_params, params)
            new_state["params"] = new_params
            new_state["round"] = state["round"] + 1

            if uses_nu:
                transmit, avg_g = stages.orientation_transmit(
                    algo, params, x_b, g0_b, acc_b, c_b, kf, kbar, lr, lam,
                    anchor_i=anchor_i)
                w_nu = sw
                if wire and (up_on or atk is not None or rb is not None):
                    t_rows = _rvr(transmit)
                    if atk is not None:
                        t_rows = atk.corrupt_nu(state["round"], t_rows,
                                                n_true, ids=ids)
                    if up_on:
                        t_rows = cs.up_nu(t_rows, state, new_state,
                                          ids=ids)
                    if rb is not None:
                        t_rows, w_nu = rb.nu(t_rows, sw, state,
                                             state["round"], ids)
                    transmit = _urr(t_rows)
                # ν renorm preserves Σw̃ so the mass-mix ρ keeps its
                # planned value; an all-dropped buffer contributes 0 and
                # ν decays by (1 − ρ) — a safe fade, never a poisoned mix
                contrib = stages.transmit_mix(w_nu, transmit)
                new_state["nu"] = stages.nu_mass_mix(state["nu"], contrib,
                                                     mass)
                if rb is not None:
                    # guard ν before the scatter/broadcast below read it
                    new_state["nu"] = rb.guard(new_state["nu"],
                                               state["nu"])
                # duplicate idx (a fast client reporting twice into one
                # buffer) resolves arbitrarily between its two same-buffer
                # reports — both are current to within one update
                new_state["nu_i"] = stages.scatter_nu_rows(
                    state["nu_i"], new_state["nu"], avg_g, ids, nu_decay)
                if rb is not None:
                    new_state["nu_i"] = rb.guard(new_state["nu_i"],
                                                 state["nu_i"])

            # this update's broadcast: ONE compression event through the
            # server-side accumulator, persisted for the next gather and
            # written into re-dispatched anchors below
            if down_on:
                new_bc = cs.down(_rv(new_params), state, new_state)
                new_state["bc_params"] = new_bc
                old_anchor, new_anchor = cur_p, _ur(new_bc)
            else:
                old_anchor, new_anchor = params, new_params

            def scatter(buf, old, new):
                # re-dispatch anchors: the pre-update model, or the
                # post-update one for tie-upgraded reporters; a duplicate
                # reporter writes once (its stale non-last occurrences are
                # routed to the scratch row M by ``write_ids``)
                return jax.tree.map(
                    lambda b, o, n: b.at[wids].set(
                        jnp.where(fresh.reshape((buffer,) + (1,) * o.ndim),
                                  jnp.broadcast_to(n[None],
                                                   (buffer,) + n.shape),
                                  jnp.broadcast_to(o[None],
                                                   (buffer,) + o.shape)
                                  ).astype(b.dtype)),
                    buf, old, new)

            A = scatter(A, old_anchor, new_anchor)
            if uses_nu:
                if down_on:
                    new_bc_nu = cs.down_nu(_rv(new_state["nu"]), state,
                                           new_state)
                    new_state["bc_nu"] = new_bc_nu
                    N = scatter(N, cur_nu, _ur(new_bc_nu))
                else:
                    N = scatter(N, state["nu"], new_state["nu"])

            metrics = {"loss": jnp.dot(sw, loss0) / mass, "kbar": kbar,
                       "mass": mass, "client_steps": client_steps}
            if rb is not None:
                metrics["quarantined"] = qcount
            return (new_state, A, N), metrics

        def chunk(carry, xs):
            return jax.lax.scan(body, carry, xs)

        return jax.jit(chunk, donate_argnums=(0,))

    # -- host-sampler batch assembly ----------------------------------------

    def _wave(self, d: int):
        """Index tensor (or full batch wave) ``d``, cached until its last
        consumer in the precomputed timeline has arrived (LRU-capped,
        see __init__)."""
        wave = self._wave_cache.pop(d, None)
        if wave is None:
            if hasattr(self.batcher, "round_indices"):
                wave = self.batcher.round_indices(d, self.k_max)
            else:
                wave = self.batcher.round_batches(d, self.k_max)
        self._wave_left[d] -= 1
        if self._wave_left[d] > 0:
            self._wave_cache[d] = wave        # re-insert: most recent
            while len(self._wave_cache) > self.clock.m + 1:
                self._wave_cache.pop(next(iter(self._wave_cache)))
        return wave

    def _host_batches(self, tl: Timeline, u0: int, r: int) -> PyTree:
        """(R, B, k_max, batch, …) gathered rows for updates u0 … u0+r-1 —
        one host→device transfer per chunk."""
        if hasattr(self.batcher, "round_indices"):
            idx = np.empty((r, self.buffer, self.k_max,
                            self.batcher.batch_size), np.int64)
            for a in range(r):
                for j in range(self.buffer):
                    idx[a, j] = self._wave(int(tl.waves[u0 + a, j]))[
                        int(tl.ids[u0 + a, j])]
            return {"x": jnp.asarray(self.batcher._x[idx]),
                    "y": jnp.asarray(self.batcher._y[idx])}
        rows = [jax.tree.map(
            lambda x, i=int(tl.ids[u0 + a, j]): x[i],
            self._wave(int(tl.waves[u0 + a, j])))
            for a in range(r) for j in range(self.buffer)]
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *rows)
        return jax.tree.map(
            lambda x: x.reshape((r, self.buffer) + x.shape[1:]), stacked)

    # -- the timeline-driven chunked executor --------------------------------

    def run(self, t_updates: int, eval_every: int = 1,
            verbose: bool = False,
            chunk_updates: Optional[int] = None) -> History:
        hist = History()
        fed = self.fed
        tl = simulate_timeline(self.k_schedule, self.clock, self.buffer,
                               t_updates, population=self.population,
                               scenario=self.scenario)
        tau = tl.staleness
        s = staleness_weight(tau, fed.staleness, fed.staleness_a,
                             fed.staleness_b)
        # per-report base weights: raw ω for full participation, the
        # population's per-sampler renormalization (Horvitz–Thompson /
        # uniform-1/C) under partial participation (DESIGN.md §10)
        base_w = (self.weights
                  if self.population is None
                  or self.population.full_participation
                  else self.population.report_weights())
        sw = base_w[tl.ids] * s
        if self.scenario is not None:
            # partial-work recovery (DESIGN.md §12): an aborted report's
            # FedNova-normalized per-step direction keeps only the mass it
            # earned — w̃ · k′/K feeds BOTH the pseudo-delta aggregation
            # and the ν mass-mix (stages.delivered_weights rule)
            sw = sw * (tl.k_steps / np.maximum(tl.k_sched, 1))
        sw_all = sw.astype(np.float32)
        cur_all = tl.versions == np.arange(t_updates)[:, None]
        # duplicate dispatches: only the LAST occurrence re-writes the
        # client's anchor row; earlier ones land in the scratch row M
        write_ids = tl.dispatch_ids.copy()
        for u in range(t_updates):
            seen: set[int] = set()
            for j in range(self.buffer - 1, -1, -1):
                i = int(tl.dispatch_ids[u, j])
                if i in seen:
                    write_ids[u, j] = self.clock.m
                else:
                    seen.add(i)
        lam_all = np.asarray(
            [float(self.lam_schedule(u)) if self.lam_schedule
             else self.algo.lam for u in range(t_updates)], np.float32)
        if self._down_on:
            self._broadcast_init()
        self._reset_anchors()
        if not self._device_sampler:
            self._wave_cache = {}
            self._wave_left = np.bincount(tl.waves.ravel())

        chunk = max(int(chunk_updates if chunk_updates is not None
                        else eval_every), 1)
        if (chunk_updates is not None and chunk > eval_every
                and self.eval_fn is not None):
            warnings.warn(
                f"chunk_updates={chunk_updates} is clamped to the eval "
                f"cadence (eval_every={eval_every}): the host must sync at "
                f"every eval boundary", stacklevel=2)
        u = 0
        while u < t_updates:
            r = min(chunk, t_updates - u)
            if self.eval_fn is not None:
                r = min(r, eval_every - u % eval_every)
            sl = slice(u, u + r)
            xs = {"ids": jnp.asarray(tl.ids[sl], jnp.int32),
                  "k": jnp.asarray(tl.k_steps[sl], jnp.int32),
                  "sw": jnp.asarray(sw_all[sl]),
                  "cur": jnp.asarray(cur_all[sl]),
                  "fresh": jnp.asarray(tl.fresh[sl]),
                  "write_ids": jnp.asarray(write_ids[sl], jnp.int32),
                  "lam": jnp.asarray(lam_all[sl])}
            if self._device_sampler:
                xs["waves"] = jnp.asarray(tl.waves[sl], jnp.int32)
            else:
                xs["batches"] = self._host_batches(tl, u, r)
            fn = self._chunk_fn()
            carry, metrics = fn((self.state, self._anchors,
                                 self._nu_anchors), xs)
            self.state, self._anchors, self._nu_anchors = carry
            jax.block_until_ready(self.state)
            hist.record(metrics)
            hist.sim_time.extend(tl.arrival_t[sl, -1].tolist())
            hist.staleness.extend(tau[sl].mean(axis=1).tolist())
            # wire traffic per update: B reports up, B re-dispatch
            # downloads of the (possibly compressed) new broadcast
            hist.bytes_up.extend(
                [self.buffer * self._wire["uplink_per_client"]] * r)
            hist.bytes_down.extend(
                [self.buffer * self._wire["downlink_per_client"]] * r)
            if self.scenario is not None:
                hist.dropped.extend(
                    tl.aborted[sl].mean(axis=1).tolist())
            u += r
            if self.eval_fn is not None and u % eval_every == 0:
                value = float(self.eval_fn(self.params))
                _check_finite_metric(value, u)
                hist.metric.append(value)
            if verbose and (u % 10 < r or u == t_updates):
                mtr = hist.metric[-1] if hist.metric else float("nan")
                print(f"  update {u - 1:4d}  t={hist.sim_time[-1]:8.2f}  "
                      f"loss={hist.loss[-1]:.4f}  metric={mtr:.4f}  "
                      f"stale={hist.staleness[-1]:.1f}")
        self.version += t_updates
        return hist

    @property
    def params(self) -> PyTree:
        """Current global model as a pytree (flat layout unravels)."""
        if self.layout == "flat":
            return flat.unravel(self._spec, self.state["params"])
        return self.state["params"]
