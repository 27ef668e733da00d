"""Single-host federated simulator.

Drives the SPMD round engine (core/rounds.py) with vmap-over-clients on one
device: samples K_i schedules, assembles per-round microbatches, runs T
rounds jitted, and records loss / eval metrics.  This is the harness behind
the paper-experiment benchmarks (Tables 1/2/6, Figures 2/3/5).

Execution is chunked (DESIGN.md §9): ``run`` drives blocks of
``chunk_rounds`` rounds through one jitted ``lax.scan``
(core/engine.py), syncing to host only at chunk boundaries — the eval
cadence defines the default chunk size, so the legacy behavior
(``eval_every=1`` ⇒ one dispatch + one sync per round) is the
``chunk_rounds=1`` compat path, bit-identical by construction and pinned by
tests/test_golden_equivalence.py.  With a ``DeviceBatcher`` the per-round
microbatches are also drawn inside the scan; host batchers stack R rounds
into a single transfer."""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import FedConfig
from repro.core import compress, engine, flat, robust, rounds, stages
from repro.core.fedopt import get_algorithm
from repro.data.partition import gaussian_k_schedule
from repro.fed.population import ClientPopulation
from repro.fed.scenarios import Scenario, make_scenario

PyTree = Any


def _check_finite_metric(value: float, t: int) -> None:
    """Fail loudly at the eval boundary: a non-finite metric means the run
    diverged or was poisoned — silently logging NaN into History lets a
    corrupted model ship (defenses/quarantine: core/robust.py, §16)."""
    if not np.isfinite(value):
        raise FloatingPointError(
            f"evaluation metric is non-finite ({value}) after round {t}: "
            f"the run has diverged or been poisoned; configure a defense "
            f"(FedConfig.defense / quarantine_window, core/robust.py)")


@dataclasses.dataclass
class History:
    loss: list[float] = dataclasses.field(default_factory=list)
    metric: list[float] = dataclasses.field(default_factory=list)
    kbar: list[float] = dataclasses.field(default_factory=list)
    per_client: list[list[float]] = dataclasses.field(default_factory=list)
    # buffered-async engine (fed/async_engine.py): simulated arrival time of
    # each server update, the mean staleness of its buffer, and the buffer
    # mass Σ w̃ (discount-weighted participation)
    sim_time: list[float] = dataclasses.field(default_factory=list)
    staleness: list[float] = dataclasses.field(default_factory=list)
    mass: list[float] = dataclasses.field(default_factory=list)
    # failure scenarios (fed/scenarios.py): per-round/update fraction of
    # mid-round dropouts (k′ < K_i) — population-level for the sync engine,
    # buffer-level for the async engine; empty without a scenario
    dropped: list[float] = dataclasses.field(default_factory=list)
    # Byzantine robustness (core/robust.py, DESIGN.md §16): number of
    # participants excluded by an active quarantine each round/update;
    # empty unless defense/quarantine is configured
    quarantined: list[float] = dataclasses.field(default_factory=list)
    # wire bytes per round/update under the configured compressors
    # (core/compress.py wire_cost × participants) — recorded on EVERY run,
    # fp32 cost when compression is off, so baselines compare directly
    bytes_up: list[float] = dataclasses.field(default_factory=list)
    bytes_down: list[float] = dataclasses.field(default_factory=list)
    # local steps each round/update computed (flat layout, core/flat.py):
    # Σ K_i where the clients run in turn, M · k_max where the mesh spreads
    # their rows; empty on the tree layout
    client_steps: list[float] = dataclasses.field(default_factory=list)

    def record(self, metrics: dict) -> None:
        """Append one round's metrics, or a chunk's ``(r,)`` stacks: loss
        and kbar, and mass, quarantined and client_steps where the round
        reports them."""
        for name in ("loss", "kbar", "mass", "quarantined", "client_steps"):
            if name in metrics:
                getattr(self, name).extend(np.atleast_1d(
                    np.asarray(metrics[name], np.float64)).tolist())

    def fairness(self) -> Optional[dict]:
        """FL fairness of the final round: worst-client metric and the
        across-client std (Li et al. q-FFL reporting convention)."""
        if not self.per_client:
            return None
        last = self.per_client[-1]
        return {"worst": min(last), "best": max(last),
                "std": float(np.std(last))}

    def rounds_to_target(self, target: float, higher_is_better=True
                         ) -> Optional[int]:
        for t, v in enumerate(self.metric):
            if (v >= target) if higher_is_better else (v <= target):
                return t + 1
        return None

    def bytes_to_target(self, target: float, higher_is_better=True
                        ) -> Optional[float]:
        """Cumulative uplink bytes spent when the eval metric first hits
        ``target`` (the compression headline: bytes, not rounds, are the
        cross-device cost model) — None if the target is never reached."""
        r = self.rounds_to_target(target, higher_is_better)
        if r is None or not self.bytes_up or not self.metric:
            return None
        per_eval = max(1, len(self.bytes_up) // len(self.metric))
        return float(sum(self.bytes_up[:r * per_eval]))


class FederatedSimulation:
    """``run(T)`` executes T rounds of ``fed.algorithm`` on one device."""

    def __init__(self, loss_fn: Callable[[PyTree, PyTree], jax.Array],
                 params: PyTree, fed: FedConfig, batcher,
                 eval_fn: Optional[Callable[[PyTree], float]] = None,
                 eval_per_client: Optional[Callable[[PyTree],
                                                    list]] = None,
                 k_schedule: Optional[np.ndarray] = None,
                 lam_schedule: Optional[Callable[[int], float]] = None,
                 population: Optional[ClientPopulation] = None,
                 scenario: Optional[Scenario] = None,
                 t_max: int = 10_000):
        self.fed = fed
        self.algo = get_algorithm(fed.algorithm, fed)
        self.batcher = batcher
        self.eval_fn = eval_fn
        self.eval_per_client = eval_per_client
        self.lam_schedule = lam_schedule
        if k_schedule is None:
            k_schedule = gaussian_k_schedule(
                fed.n_clients, fed.k_mean, fed.k_var, t_max,
                mode=fed.k_mode, seed=fed.seed)
        self.k_schedule = k_schedule
        self.k_max = int(k_schedule.max())
        self.weights = (jnp.asarray(batcher.weights)
                        if fed.weights == "data"
                        else jnp.full((fed.n_clients,),
                                      1.0 / fed.n_clients, jnp.float32))
        # private copy: chunked execution donates the state buffers to the
        # scan (core/engine.py), which would delete a caller-owned ``params``
        # tree shared with other simulations
        params = jax.tree.map(jnp.array, params)
        # param_layout="flat" (core/flat.py, DESIGN.md §11): the round state
        # lives as one lane-padded (P,) buffer per vector (ν⁽ⁱ⁾: (M, P)) and
        # the flat round twins plug into the SAME run loop — only the eval
        # boundary (``self.params``) unravels back to the pytree
        if fed.param_layout not in ("tree", "flat"):
            raise ValueError(f"unknown param_layout {fed.param_layout!r}; "
                             f"choose 'tree' or 'flat'")
        self.layout = fed.param_layout
        # failure scenario (fed/scenarios.py, DESIGN.md §12): None for
        # "baseline" — every run path below then takes its literally
        # unperturbed (golden-pinned) branch.  Resolved BEFORE the spec
        # decision: payload-corruption scenarios work on wire rows, so the
        # tree layout needs the flat view table, exactly like compression.
        self.scenario = (scenario if scenario is not None
                         else make_scenario(fed))
        if self.scenario is not None and self.scenario.m != fed.n_clients:
            raise ValueError(
                f"scenario for {self.scenario.m} clients does not "
                f"match fed.n_clients={fed.n_clients}")
        self._attack = (self.scenario
                        if self.scenario is not None
                        and self.scenario.corrupts_payload else None)
        # Byzantine-robust aggregation (core/robust.py, DESIGN.md §16):
        # None when defense="none" and quarantine is off — the builders
        # then bake the identical (golden-pinned) round
        self.robust = robust.RobustConfig.from_fed(fed)
        # wire compression (core/compress.py, DESIGN.md §14): None when the
        # config requests no compression — every builder below then bakes
        # its literally unchanged (golden-pinned) round
        self.compression = compress.CompressionConfig.from_fed(fed)
        if self.layout == "flat":
            self._spec = flat.make_flat_spec(
                params, master_dtype=fed.master_dtype or None)
        elif (self.compression is not None or self.robust is not None
                or self._attack is not None):
            # the tree round works the wire rows through the view table:
            # it needs the spec (and any flat EF/health state) even though
            # params stay a pytree
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
        self.state = rounds.init_state(params, fed.n_clients, self.algo,
                                       compression=self.compression,
                                       spec=self._spec, robust=self.robust)
        self._round: Optional[Callable] = None
        self._chunks: dict[int, Callable] = {}
        self._loss_fn = loss_fn
        # a DeviceBatcher exposes a traceable in-scan sampler; host batchers
        # remain the pinned-equivalence compat mode (DESIGN.md §9)
        self._device_sampler = callable(getattr(batcher, "sample", None))
        # partial participation (fed/population.py, DESIGN.md §10): each
        # round runs a sampled cohort of C ≤ M clients; sampler "all" stays
        # on the golden-pinned full-participation path above
        self.population = (population if population is not None
                           else ClientPopulation.from_config(
                               fed, m=fed.n_clients,
                               weights=np.asarray(self.weights)))
        self._partial = (self.population is not None
                         and not self.population.full_participation)
        if (self.population is not None
                and self.population.m != fed.n_clients):
            raise ValueError(
                f"population of {self.population.m} clients does not match "
                f"fed.n_clients={fed.n_clients}")
        if (self.scenario is not None
                and self.scenario.availability_fn is not None
                and self.population is not None):
            self.population.availability_fn = self.scenario.availability_fn
        self._dw = None       # lazily-jitted delivered-weights host mirror

    def _build_round(self) -> Callable:
        """The ONE synchronous-round builder every execution path shares —
        the tree round or (``param_layout="flat"``) its single-buffer twin;
        both expose ``round_fn(state, batches, k_steps, weights, lam)``, so
        the run loop below is layout-agnostic."""
        if self.layout == "flat":
            return flat.make_flat_round(
                self._spec, self._loss_fn, self.algo, lr=self.fed.lr,
                k_max=self.k_max, compression=self.compression,
                robust=self.robust, attack=self._attack)
        return rounds.make_round(self._loss_fn, self.algo, lr=self.fed.lr,
                                 k_max=self.k_max,
                                 compression=self.compression,
                                 spec=self._spec, robust=self.robust,
                                 attack=self._attack)

    def _round_fn(self) -> Callable:
        """One jitted round for EVERY λ: the round function takes λ as a
        traced scalar argument, so ``lam_schedule`` never retraces (the old
        cache was keyed on the float λ — one fresh ``jax.jit`` trace per
        round under any non-constant schedule)."""
        if self._round is None:
            self._round = jax.jit(self._build_round())
        return self._round

    def _chunk_fn(self, r: int) -> Callable:
        """The r-round scanned chunk (cached per chunk length)."""
        if r not in self._chunks:
            fn = self._build_round()
            sample = (lambda t: self.batcher.sample(t, self.k_max)) \
                if self._device_sampler else None
            self._chunks[r] = engine.make_round_chunk(fn, r,
                                                      sample_fn=sample)
        return self._chunks[r]

    def _make_pop_round(self) -> Callable:
        """The ONE cohort-round builder both population paths share — the
        compat round and every chunk length compute the identical round."""
        if self.layout == "flat":
            return flat.make_flat_cohort_round(
                self._spec, self._loss_fn, self.algo, lr=self.fed.lr,
                k_max=self.k_max, nu_decay=self.fed.cohort_nu_decay,
                compression=self.compression, robust=self.robust,
                attack=self._attack)
        return stages.make_cohort_round(
            self._loss_fn, self.algo, lr=self.fed.lr, k_max=self.k_max,
            nu_decay=self.fed.cohort_nu_decay,
            compression=self.compression, spec=self._spec,
            robust=self.robust, attack=self._attack)

    def _pop_round_fn(self) -> Callable:
        """One jitted cohort round (partial participation, DESIGN.md §10)."""
        if self._round is None:
            self._round = jax.jit(self._make_pop_round())
        return self._round

    def _pop_chunk_fn(self, r: int) -> Callable:
        """The r-round scanned cohort chunk: with a DeviceBatcher the cohort
        draw AND the batch generation run inside the scan (O(C) memory);
        host batchers feed precomputed (r, C, …) cohort tensors."""
        if r not in self._chunks:
            fn = self._make_pop_round()
            pop, k_max = self.population, self.k_max
            if self._device_sampler:
                scn = self.scenario
                scenario_fn = (
                    (lambda t, k_c, ids: scn.k_eff(t, k_c, ids=ids))
                    if scn is not None and scn.perturbs_k else None)
                self._chunks[r] = engine.make_population_chunk(
                    fn, r, cohort_fn=pop.cohort_and_weights,
                    sample_fn=lambda t, ids: self.batcher.sample_cohort(
                        t, ids, k_max),
                    scenario_fn=scenario_fn)
            else:
                self._chunks[r] = engine.make_population_chunk(fn, r)
        return self._chunks[r]

    def _lam(self, t: int) -> float:
        return (float(self.lam_schedule(t)) if self.lam_schedule
                else self.algo.lam)

    # -- failure-scenario host mirrors (fed/scenarios.py, DESIGN.md §12) ----

    def _sched_row(self, t: int) -> np.ndarray:
        return np.asarray(self.k_schedule[t % len(self.k_schedule)])

    def _k_row(self, t: int) -> np.ndarray:
        """Round t's effective K row: the schedule row, perturbed to k′ by
        the scenario's host mirror — the SAME jax draw the in-scan hook
        evaluates, so host and device paths stay bit-identical."""
        row = self._sched_row(t)
        if self.scenario is None or not self.scenario.perturbs_k:
            return row
        return self.scenario.host_k_eff(t, row)

    def _delivered(self, cw: np.ndarray, k_eff: np.ndarray,
                   k_sched: np.ndarray) -> np.ndarray:
        """Host mirror of the in-scan delivered-fraction weight scaling."""
        if self._dw is None:
            self._dw = jax.jit(stages.delivered_weights)
        return np.asarray(self._dw(jnp.asarray(cw),
                                   jnp.asarray(k_eff, jnp.int32),
                                   jnp.asarray(k_sched, jnp.int32)))

    def _record_dropped(self, hist: History, t0: int, r: int) -> None:
        """Population-level abort fraction per round (pure in (seed, t))."""
        if self.scenario is None:
            return
        if not self.scenario.perturbs_k:
            hist.dropped.extend([0.0] * r)
            return
        hist.dropped.extend(
            float(np.mean(self._k_row(t0 + j) < self._sched_row(t0 + j)))
            for j in range(r))

    def _record_bytes(self, hist: History, r: int, participants: int
                      ) -> None:
        """Measured wire traffic for r rounds of ``participants`` reports
        each (fp32 cost when compression is off — the baseline series)."""
        hist.bytes_up.extend(
            [participants * self._wire["uplink_per_client"]] * r)
        hist.bytes_down.extend(
            [participants * self._wire["downlink_per_client"]] * r)

    def _chunk_inputs(self, t0: int, r: int):
        """Stacked (k_steps, weights, lam) + batches for rounds t0…t0+r-1."""
        ks = jnp.asarray(np.stack(
            [self._k_row(t0 + j) for j in range(r)]).astype(np.int32))
        lams = jnp.asarray([self._lam(t0 + j) for j in range(r)],
                           jnp.float32)
        weights = jnp.broadcast_to(self.weights, (r,) + self.weights.shape)
        if self._device_sampler:
            batches = jnp.arange(t0, t0 + r, dtype=jnp.int32)
        elif hasattr(self.batcher, "chunk_batches"):
            batches = self.batcher.chunk_batches(t0, r, self.k_max)
        else:
            waves = [self.batcher.round_batches(t0 + j, self.k_max)
                     for j in range(r)]
            batches = jax.tree.map(lambda *xs: jnp.stack(xs), *waves)
        return batches, ks, weights, lams

    def _run_round(self, t: int, hist: History) -> None:
        """The chunk_rounds=1 compat path: one dispatch + one host sync per
        round, bit-identical to the pre-chunking loop (golden-pinned)."""
        lam = self._lam(t)
        round_fn = self._round_fn()
        k_t = (jnp.asarray(self.k_schedule[t % len(self.k_schedule)])
               if self.scenario is None else jnp.asarray(self._k_row(t)))
        batches = self.batcher.round_batches(t, self.k_max)
        self.state, metrics = round_fn(self.state, batches, k_t,
                                       self.weights, jnp.float32(lam))
        jax.block_until_ready(self.state)
        hist.record(metrics)
        self._record_dropped(hist, t, 1)
        self._record_bytes(hist, 1, self.fed.n_clients)

    def _run_chunk(self, t0: int, r: int, hist: History) -> None:
        """One scanned chunk of r rounds, under host spans on the
        profiler's clock: ``fed.chunk`` (a step, numbered by its first
        round) holding ``fed.inputs``, ``fed.dispatch``, ``fed.wait`` and
        ``fed.history``."""
        chunk_fn = self._chunk_fn(r)
        with jax.profiler.StepTraceAnnotation("fed.chunk", step_num=t0):
            with jax.profiler.TraceAnnotation("fed.inputs"):
                batches, ks, weights, lams = self._chunk_inputs(t0, r)
            with jax.profiler.TraceAnnotation("fed.dispatch"):
                self.state, metrics = chunk_fn(self.state, batches, ks,
                                               weights, lams)
            with jax.profiler.TraceAnnotation("fed.wait"):
                jax.block_until_ready(self.state)
            with jax.profiler.TraceAnnotation("fed.history"):
                hist.record(metrics)
                self._record_dropped(hist, t0, r)
                self._record_bytes(hist, r, self.fed.n_clients)

    def lower_chunk(self, r: int, t0: int = 0) -> jax.stages.Lowered:
        """The r-round chunk ``run`` dispatches for rounds t0…t0+r-1,
        lowered on the current state.  ``.compile()`` builds the very
        executable the next such ``run`` reuses, so its compile time,
        memory analysis and program text describe what runs."""
        if self._partial:
            raise NotImplementedError("lower_chunk covers the full-"
                                      "participation chunk")
        return self._chunk_fn(r).lower(self.state,
                                       *self._chunk_inputs(t0, r))

    # -- partial-participation execution (fed/population.py, DESIGN.md §10) --

    def _run_pop_round(self, t: int, hist: History) -> None:
        """chunk_rounds=1 cohort path: cohort drawn on host (identical to
        the in-scan draw — same jax.random function of (seed, t))."""
        lam = self._lam(t)
        fn = self._pop_round_fn()
        ids, cw = self.population.host_cohort(t)
        k_c = self._sched_row(t)[ids]
        if self.scenario is not None and self.scenario.perturbs_k:
            # same perturbation the in-scan hook applies (values identical
            # by the per-(round, client) keying): run the k′ prefix and
            # scale w̃ by the delivered fraction
            k_eff = self._k_row(t)[ids]
            cw = self._delivered(cw, k_eff, k_c)
            k_c = k_eff
        if self._device_sampler:
            batches = self.batcher.sample_cohort(
                jnp.int32(t), jnp.asarray(ids, jnp.int32), self.k_max)
        else:
            batches = self.batcher.cohort_batches(t, ids, self.k_max)
        self.state, metrics = fn(self.state, batches,
                                 jnp.asarray(ids, jnp.int32),
                                 jnp.asarray(k_c, jnp.int32),
                                 jnp.asarray(cw), jnp.float32(lam))
        jax.block_until_ready(self.state)
        hist.record(metrics)
        self._record_dropped(hist, t, 1)
        self._record_bytes(hist, 1, self.population.cohort_size)

    def _run_pop_chunk(self, t0: int, r: int, hist: History) -> None:
        chunk_fn = self._pop_chunk_fn(r)
        perturb = self.scenario is not None and self.scenario.perturbs_k
        lams = jnp.asarray([self._lam(t0 + j) for j in range(r)],
                           jnp.float32)
        if self._device_sampler:
            # cohort draw + batch sampling both happen inside the scan —
            # with a scenario, so does the k′ perturbation
            # (engine.make_population_chunk's scenario_fn); the host ships
            # only the (r,) round indices and (r, M) SCHEDULED K rows
            ts = jnp.arange(t0, t0 + r, dtype=jnp.int32)
            k_rows = jnp.asarray(np.stack(
                [self._sched_row(t0 + j)
                 for j in range(r)]).astype(np.int32))
            args = (ts, k_rows, lams)
        else:
            drawn = [self.population.host_cohort(t0 + j) for j in range(r)]
            cohorts = np.stack([ids for ids, _ in drawn])
            cws = np.stack([w for _, w in drawn])
            ks = np.stack(
                [self._sched_row(t0 + j)[cohorts[j]]
                 for j in range(r)]).astype(np.int32)
            if perturb:
                keffs = np.stack(
                    [self._k_row(t0 + j)[cohorts[j]]
                     for j in range(r)]).astype(np.int32)
                cws = self._delivered(cws, keffs, ks)
                ks = keffs
            batches = self.batcher.chunk_cohort_batches(t0, cohorts,
                                                        self.k_max)
            args = (batches, jnp.asarray(cohorts, jnp.int32),
                    jnp.asarray(ks), jnp.asarray(cws), lams)
        self.state, metrics = chunk_fn(self.state, *args)
        jax.block_until_ready(self.state)
        hist.record(metrics)
        self._record_dropped(hist, t0, r)
        self._record_bytes(hist, r, self.population.cohort_size)

    def run(self, t_rounds: int, eval_every: int = 1,
            verbose: bool = False,
            chunk_rounds: Optional[int] = None,
            publish_fn: Optional[Callable[[dict], None]] = None,
            publish_every: int = 0) -> History:
        """``chunk_rounds=None`` chunks at the eval cadence (``eval_every``);
        ``1`` forces the per-round compat loop.  Eval hooks fire at the same
        rounds regardless of chunking — chunks never cross an eval
        boundary, so an explicit ``chunk_rounds`` larger than ``eval_every``
        is clamped (raise ``eval_every`` to actually chunk).

        ``publish_fn(snapshot)`` fires every ``publish_every`` rounds with a
        versioned serving snapshot (``publish_snapshot``) — the hot-swap
        feed for serving/personalized.py.  Chunks never cross a publish
        boundary either, so publications see exact round states."""
        chunk = max(int(chunk_rounds if chunk_rounds is not None
                        else eval_every), 1)
        if (chunk_rounds is not None and chunk > eval_every
                and (self.eval_fn is not None
                     or self.eval_per_client is not None)):
            warnings.warn(
                f"chunk_rounds={chunk_rounds} is clamped to the eval "
                f"cadence (eval_every={eval_every}): the host must sync at "
                f"every eval boundary", stacklevel=2)
        hist = History()
        t = 0
        while t < t_rounds:
            r = min(chunk, t_rounds - t)
            if self.eval_fn is not None or self.eval_per_client is not None:
                r = min(r, eval_every - t % eval_every)
            if publish_fn is not None and publish_every > 0:
                r = min(r, publish_every - t % publish_every)
            if self._partial and r == 1:
                self._run_pop_round(t, hist)
            elif self._partial:
                self._run_pop_chunk(t, r, hist)
            elif r == 1:
                self._run_round(t, hist)
            else:
                self._run_chunk(t, r, hist)
            t += r
            if publish_fn is not None and publish_every > 0 \
                    and t % publish_every == 0:
                publish_fn(self.publish_snapshot())
            if t % eval_every == 0:
                if self.eval_fn is not None:
                    value = float(self.eval_fn(self.params))
                    _check_finite_metric(value, t)
                    hist.metric.append(value)
                if self.eval_per_client is not None:
                    hist.per_client.append(
                        [float(v) for v in
                         self.eval_per_client(self.params)])
            if verbose and (t % 10 < r or t == t_rounds):
                m = hist.metric[-1] if hist.metric else float("nan")
                print(f"  round {t - 1:4d}  loss={hist.loss[-1]:.4f}  "
                      f"metric={m:.4f}")
        return hist

    @property
    def params(self) -> PyTree:
        """Current global model as a pytree (flat layout unravels — the
        only place the flat engine materializes the tree outside the
        loss boundary)."""
        if self.layout == "flat":
            return flat.unravel(self._spec, self.state["params"])
        return self.state["params"]

    @property
    def flat_spec(self) -> flat.FlatSpec:
        """The FlatSpec describing this model's `(P,)` layout.  Flat runs
        (and compressed tree runs) already own one; a plain tree run
        builds and caches it on first use — the spec is pure shape
        metadata, so this never perturbs the round state."""
        if self._spec is None:
            self._spec = flat.make_flat_spec(self.state["params"])
        return self._spec

    def publish_snapshot(self) -> dict:
        """A versioned serving snapshot of the CURRENT training state:
        the `(P,)` flat master plus the per-client calibration signal
        (ν, ν⁽ⁱ⁾ rows) when the algorithm maintains one.  Version = round
        counter, so every publication is totally ordered.  Consumed by
        serving/personalized.py (view resolution + hot-swap)."""
        spec = self.flat_spec
        # snapshots OWN their buffers: chunked execution donates the state
        # arrays to the next scan, which would delete aliased references
        if self.layout == "flat":
            master = jnp.array(self.state["params"])
        else:
            master = flat.ravel(spec, self.state["params"])
        snap = {"version": np.int32(int(self.state["round"])),
                "flat_master": master}
        if self.algo.uses_nu and "nu" in self.state:
            nu, nu_i = self.state["nu"], self.state["nu_i"]
            if self.layout != "flat":
                nu = flat.ravel(spec, nu)
                nu_i = flat.ravel(spec, nu_i, client_dims=1)
            else:
                nu, nu_i = jnp.array(nu), jnp.array(nu_i)
            snap["nu"] = nu
            snap["nu_i"] = nu_i
        return snap

    def save_snapshot(self, path: str) -> dict:
        """Publish + persist (checkpoint/serialize.py msgpack); the serving
        side restores with ``serving.personalized.load_snapshot``."""
        from repro.checkpoint import serialize
        snap = self.publish_snapshot()
        serialize.save(path, snap)
        return snap


def compare_algorithms(algorithms: list[str], make_sim: Callable[[str],
                       FederatedSimulation], t_rounds: int,
                       eval_every: int = 1) -> dict[str, History]:
    """Run the same task under several algorithms (benchmark helper)."""
    out = {}
    for name in algorithms:
        sim = make_sim(name)
        out[name] = sim.run(t_rounds, eval_every=eval_every)
    return out
