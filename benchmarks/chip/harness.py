"""The benchmark's harness: finds a cell's parts by name, holds the chip
check, the compile cache, the weights and the result line.

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration and a traffic mix.  Everything else is found by name:

    configs/<config>.json     sizes, source, cut, and the program's registry name
    traffic/<mix>.json        parameters, and the driver that runs them
    drivers/<driver>.py       ``drive(ctx) -> dict``: set-up, window, check
    reference/<config>.py     plain float32 model the check compares with
    flops/<config>.py         operations the model requires per token
    limits/<cell>.json        limit of each number the check compares
    metrics/<metric>.py       ``read(ctx) -> float | None`` per per-layer metric

so a later change adds a configuration, a mix or a metric by adding files
and manifest entries.  The program under test is imported from
``<checkout>/src``; nothing here is imported by it.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import re
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


class BenchError(SystemExit):
    """A run that cannot produce a result: exits non-zero, prints none."""

    def __init__(self, msg: str):
        super().__init__(f"benchmark: {msg}")


# -- finding the parts ---------------------------------------------------------

def manifest(root: str = ROOT) -> dict:
    path = os.path.join(root, "BENCHMARK.json")
    if not os.path.exists(path):
        raise BenchError(f"no BENCHMARK.json at {root}")
    with open(path) as f:
        return json.load(f)


def load_json(kind: str, name: str, base: str = HERE) -> dict:
    path = os.path.join(base, kind, f"{name}.json")
    if not os.path.exists(path):
        raise BenchError(f"{kind}/{name}.json not found")
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str, base: str = HERE):
    path = os.path.join(base, kind, f"{name}.py")
    if not os.path.exists(path):
        raise BenchError(f"{kind}/{name}.py not found")
    mod_name = "bench_" + re.sub(r"\W", "_", f"{kind}_{name}")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def find_cell(man: dict, workload: str) -> dict:
    for w in man["workloads"]:
        if w["name"] == workload:
            return w
    raise BenchError(f"no workload {workload!r} in BENCHMARK.json")


def metrics_for(man: dict, workload: str, section: str) -> list[dict]:
    """The metrics of ``section`` that ``workload`` reports: those listing
    it under ``workloads``; a per-layer metric without the key goes with
    every cell that reports the end-to-end metric it moves."""
    e2e = {m["name"] for m in man["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]}
    out = []
    for m in man[section]:
        if "workloads" in m:
            if workload in m["workloads"]:
                out.append(m)
        elif section == "end_to_end" or m["moves"] in e2e:
            out.append(m)
    return out


# -- the device ------------------------------------------------------------------

def device_info() -> dict:
    import jax
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def require_chip(chips: int) -> dict:
    info = device_info()
    if info["platform"] != "tpu":
        raise BenchError(f"no TPU found (JAX platform {info['platform']!r});"
                         f" the benchmark runs only on the chip")
    if info["count"] < chips:
        raise BenchError(f"{chips} chips needed, {info['count']} found")
    return info


def memory_peak_bytes() -> int | None:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def peaks(kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise BenchError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def setup_compile_cache() -> str:
    """JAX's persistent compilation cache: where JAX_COMPILATION_CACHE_DIR
    says, else at the fixed path <checkout>/.jax_cache.  Every program is
    kept, however quick to compile, so a warm set-up compiles nothing."""
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR


def seed_key(seed: int):
    """A PRNG key from any whole number: the low 32 bits seed it and the
    high bits are folded in, so seeds past 2**32 stay distinct."""
    import jax
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


# -- the program's model ---------------------------------------------------------

def model_config(cfg: dict):
    """The program's ModelConfig for a configuration file: its registry entry
    with the file's ``model`` overrides, checked against the file's widths."""
    import dataclasses
    from repro.configs.registry import get_arch
    mc = get_arch(cfg["registry"])
    kw = {}
    for key, val in cfg.get("model", {}).items():
        if isinstance(val, dict):
            kw[key] = dataclasses.replace(getattr(mc, key), **val)
        else:
            kw[key] = val
    mc = dataclasses.replace(mc, **kw)
    want = {"d_model": cfg["hidden_size"],
            "n_layers": cfg["num_hidden_layers"],
            "vocab": cfg["vocab_size"],
            "tie_embeddings": cfg["tie_word_embeddings"]}
    got = {k: getattr(mc, k) for k in want}
    if got != want:
        raise BenchError(f"program config {cfg['registry']} differs from "
                         f"{cfg['name']}: {got} != {want}")
    return mc


def _init_leaf(key, path: tuple[str, ...], sds, ln_scales: set,
               fills: dict):
    """One leaf drawn from the seed: a matrix N(0, 1/fan_in) with fan_in its
    second-to-last dimension (stacked layer dims excluded), the embedding
    N(0, 0.02²), a layer-norm scale ones, every other vector zeros; then
    the configuration's ``init`` fills, [start, end) fractions of the last
    axis set to a constant (gate biases)."""
    import jax
    import jax.numpy as jnp
    core = sds.shape[2:] if path[0] == "segments" else sds.shape
    name = path[-1]
    if name == "embed":
        x = 0.02 * jax.random.normal(key, sds.shape, jnp.float32)
    elif len(core) >= 2:
        x = jax.random.normal(key, sds.shape, jnp.float32) * core[-2] ** -0.5
    elif path in ln_scales:
        x = jnp.ones(sds.shape, jnp.float32)
    else:
        x = jnp.zeros(sds.shape, jnp.float32)
    n = sds.shape[-1]
    for lo, hi, val in fills.get(name, ()):
        x = x.at[..., int(lo * n):int(hi * n)].set(val)
    return x.astype(sds.dtype)


def _path_names(path) -> tuple[str, ...]:
    out = []
    for p in path:
        out.append(str(getattr(p, "key", getattr(p, "idx", p))))
    return tuple(out)


def make_weights(mc, seed: int, fills: dict | None = None):
    """The model's weights from ``seed``, on the device, in one jitted call,
    each leaf in the dtype the program keeps it in.  The program describes
    only the shapes (``jax.eval_shape`` of its init); the values are the
    benchmark's own."""
    import jax
    from repro.models import model as model_lib
    shapes = jax.eval_shape(lambda k: model_lib.init_params(k, mc),
                            jax.random.PRNGKey(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    paths = [_path_names(p) for p, _ in flat]
    ln_scales = {p for p in paths if p[-1] == "scale"
                 and p[:-1] + ("bias",) in set(paths)}

    @jax.jit
    def make(key):
        leaves = [_init_leaf(jax.random.fold_in(key, i), p, sds, ln_scales,
                             fills or {})
                  for i, (p, (_, sds)) in enumerate(zip(paths, flat))]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return make(seed_key(seed))


# -- one run -------------------------------------------------------------------

class Context:
    """What a driver and the metric readers see of one run."""

    def __init__(self, *, workload: str, cell: dict, config: dict,
                 traffic: dict, limits: dict, seed: int, seconds: float,
                 trace: bool, t0: float, base: str = HERE):
        self.workload = workload
        self.cell = cell
        self.chips = cell["chips"]
        self.config = config
        self.traffic = traffic
        self.limits = limits
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.t0 = t0
        self.base = base
        self.window_s: float | None = None
        self.reduction: dict | None = None
        self.values: dict = {}          # what drivers hand the metric readers
        self.device: dict = {}

    def module(self, kind: str, name: str | None = None):
        return load_module(kind, name or self.config["name"], self.base)

    def model_config(self):
        return model_config(self.config)

    def peaks(self) -> dict:
        return peaks(self.device.get("kind", ""))

    def make_weights(self, mc):
        return make_weights(mc, self.seed, self.config.get("init"))

    def memory_peak(self) -> int | None:
        return memory_peak_bytes()

    @contextlib.contextmanager
    def span(self, name: str):
        import jax
        with jax.profiler.TraceAnnotation(name):
            yield

    @contextlib.contextmanager
    def window(self):
        """The measured window: host clock around it; with ``trace`` the
        profiler records it, and the reduction is kept for the readers."""
        import jax
        logdir = tempfile.mkdtemp(prefix="bench-trace-") if self.trace \
            else None
        if logdir:
            jax.profiler.start_trace(logdir)
        try:
            tic = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.window"):
                yield self
            self.window_s = time.perf_counter() - tic
        finally:
            if logdir:
                jax.profiler.stop_trace()
        if logdir:
            tr = load_module(".", "trace", HERE)
            path = tr.find_xplane(logdir)
            self.reduction = tr.reduce(path) if path else None
            shutil.rmtree(logdir, ignore_errors=True)
            if self.reduction:
                top = sorted(self.reduction["kernels"].items(),
                             key=lambda kv: -kv[1]["seconds"])[:12]
                print(f"trace: window {self.reduction['window_s']:.6f} s, "
                      f"busy {self.reduction['busy_s']:.6f} s; by kind "
                      f"{json.dumps(dict(top))}", file=sys.stderr,
                      flush=True)


def judge(checks: dict) -> bool:
    """Every number compared lies at or under its limit."""
    return bool(checks) and all(
        c["value"] is not None and c["value"] == c["value"]
        and c["value"] <= c["limit"] for c in checks.values())


def context(workload: str, seed: int, seconds: float = 0.0,
            trace: bool = False, t0: float = 0.0, *, chip_check: bool = True,
            overrides: dict | None = None) -> Context:
    """A cell's parts, found by name, and the device checked.
    ``chip_check`` and ``overrides`` ({"config": {...}, "traffic": {...}})
    serve the tests, which drive a run on the CPU at a small size."""
    man = manifest()
    cell = find_cell(man, workload)
    config = load_json("configs", cell["config"])
    traffic = load_json("traffic", cell["traffic"])
    limits = load_json("limits", workload)
    for key, part in (overrides or {}).items():
        {"config": config, "traffic": traffic, "limits": limits}[key].update(
            part)
    device = require_chip(cell["chips"]) if chip_check else device_info()
    setup_compile_cache()
    ctx = Context(workload=workload, cell=cell, config=config,
                  traffic=traffic, limits=limits, seed=seed, seconds=seconds,
                  trace=trace, t0=t0)
    ctx.device = dict(device)
    ctx.manifest = man
    return ctx


def run(workload: str, seed: int, seconds: float, trace: bool, t0: float,
        **kw) -> dict:
    """One run of one cell; returns the result line as a dict."""
    ctx = context(workload, seed, seconds, trace, t0, **kw)
    man, traffic, base = ctx.manifest, ctx.traffic, ctx.base
    driver = load_module("drivers", traffic["driver"], base)
    out = driver.drive(ctx)

    if trace:
        metrics = {}
        for m in metrics_for(man, workload, "per_layer"):
            val = load_module("metrics", m["name"], base).read(ctx)
            if val is not None:
                metrics[m["name"]] = {"value": val, "unit": m["unit"]}
    else:
        metrics = {}
        for m in metrics_for(man, workload, "end_to_end"):
            if m["name"] in out["e2e"]:
                metrics[m["name"]] = {"value": out["e2e"][m["name"]],
                                      "unit": m["unit"]}
    dev = dict(ctx.device)
    dev["memory_peak_bytes"] = out.get("memory_peak_bytes")
    checks = out["checks"]
    result = {"correct": judge(checks) and not out.get("failed"),
              "attempted": out["attempted"], "failed": out["failed"],
              "metrics": metrics, "device": dev}
    if trace and ctx.reduction is not None:
        dev["busy_s"] = ctx.reduction["busy_s"]
        dev["window_s"] = ctx.reduction["window_s"]
        result["breakdown"] = {"device_ops": ctx.reduction["device_ops"],
                               "idle_gaps": ctx.reduction["idle_gaps"]}
    result["checks"] = checks
    return result
