"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

The device plane (``/device:TPU:<n>``) carries an ``XLA Ops`` line whose
events are HLO instructions; their names are the instruction's text, which
starts ``%<name> = <shape> ...``.  A Pallas kernel's instruction is named
after its ``pallas_call`` (``%calibrated_update.1 = f32[2,16777216]...``).
Host spans written with ``jax.profiler.TraceAnnotation`` lie on the host
plane (``/host:CPU``) on the same clock.

``reduce(path, window=(t0_ns, t1_ns) | None)`` returns::

    {"window_s", "busy_s", "chips",
     "kernels": {name: {"seconds", "calls",
                        "shapes": {"bf16[4,16,1024,128]": calls per chip}}},
     "device_ops": [[op, seconds], ...]  (10 largest, averaged over chips),
     "idle_gaps": [[host span, seconds], ...]  (10 longest gaps),
     "spans": {name: [seconds, ...]}}    (host spans named ``bench.*``)

Busy time is the union of the ``XLA Ops`` intervals inside the window
(control-flow instructions, which span their bodies' ops, left out),
averaged over the device planes.  The window defaults to the span of the
host's ``bench.window`` annotation, else to the extent of the device ops.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

# control-flow instructions span the ops of their bodies, which the trace
# lists on their own: they are left out of busy time and the op lists
CONTAINERS = ("while", "conditional", "call")
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
_OP_NAME = re.compile(r"^%?([^\s=]+)")
_SHAPE = re.compile(r"=\s*\(?\s*([a-z0-9]+\[[0-9,]*\])")


def find_xplane(log_dir: str) -> str | None:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def op_name(event_name: str) -> str:
    """``%flash_fwd.1 = (bf16[...]...`` -> ``flash_fwd.1``."""
    m = _OP_NAME.match(event_name)
    return m.group(1) if m else event_name


def kernel_of(name: str) -> str:
    """Instruction name without its numeric suffix: ``flash_fwd.1`` ->
    ``flash_fwd``; ``fusion.12`` -> ``fusion``."""
    return re.sub(r"(\.\d+)+$", "", name)


def parse_shape(key: str) -> tuple[str, tuple[int, ...]]:
    """``"f32[2,128]"`` -> ``("f32", (2, 128))``."""
    dtype, dims = key.split("[")
    return dtype, tuple(int(d) for d in dims.rstrip("]").split(",") if d)


def out_shape(event_name: str) -> tuple[str, tuple[int, ...]] | None:
    """First output shape of an instruction: ``("f32", (2, 16777216))``."""
    m = _SHAPE.search(event_name)
    return parse_shape(m.group(1)) if m else None


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _clip(a: int, b: int, lo: int, hi: int):
    a, b = max(a, lo), min(b, hi)
    return (a, b) if b > a else None


def reduce(path: str, window: tuple[int, int] | None = None) -> dict:
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    devices, host_spans = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [(int(e.start_ns), int(e.start_ns + e.duration_ns),
                            e.name) for e in line.events]
            devices.append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        host_spans.append((int(e.start_ns),
                                           int(e.start_ns + e.duration_ns),
                                           e.name))
    if window is None:
        win = [s for s in host_spans if s[2] == WINDOW_SPAN]
        if win:
            window = (win[0][0], win[0][1])
        else:
            starts = [a for ops in devices for a, _, _ in ops]
            ends = [b for ops in devices for _, b, _ in ops]
            window = (min(starts), max(ends)) if starts else (0, 0)
    lo, hi = window
    chips = max(len(devices), 1)

    busy_ns = 0
    op_time: dict[str, float] = defaultdict(float)
    kernels: dict[str, dict] = {}
    gaps: list[tuple[int, int]] = []
    for ops in devices:
        clipped = []
        for a, b, name in ops:
            c = _clip(a, b, lo, hi)
            oname = op_name(name)
            kname = kernel_of(oname)
            if c is None or kname in CONTAINERS:
                continue
            clipped.append(c)
            op_time[oname] += (c[1] - c[0]) / chips
            k = kernels.setdefault(kname, {"seconds": 0.0, "calls": 0,
                                           "shapes": {}})
            k["seconds"] += (c[1] - c[0]) * 1e-9 / chips
            k["calls"] += 1
            shape = out_shape(name)
            if shape is not None:
                key = f"{shape[0]}{list(shape[1])}".replace(" ", "")
                k["shapes"][key] = k["shapes"].get(key, 0) + 1.0 / chips
        busy = _union(clipped)
        busy_ns += sum(b - a for a, b in busy)
        prev = lo
        for a, b in busy:
            if a > prev:
                gaps.append((prev, a))
            prev = b
        if hi > prev:
            gaps.append((prev, hi))

    def doing(a: int, b: int) -> str:
        """The bench.* host span that covers most of the gap (the shorter
        one on a tie), or "no host span" where most of it is uncovered."""
        best, best_key, covered = "no host span", (0, 0), []
        for s0, s1, name in host_spans:
            if name == WINDOW_SPAN:
                continue
            c = _clip(s0, s1, a, b)
            if c is not None:
                covered.append(c)
                key = (c[1] - c[0], -(s1 - s0))
                if key > best_key:
                    best, best_key = name, key
        uncovered = (b - a) - sum(y - x for x, y in _union(covered))
        return "no host span" if uncovered > best_key[0] else best

    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    spans: dict[str, list[float]] = defaultdict(list)
    for s0, s1, name in host_spans:
        if _clip(s0, s1, lo, hi) is not None:
            spans[name].append((s1 - s0) * 1e-9)
    top_ops = sorted(op_time.items(), key=lambda kv: kv[1], reverse=True)
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_ns * 1e-9 / chips,
        "chips": len(devices),
        "kernels": kernels,
        "device_ops": [[n, t * 1e-9] for n, t in top_ops[:10]],
        "idle_gaps": [[doing(a, b), (b - a) * 1e-9] for a, b in gaps[:10]],
        "spans": dict(spans),
    }
