"""Device time by the program's named scopes, and host time by its
``fed.*`` spans, from a JAX profiler trace (``.xplane.pb``).

The program names its work with ``jax.named_scope`` (round stages
``fed.*``, model blocks ``model.*`` and ``moe.*``).  XLA keeps each op's
JAX name stack in its ``op_name`` metadata, and the profiler writes it
into the ``tf_op`` stat of the op's event metadata on the device plane::

    jit(chunk_fn)/while/body/closed_call/fed.client_update/while/body/
    closed_call/vmap(transpose(jvp()))/.../checkpoint/rematted_computation/
    .../moe.dispatch/jit(searchsorted)/.../gather

``jax.profiler.ProfileData`` shows event stats only, not the stats of an
event's metadata, so this module reads the few fields of the XSpace
protobuf it needs itself, with the standard library.

``reduce(path, window=None)`` returns::

    {"scopes": {scope: seconds, ..., "unscoped": seconds},
     "program_spans": {"seconds": {span: [seconds, ...]},
                       "idle_s": {span: seconds, "no fed span": seconds}}}

An op belongs to the innermost scope of ``SCOPES`` on its name stack, once
transform wrappers (``jvp(…)``, ``transpose(…)``, ``vmap(…)``) are
stripped and ``checkpoint`` / ``rematted_computation`` dropped; its
clipped device time counts for that scope and for every scope of
``SCOPES`` enclosing it, so a scope's time includes its sub-scopes.  An op
under none is ``unscoped``.  Times are averaged over the device planes,
and control-flow instructions are left out, as in ``trace.py``; the
window is ``trace.py``'s too.  ``program_spans`` lists the durations of
the ``fed.*`` host spans that meet the window, and the seconds in which
no op ran on a device, each summed under the innermost ``fed.*`` span
that covers it (averaged over the device planes).
"""
from __future__ import annotations

import re
import struct
from collections import defaultdict

import harness

SCOPES = ("fed.client_update", "fed.local_step", "fed.flat_boundary",
          "fed.aggregate", "fed.orientation",
          "model.embed", "model.attention", "model.head", "model.loss",
          "moe.route", "moe.dispatch", "moe.experts", "moe.combine")
UNSCOPED = "unscoped"
SPAN_PREFIX = "fed."
NO_SPAN = "no fed span"
DROPPED = ("checkpoint", "rematted_computation")
_WRAPPED = re.compile(r"^[\w.-]*\((.*)\)$")


# -- the XSpace protobuf, read with the standard library -----------------------
#
# XSpace.planes = 1; XPlane: name = 2, lines = 3, event_metadata = 4 and
# stat_metadata = 5 (maps: key 1, value 2); XLine: name = 2,
# timestamp_ns = 3, events = 4; XEvent: metadata_id = 1, offset_ps = 2,
# duration_ps = 3; XEventMetadata: id = 1, name = 2, stats = 5;
# XStatMetadata: id = 1, name = 2; XStat: metadata_id = 1, str_value = 5,
# ref_value = 7 (the id of a stat metadata whose name is the value).

def _varint(buf: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf) -> list[tuple[int, int | memoryview]]:
    """The (field number, value) pairs of one message: an int for a
    varint or fixed field, a memoryview for a length-delimited one."""
    buf = memoryview(buf)
    out, i, n = [], 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire == 1:
            val, i = struct.unpack_from("<Q", buf, i)[0], i + 8
        elif wire == 5:
            val, i = struct.unpack_from("<I", buf, i)[0], i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        out.append((num, val))
    return out


def text(val) -> str:
    return bytes(val).decode("utf-8", "replace")


def _signed(val: int) -> int:
    return val - (1 << 64) if val >= 1 << 63 else val


def entries(pairs: list) -> dict[int, list]:
    """A protobuf map's entries: key -> the fields of its value."""
    out = {}
    for entry in pairs:
        kv = dict(fields(entry))
        out[_signed(kv.get(1, 0))] = fields(kv.get(2, b""))
    return out


def plane_parts(buf) -> tuple[str, list, list, list]:
    """An XPlane's name, lines, event metadata and stat metadata."""
    name, lines, ev_meta, st_meta = "", [], [], []
    for num, val in fields(buf):
        if num == 2:
            name = text(val)
        elif num == 3:
            lines.append(val)
        elif num == 4:
            ev_meta.append(val)
        elif num == 5:
            st_meta.append(val)
    return name, lines, ev_meta, st_meta


def read_planes(path: str) -> dict:
    """Device and host events of a trace, by plane kind.

    ``{"devices": [[(start_ns, end_ns, name, tf_op), ...] per device],
    "host": [(start_ns, end_ns, name), ...]}``: a device's ``XLA Ops``
    events with the ``tf_op`` of each event's own metadata entry (``""``
    without one), and every event of the host planes.  Times are
    ``timestamp_ns`` of the line plus the event's offset, in whole
    nanoseconds, as ``jax.profiler.ProfileData`` gives them."""
    with open(path, "rb") as f:
        space = f.read()
    devices, host = [], []
    for num, plane in fields(space):
        if num != 1:
            continue
        name, lines, ev_meta, st_meta = plane_parts(plane)
        is_device = name.startswith("/device:TPU:")
        if not (is_device or name.startswith("/host:")):
            continue
        stat_names = {k: text(dict(v).get(2, b""))
                      for k, v in entries(st_meta).items()}
        metas = {}
        for k, v in entries(ev_meta).items():
            meta = dict(v)
            tf_op = ""
            if is_device:
                for n, stat in v:
                    if n != 5:
                        continue
                    st = dict(fields(stat))
                    if stat_names.get(_signed(st.get(1, 0))) != "tf_op":
                        continue
                    if 5 in st:
                        tf_op = text(st[5])
                    elif 7 in st:
                        tf_op = stat_names.get(_signed(st[7]), "")
            metas[k] = (text(meta.get(2, b"")), tf_op)
        ops = []
        for line in lines:
            lf = fields(line)
            lname = next((text(v) for n, v in lf if n == 2), "")
            if is_device and lname != "XLA Ops":
                continue
            base = _signed(next((v for n, v in lf if n == 3), 0))
            for n, ev in lf:
                if n != 4:
                    continue
                e = dict(fields(ev))
                start = base + _signed(e.get(2, 0)) // 1000
                end = start + _signed(e.get(3, 0)) // 1000
                ename, tf_op = metas.get(_signed(e.get(1, 0)), ("", ""))
                if is_device:
                    ops.append((start, end, ename, tf_op))
                else:
                    host.append((start, end, ename))
        if is_device:
            devices.append(ops)
    return {"devices": devices, "host": host}


# -- attribution ---------------------------------------------------------------

def stack(tf_op: str) -> list[str]:
    """A name stack's components, outermost first, with transform
    wrappers stripped and remat markers dropped:
    ``a/vmap(transpose(jvp(model.head)))/checkpoint/dot:`` ->
    ``["a", "model.head", "dot"]``."""
    parts, depth, cur = [], 0, []
    for ch in tf_op.rstrip(":"):
        if ch == "/" and depth == 0:
            parts.append("".join(cur))
            cur = []
            continue
        depth += (ch == "(") - (ch == ")")
        cur.append(ch)
    parts.append("".join(cur))
    out = []
    for part in parts:
        m = _WRAPPED.match(part)
        while m:
            part = m.group(1)
            m = _WRAPPED.match(part)
        if part and part not in DROPPED:
            out.append(part)
    return out


def scopes_of(tf_op: str) -> list[str]:
    """The scopes of ``SCOPES`` on an op's name stack, outermost first,
    each once; the last is the op's own."""
    out = []
    for part in stack(tf_op):
        if part in SCOPES and part not in out:
            out.append(part)
    return out


def _span_idle(gaps: list, spans: list) -> dict[str, float]:
    """Seconds of each gap under the innermost (shortest) span covering
    it, piece by piece; ``NO_SPAN`` where none covers it."""
    out: dict[str, float] = defaultdict(float)
    for a, b in gaps:
        cuts = sorted({a, b} | {t for s0, s1, _ in spans
                                for t in (s0, s1) if a < t < b})
        for x, y in zip(cuts, cuts[1:]):
            cover = [(s1 - s0, name) for s0, s1, name in spans
                     if s0 <= x and y <= s1]
            out[min(cover)[1] if cover else NO_SPAN] += (y - x) * 1e-9
    return out


def reduce(path: str, window: tuple[int, int] | None = None) -> dict:
    """``scopes`` and ``program_spans`` of a trace (module docstring)."""
    tr = harness.load_module(".", "trace")
    planes = read_planes(path)
    devices = planes["devices"]
    if window is None:
        win = [s for s in planes["host"] if s[2] == tr.WINDOW_SPAN]
        if win:
            window = (win[0][0], win[0][1])
        else:
            starts = [a for ops in devices for a, _, _, _ in ops]
            ends = [b for ops in devices for _, b, _, _ in ops]
            window = (min(starts), max(ends)) if starts else (0, 0)
    lo, hi = window
    chips = max(len(devices), 1)
    spans = [s for s in planes["host"] if s[2].startswith(SPAN_PREFIX)
             and tr._clip(s[0], s[1], lo, hi) is not None]

    seconds: dict[str, float] = defaultdict(float)
    idle: dict[str, float] = defaultdict(float)
    for ops in devices:
        busy = []
        for a, b, name, tf_op in ops:
            c = tr._clip(a, b, lo, hi)
            if c is None or tr.kernel_of(tr.op_name(name)) in tr.CONTAINERS:
                continue
            busy.append(c)
            dt = (c[1] - c[0]) * 1e-9 / chips
            for scope in scopes_of(tf_op) or [UNSCOPED]:
                seconds[scope] += dt
        gaps, prev = [], lo
        for a, b in tr._union(busy):
            if a > prev:
                gaps.append((prev, a))
            prev = b
        if hi > prev:
            gaps.append((prev, hi))
        for name, s in _span_idle(gaps, spans).items():
            idle[name] += s / chips
    durations: dict[str, list[float]] = defaultdict(list)
    for s0, s1, name in spans:
        durations[name].append((s1 - s0) * 1e-9)
    return {"scopes": dict(seconds),
            "program_spans": {"seconds": dict(durations),
                              "idle_s": dict(idle)}}
