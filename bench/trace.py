"""From a profiler trace and the benchmark's own host spans to numbers.

The benchmark wraps each replica's ``admit`` and ``step_many`` calls (the
fused admission and the fused decode block) in host spans named
``bench.<kind>.<replica>.<n>`` (:class:`Spans`); the profiler writes them
into its trace beside the device's operations, on the same clock.  Here:

* busy time of a device is the union of its operations' intervals, and
  the idle share is one less busy over the traced window;
* a call's device time is the summed duration of the device's executable
  events (``jit_admit_fused``, ``jit_multi``: the jitted functions' names
  as the program has them) that overlap the call's host span most;
* each idle gap is labelled with the host span that covers it, if any;
* an operation's scope path is the ``jax.named_scope``s of its op name,
  which the profiler keeps in the ``tf_op`` stat of the operation's event
  metadata (``"jit(multi)/while/body/closed_call/decode_step/dot_general:"``
  gives ``decode_step``); the device time of the matched calls is summed
  by scope, and, where a family counts it (``parts``), so is the work
  each scope needed.

Pure functions over plain tuples, so that a recorded trace (a fixture)
checks them without a chip.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field

#: executable (jitted function) behind each kind of host span
EXECUTABLES = {"admit": "admit_fused", "block": "multi"}
_SPAN = re.compile(r"^bench\.(admit|block)\.(\d+)\.(\d+)$")
#: device trace lines: executables, and the operations inside them
MODULE_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
#: operations whose events span the operations inside them (a loop's body)
_CONTAINERS = ("%while", "%conditional", "%call")
#: the event metadata stat that holds an operation's op name
SCOPE_STAT = "tf_op"
#: frames of an op name that JAX adds itself, not ``jax.named_scope``s
_FRAMES = re.compile(r"^(while|body|cond|closed_call|core_call|checkpoint|"
                     r"remat|custom_jvp_call|custom_vjp_call|branch_\d+)$"
                     r"|\(|->|,")


@dataclass
class Spans:
    """The host spans the benchmark opens around calls into the program:
    when each began on the host clock, and the work each call needed
    (``bench.counts``), filled in when the call returns."""

    work: dict = field(default_factory=dict)        # name -> (flops, bytes)
    host_start: dict = field(default_factory=dict)  # name -> perf_counter_ns
    #: name -> {scope path: (flops, bytes)}, where the family counts parts
    parts: dict = field(default_factory=dict)
    _n: int = 0

    def name(self, kind: str, replica: int) -> str:
        self._n += 1
        return f"bench.{kind}.{replica}.{self._n}"


def load(trace_dir: str, scopes: dict | None = None) -> list[tuple]:
    """Every event of the trace under ``trace_dir`` as
    ``(plane, line, name, start_ns, duration_ns)``.  With ``scopes``, also
    fill it with the op name of each device operation that has one, keyed
    by ``(plane, name)`` (:func:`op_names`)."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    out = []
    for path in paths:
        if scopes is not None:
            with open(path, "rb") as f:
                scopes.update(op_names(f.read()))
        pd = ProfileData.from_file(path)
        for plane in pd.planes:
            for line in plane.lines:
                for ev in line.events:
                    out.append((plane.name, line.name, ev.name,
                                float(ev.start_ns), float(ev.duration_ns)))
    return out


# -- op names from the XSpace protobuf ---------------------------------------
# ``ProfileData`` gives each event's own stats, not its metadata's, which is
# where the op name is; this reads the few fields that hold it straight
# from the wire format (tsl/profiler/protobuf/xplane.proto): XSpace.planes
# (1); XPlane.name (2), .event_metadata (4), .stat_metadata (5), both maps of
# key (1) to value (2); XEventMetadata.name (2), .stats (5); XStat
# .metadata_id (1), .str_value (5), .ref_value (7); XStatMetadata.name (2).


def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, start: int = 0, end: int | None = None):
    """``(field, value)`` of a message's fields: an int for a varint, a
    ``(start, end)`` slice of ``buf`` for a length-delimited field."""
    i, end = start, len(buf) if end is None else end
    while i < end:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif kind in (1, 5):
            value, i = None, i + (8 if kind == 1 else 4)
        else:
            raise ValueError(f"wire type {kind} in an xplane")
        yield key >> 3, value


def _map(buf, span) -> tuple[int, tuple]:
    key, value = 0, (0, 0)
    for f, v in _fields(buf, *span):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def op_names(data: bytes) -> dict:
    """``{(plane, event name): op name}`` of every device plane's event
    metadata that has a ``tf_op`` stat, from an ``.xplane.pb``'s bytes."""
    buf = memoryview(data)
    text = lambda span: bytes(buf[span[0]:span[1]]).decode()  # noqa: E731
    out = {}
    for f, plane in _fields(buf):
        if f != 1:
            continue
        name, events, stat_names = "", [], {}
        for g, v in _fields(buf, *plane):
            if g == 2:
                name = text(v)
            elif g == 4:
                events.append(_map(buf, v)[1])
            elif g == 5:
                sid, meta = _map(buf, v)
                stat_names[sid] = next(
                    (text(x) for h, x in _fields(buf, *meta) if h == 2), "")
        if not name.startswith("/device:"):
            continue
        scope_id = next((k for k, n in stat_names.items()
                         if n == SCOPE_STAT), None)
        if scope_id is None:
            continue
        for ev in events:
            ev_name, op = None, None
            for g, v in _fields(buf, *ev):
                if g == 2:
                    ev_name = text(v)
                elif g == 5:
                    stat = dict(_fields(buf, *v))
                    if stat.get(1) == scope_id:
                        op = text(stat[5]) if 5 in stat else \
                            stat_names.get(stat.get(7), "")
            if ev_name is not None and op:
                out[name, ev_name] = op
    return out


def scope_path(op_name: str) -> str:
    """The ``jax.named_scope``s of an op name, joined by ``/``: the last
    part (the primitive) and the frames JAX adds itself (``jit(...)``,
    ``while``, ``body``, ``closed_call``, an einsum's spec, ...) dropped."""
    parts = op_name.rsplit(":", 1)[0].split("/")[:-1]
    return "/".join(p for p in parts if p and not _FRAMES.search(p))


def union(intervals) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _executable(name: str) -> str | None:
    for kind, fn in EXECUTABLES.items():
        if re.match(rf"^jit_{fn}(\b|[^a-zA-Z0-9_])", name) or name == fn:
            return kind
    return None


def reduce(events, window_ns: tuple[float, float], replica_plane: dict,
           spans: Spans, scopes: dict | None = None) -> dict:
    """Reduce a trace to what the per-layer metrics read.

    ``window_ns`` is the traced window on the trace's clock;
    ``replica_plane`` maps each replica to the name of its device's plane;
    ``scopes`` maps ``(plane, operation name)`` to the operation's op name
    (:func:`load`).  Besides busy time, the matched ``calls`` and idle
    gaps: ``op_time``, every operation's device seconds in the window;
    ``scope_time``, the device seconds of the operations inside the
    matched calls' executables by scope path, each path also counted in
    every path it is nested in (``decode_step`` holds
    ``decode_step/experts``); and, where ``spans`` has ``parts``,
    ``scope_work``, their ``(flops, bytes)`` summed over the same calls.
    """
    w0, w1 = window_ns
    planes = sorted(set(replica_plane.values()))
    by_line = defaultdict(list)
    for plane, line, name, start, dur in events:
        by_line[plane, line].append((name, start, start + dur))
    busy_by_plane, ops_time = {}, defaultdict(float)
    modules = {p: [(s, e, _executable(n)) for n, s, e
                   in by_line[p, MODULE_LINE]] for p in planes}
    for plane in planes:
        line = OPS_LINE if by_line[plane, OPS_LINE] else MODULE_LINE
        iv = []
        for name, start, end in by_line[plane, line]:
            a, b = max(start, w0), min(end, w1)
            if b > a:
                iv.append((a, b))
                if line == OPS_LINE and not name.startswith(_CONTAINERS):
                    ops_time[name[:120]] += (b - a) * 1e-9
        busy_by_plane[plane] = union(iv)
    # host spans -> device time of the executables they launched: each
    # executable event goes to the span of its kind on its device that it
    # overlaps most (the device's clock and the host's may differ by a
    # fraction of a millisecond, so an event can start just before its span)
    host = [(e[2], e[3], e[3] + e[4]) for e in events
            if not e[0].startswith("/device:") and _SPAN.match(e[2])]
    dev = defaultdict(float)
    owner = defaultdict(list)  # plane -> (start, end, span) of executables
    for plane in planes:
        mine = [(n, a, b) for n, a, b in host
                if replica_plane.get(int(_SPAN.match(n).group(2))) == plane]
        for start, end, kind in modules[plane]:
            best, most = None, 0.0
            for n, a, b in mine:
                overlap = min(end, b) - max(start, a)
                if overlap > most and _SPAN.match(n).group(1) == kind:
                    best, most = n, overlap
            if best is not None:
                dev[best] += end - start
                owner[plane].append((start, end, best))
    calls, matched = [], set()
    for name, a, b in host:
        if a >= w0 and b <= w1 and dev[name] > 0 and name in spans.work:
            matched.add(name)
            flops, nbytes = spans.work[name]
            calls.append((_SPAN.match(name).group(1), flops, nbytes,
                          dev[name] * 1e-9))
    busy = [sum(b - a for a, b in busy_by_plane[p]) * 1e-9 for p in planes]
    gaps = []
    for p in planes:
        edges = [w0] + [x for ab in busy_by_plane[p] for x in ab] + [w1]
        gaps += [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
    idle = []
    for a, b in gaps:
        mid = (a + b) / 2
        label = next((_SPAN.match(n).group(1) + " call"
                      for n, s, e in host if s <= mid <= e),
                     "outside admit and block calls")
        idle.append([label, (b - a) * 1e-9])
    op_time = dict(sorted(ops_time.items(), key=lambda kv: -kv[1]))
    out = {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": sum(busy) / len(busy) if busy else 0.0,
        "devices": len(planes),
        "calls": calls,
        "device_ops": list(op_time.items())[:10],
        "idle_gaps": idle,
        "op_time": op_time,
        "scope_time": _scope_time(by_line, planes, owner, matched,
                                  scopes or {}),
    }
    if spans.parts:
        work = defaultdict(lambda: [0.0, 0.0])
        for name in matched:
            for scope, (flops, nbytes) in spans.parts.get(name, {}).items():
                work[scope][0] += flops
                work[scope][1] += nbytes
        out["scope_work"] = {k: tuple(v) for k, v in work.items()}
    return out


def _scope_time(by_line, planes, owner, matched, scopes) -> dict:
    """Device seconds by scope path of the operations inside the
    executables of the ``matched`` calls."""
    paths = {k: scope_path(v) for k, v in scopes.items()}
    out = defaultdict(float)
    for plane in planes:
        runs = sorted(r for r in owner[plane] if r[2] in matched)
        starts = [r[0] for r in runs]
        for name, start, end in by_line[plane, OPS_LINE]:
            path = paths.get((plane, name))
            if not path or name.startswith(_CONTAINERS):
                continue
            i = bisect.bisect_right(starts, start) - 1
            if i < 0 or start >= runs[i][1]:
                continue
            parts = path.split("/")
            for k in range(1, len(parts) + 1):
                out["/".join(parts[:k])] += (end - start) * 1e-9
    return dict(out)


def host_to_trace_offset(events, spans_host_ns: dict) -> float | None:
    """Trace clock minus ``time.perf_counter_ns`` clock, from the spans the
    benchmark timed itself (median over spans found in both)."""
    diffs = sorted(start - spans_host_ns[name] for _, _, name, start, _ in
                   events if name in spans_host_ns)
    return diffs[len(diffs) // 2] if diffs else None


def call_work(trace: dict, peak: dict, kind: str | None = None):
    """(FLOPs, least seconds, device seconds) summed over the traced
    window's calls of ``kind`` (every kind when None)."""
    from bench.counts import least_seconds

    rows = [(f, least_seconds(f, b, peak), dev)
            for k, f, b, dev in trace["calls"] if kind in (None, k)]
    return tuple(map(sum, zip(*rows))) if rows else (0.0, 0.0, 0.0)


def roofline_pct(ctx, kind: str) -> float | None:
    """Least time for the needed work over the device time of the
    executables that did it, in per cent; None when nothing was matched."""
    if ctx.trace is None:
        return None
    _, need, dev = call_work(ctx.trace, ctx.peak, kind)
    return 100.0 * need / dev if dev > 0 else None
