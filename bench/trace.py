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
* each idle gap is labelled with the host span that covers it, if any.

Pure functions over plain tuples, so that a recorded trace (a fixture)
checks them without a chip.
"""

from __future__ import annotations

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


@dataclass
class Spans:
    """The host spans the benchmark opens around calls into the program:
    when each began on the host clock, and the work each call needed
    (``bench.counts``), filled in when the call returns."""

    work: dict = field(default_factory=dict)        # name -> (flops, bytes)
    host_start: dict = field(default_factory=dict)  # name -> perf_counter_ns
    _n: int = 0

    def name(self, kind: str, replica: int) -> str:
        self._n += 1
        return f"bench.{kind}.{replica}.{self._n}"


def load(trace_dir: str) -> list[tuple]:
    """Every event of the trace under ``trace_dir`` as
    ``(plane, line, name, start_ns, duration_ns)``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    out = []
    for path in paths:
        pd = ProfileData.from_file(path)
        for plane in pd.planes:
            for line in plane.lines:
                for ev in line.events:
                    out.append((plane.name, line.name, ev.name,
                                float(ev.start_ns), float(ev.duration_ns)))
    return out


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
           spans: Spans) -> dict:
    """Reduce a trace to what the per-layer metrics read.

    ``window_ns`` is the traced window on the trace's clock;
    ``replica_plane`` maps each replica to the name of its device's plane.
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
    calls = []
    for name, a, b in host:
        if a >= w0 and b <= w1 and dev[name] > 0 and name in spans.work:
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
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": sum(busy) / len(busy) if busy else 0.0,
        "devices": len(planes),
        "calls": calls,
        "device_ops": sorted(ops_time.items(), key=lambda kv: -kv[1])[:10],
        "idle_gaps": idle,
    }


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
