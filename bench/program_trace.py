"""The program's own spans beside a profiler trace and the host's stamps.

With spans on (``ClusterServingEngine.enable_spans``), the serving path
records ``ham.*`` spans ``(name, replica, rid, t_start_ns, t_end_ns)`` on
the ``time.perf_counter_ns`` clock (``repro.serve.spans``; docs/serving.md,
"Tracing", lists them).  Here:

* :class:`ProgramSpans` is what the readers of those spans take, as
  ``ctx.spans``: the records, the window's requests and, for a traced run,
  the device's idle time labelled by program span;
* :func:`idle_by_span` labels each replica's idle device time with the
  innermost span its decode loop was in at the time (the host's spans
  are put on the trace's clock by the offset ``bench.trace`` finds);
* :func:`host_share` is the share of the window in which the device waited
  on host work: idle while the loop was in an iteration, but not in one
  of its waits on the device;
* :func:`ttft_stages` splits each request's time to first token into the
  stages the spans and the host's stamps bound, and checks the spans
  against the host's stamp of the lease ack.

Pure functions over plain tuples, so that they are checked without a chip.
"""

from __future__ import annotations

from collections import defaultdict
from dataclasses import dataclass

from bench import trace as tr

#: spans recorded per request across threads and iterations, not nested
REQUEST_SPANS = ("ham.req.queued", "ham.req.held")
#: the spans of a decode loop's thread that are not part of an iteration
OUTSIDE_ITER = ("ham.loop.park",)
#: the host waiting on the device, inside an iteration
WAITS = ("ham.admit.wait", "ham.block.wait")
#: idle time in no program span
NO_SPAN = "no program span"
#: the stages of a request's time to first token, in order
STAGES = ("host_admission", "loop_queue", "admission", "held", "to_host")


@dataclass
class ProgramSpans:
    """The program's span records of one run, for the metric readers."""

    records: list          # (name, replica, rid, t0_ns, t1_ns)
    dropped: int           # records the ring let go of
    rids: frozenset        # the requests due in the window
    idle: dict | None = None   # idle_by_span of the traced window


def durations_ms(spans: ProgramSpans, name: str) -> list[float]:
    """Durations (ms) of the window's requests' spans called ``name``."""
    return [(b - a) * 1e-6 for n, _, rid, a, b in spans.records
            if n == name and rid in spans.rids]


def busy_intervals(events, plane: str, window_ns) -> list[tuple]:
    """The union of the device's operation intervals on ``plane``, clipped
    to the window, as ``bench.trace.reduce`` counts busy time (it computes
    them inline and does not return them)."""
    w0, w1 = window_ns
    ops = [(s, s + d) for p, line, _, s, d in events
           if p == plane and line == tr.OPS_LINE]
    if not ops:
        ops = [(s, s + d) for p, line, _, s, d in events
               if p == plane and line == tr.MODULE_LINE]
    return tr.union((max(a, w0), min(b, w1)) for a, b in ops
                    if min(b, w1) > max(a, w0))


def innermost(spans) -> list[tuple[float, float, str]]:
    """Disjoint segments ``(start, end, name)`` of the union of ``spans``
    (``(start, end, name)``, nested as one thread's spans are), each named
    by the innermost span open over it."""
    order = sorted(spans, key=lambda s: (s[0], -s[1]))
    points = sorted({x for a, b, _ in spans for x in (a, b)})
    out, stack, j = [], [], 0
    for p, q in zip(points, points[1:]):
        while j < len(order) and order[j][0] <= p:
            stack.append(order[j])
            j += 1
        stack = [s for s in stack if s[1] > p]
        if stack:
            out.append((p, q, stack[-1][2]))
    return out


def label(gaps, segments) -> dict[str, float]:
    """Seconds of each gap (sorted, disjoint, ns) under each segment's name
    (sorted, disjoint), and under ``NO_SPAN`` where no segment lies."""
    out: dict[str, float] = defaultdict(float)
    j = 0
    for a, b in gaps:
        covered = 0.0
        while j < len(segments) and segments[j][1] <= a:
            j += 1
        k = j
        while k < len(segments) and segments[k][0] < b:
            s, e, name = segments[k]
            part = min(b, e) - max(a, s)
            if part > 0:
                out[name] += part * 1e-9
                covered += part
            k += 1
        if b - a > covered:
            out[NO_SPAN] += (b - a - covered) * 1e-9
    return dict(out)


def idle_by_span(events, window_ns, replica_plane: dict, records,
                 to_trace_ns: float) -> dict:
    """Each replica's idle device time in the window, in seconds, by the
    innermost program span of its decode loop at the time.  ``to_trace_ns``
    is the trace's clock less ``perf_counter_ns``."""
    w0, w1 = window_ns
    by_replica = {}
    for replica, plane in sorted(replica_plane.items()):
        busy = busy_intervals(events, plane, window_ns)
        edges = [w0] + [x for ab in busy for x in ab] + [w1]
        gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        mine = [(a + to_trace_ns, b + to_trace_ns, n)
                for n, rep, _, a, b in records
                if rep == replica and n not in REQUEST_SPANS]
        by_replica[replica] = label(gaps, innermost(mine))
    return {"window_s": (w1 - w0) * 1e-9, "by_replica": by_replica}


def host_share(idle: dict | None) -> float | None:
    """Mean over replicas of the share of the window in which the device
    was idle while its loop was inside an iteration but outside its waits
    on the device."""
    if not idle or not idle["by_replica"] or idle["window_s"] <= 0:
        return None
    skip = {NO_SPAN, *OUTSIDE_ITER, *WAITS}
    shares = [sum(s for n, s in spans.items() if n not in skip)
              / idle["window_s"] for spans in idle["by_replica"].values()]
    return sum(shares) / len(shares)


def covered_share(idle: dict) -> float | None:
    """Share of the idle time that lies inside some program span."""
    total = sum(s for spans in idle["by_replica"].values()
                for s in spans.values())
    none = sum(spans.get(NO_SPAN, 0.0)
               for spans in idle["by_replica"].values())
    return 1.0 - none / total if total > 0 else None


def ttft_stages(requests: dict, records, to_perf_ns: float) -> dict:
    """rid -> the stages (ms) of its time to first token, and its lease ack.
    ``requests`` maps rid -> {"due", "t_admit", "t_first"} on the
    ``time.monotonic`` clock; ``to_perf_ns`` is ``perf_counter_ns`` less
    ``monotonic_ns``.  Stages: due -> enqueued in the loop (the host's
    admission), the loop's queue, the admission, the first token held on
    the worker, the flush -> the host's receipt.  Each stage starts where
    the one before ends, so they sum to the TTFT by construction; what
    checks the spans against the host is ``lease_ack``: the host's own
    stamp of the lease ack (``t_admit``) less the worker's enqueue, which
    the ack follows, so it is never negative where the clocks agree."""
    spans: dict = defaultdict(dict)
    for name, _, rid, a, b in records:
        if name in ("ham.req.queued", "ham.loop.admit", "ham.req.held"):
            spans[rid].setdefault(name, []).append((a, b))
    out = {}
    for rid, q in requests.items():
        got = spans.get(rid, {})
        if q.get("t_first") is None or q.get("t_admit") is None or any(
                len(got.get(n, ())) != 1 for n in
                ("ham.req.queued", "ham.loop.admit", "ham.req.held")):
            continue
        (q0, q1), = got["ham.req.queued"]
        (a0, a1), = got["ham.loop.admit"]
        (h0, h1), = got["ham.req.held"]
        due, admit, first = (q[k] * 1e9 + to_perf_ns
                             for k in ("due", "t_admit", "t_first"))
        stages = dict(zip(STAGES, ((q0 - due) * 1e-6, (q1 - q0) * 1e-6,
                                   (a1 - a0) * 1e-6, (h1 - h0) * 1e-6,
                                   (first - h1) * 1e-6)))
        out[rid] = dict(stages, ttft=(first - due) * 1e-6,
                        lease_ack=(admit - q0) * 1e-6)
    return out
