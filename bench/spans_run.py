#!/usr/bin/env python3
"""Run one benchmark cell once with the program's own spans on.

    python3 bench/spans_run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

A tool for reading where the time goes, not part of the benchmark: the
run is ``bench/run.py``'s own (the same build, warm-up, traffic, window,
profiler and check), with ``ClusterServingEngine.enable_spans`` called
once the warm-up is done and the replicas' lane counters added to the
counter snapshots.  ``bench/run.py`` with the same arguments is the same
run with spans off; with ``--trace 0`` the two give the cost of the spans.
The last line of standard output is one JSON object: ``result``, the line
``bench/run.py`` would print, and ``program``, what the spans read:

* the per-layer metrics of the program's spans (``bench/metrics``:
  ``loop_queue_p95_ms``, ``first_token_held_p50_ms``,
  ``lanes_past_budget_share``, ``idle_host_share``), where they read;
* the median of each stage of the window's times to first token, and of
  the lease ack against the host's own stamp
  (``bench.program_trace.ttft_stages``);
* with ``--trace 1``, the idle seconds of each replica's device by the
  innermost program span at the time, also logged to standard error.

It wraps functions of ``bench.run`` for the length of the run, since the
harness has no hook for spans yet; once ``bench/run.py`` turns the spans on
itself, this file goes.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import statistics
import sys
import time
import types
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import program_trace as pt  # noqa: E402
from bench import run, traffic  # noqa: E402
from bench import trace as tr  # noqa: E402

#: span records the log holds: far more than one window records
CAPACITY = 1 << 20
LANES = ("lanes_stepped", "lanes_past_budget")
READERS = ("loop_queue_p95_ms", "first_token_held_p50_ms",
           "lanes_past_budget_share", "idle_host_share.lat",
           "idle_host_share.tput", "route_imbalance")


@contextlib.contextmanager
def instrument():
    """For the length of the ``with``, ``bench.run`` turns spans on after
    the warm-up and keeps what the span readers need in the dict this
    yields.  Nothing the harness measures changes."""
    box: dict = {}
    saved = {(run, n): getattr(run, n)
             for n in ("warm", "counters", "drive", "reduce_trace")}
    saved[(tr, "load")] = tr.load
    warm, counters, drive = run.warm, run.counters, run.drive
    load, reduce_trace = tr.load, run.reduce_trace

    def warm_then_enable(eng, lengths, block):
        warm(eng, lengths, block)
        box["log"] = eng.enable_spans(CAPACITY)

    def counters_with_lanes(eng, program=None):
        out = counters(eng, program)
        for key in LANES:
            out[key] = sum(getattr(e, key) for _, e, _ in run.replicas(eng))
        return out

    def drive_kept(*a, **k):
        box["run"] = drive(*a, **k)
        return box["run"]

    def load_kept(trace_dir, scopes=None):
        box["events"] = load(trace_dir, scopes)
        return box["events"]

    def reduce_kept(trace_box, bench_spans, plane_of):
        box.update(window=(trace_box["w0"], trace_box["w1"]),
                   bench_spans=bench_spans, plane_of=plane_of)
        return reduce_trace(trace_box, bench_spans, plane_of)

    run.warm, run.counters, run.drive = (warm_then_enable,
                                         counters_with_lanes, drive_kept)
    tr.load, run.reduce_trace = load_kept, reduce_kept
    try:
        yield box
    finally:
        for (mod, name), fn in saved.items():
            setattr(mod, name, fn)


def program_view(box: dict, log=print) -> dict:
    """What the spans of an instrumented run read."""
    r = box["run"]
    reqs = r["requests"]
    records = box["log"].records()
    spans = pt.ProgramSpans(records=records, dropped=box["log"].dropped,
                            rids=frozenset(r["measured"]))
    if "events" in box:
        ev = box["events"]
        offset = tr.host_to_trace_offset(ev, box["bench_spans"].host_start)
        window = tuple(w + offset for w in box["window"])
        spans.idle = pt.idle_by_span(ev, window, box["plane_of"], records,
                                     offset)
        for rep, by in spans.idle["by_replica"].items():
            for name, s in sorted(by.items(), key=lambda kv: -kv[1]):
                log(f"idle on replica {rep}: {s:.6f} s in {name}")
    ctx = types.SimpleNamespace(snap=r["snap"], spans=spans)
    out = {"records": len(records), "dropped": spans.dropped,
           "metrics": {n: run.reader(n)(ctx) for n in READERS}}
    to_perf = time.perf_counter_ns() - time.monotonic_ns()
    stages = pt.ttft_stages({rid: reqs[rid] for rid in r["measured"]},
                            records, to_perf)
    if stages:
        out["ttft_stages_ms"] = {
            k: statistics.median(s[k] for s in stages.values())
            for k in (*pt.STAGES, "ttft", "lease_ack")}
        out["ttft_tiled"] = len(stages)
        out["lease_ack_before_enqueue"] = sum(
            1 for s in stages.values() if s["lease_ack"] < 0)
    if spans.idle is not None:
        out["idle_by_span_s"] = spans.idle["by_replica"]
        out["idle_covered_share"] = pt.covered_share(spans.idle)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax

    bench = run.load_json(run.ROOT / "BENCHMARK.json")
    cell = run.find_cell(bench, args.workload)
    c = run.load_config(cell["config"])
    mix = traffic.load_mix(cell["traffic"])
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print("spans_run: needs the cell's TPU chips", file=sys.stderr)
        return 2
    with instrument() as box:
        result = run.run_cell(bench, cell, c, mix, seed=args.seed,
                              seconds=args.seconds, trace=bool(args.trace),
                              devices=devices[: cell["chips"]],
                              peak=run.peak_of(devices[0].device_kind))
    err = lambda *a: print(*a, file=sys.stderr, flush=True)  # noqa: E731
    print(json.dumps({"cell": cell["name"], "seed": args.seed,
                      "trace": bool(args.trace), "result": result,
                      "program": program_view(box, log=err)},
                     default=float), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
