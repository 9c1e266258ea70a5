"""The readers of the program's own spans and lane counters, the labelling
of idle device time by program span, and the tiling of the time to first
token, on synthetic input and on the CPU at a reduced size."""

import types

import pytest

from bench import program_trace as pt
from bench import run as harness
from bench import spans_run
from bench.tests import tiny
from bench.tests.tiny import no_cache  # noqa: F401  (fixture)

DEV = "/device:TPU:0"


def read(name, ctx):
    return harness.reader(name)(ctx)


def spans_ctx(records, rids, idle=None, snap=None):
    return types.SimpleNamespace(
        snap=snap or {}, spans=pt.ProgramSpans(records=records, dropped=0,
                                               rids=frozenset(rids),
                                               idle=idle))


def test_request_span_readers_take_the_window_requests():
    ms = 1_000_000
    recs = [("ham.req.queued", 1, rid, 0, (rid + 1) * ms)
            for rid in range(20)]
    recs += [("ham.req.held", 1, rid, 0, 2 * rid * ms) for rid in range(20)]
    recs += [("ham.loop.admit", 1, 5, 0, 99 * ms)]
    ctx = spans_ctx(recs, rids=range(1, 20))  # rid 0 was due before it
    # queued: 2..20 ms over 19 requests, nearest-rank p95 is the 19th
    assert read("loop_queue_p95_ms", ctx) == pytest.approx(20.0)
    # held: 2, 4, .., 38 ms; the p50 is the 10th, 20 ms
    assert read("first_token_held_p50_ms", ctx) == pytest.approx(20.0)


def test_counter_readers_worked_by_hand():
    snap = {"t0": {"lanes_stepped": 64, "lanes_past_budget": 27,
                   "routed": {1: 10, 2: 12, 3: 9, 4: 11}},
            "t1": {"lanes_stepped": 576, "lanes_past_budget": 155,
                   "routed": {1: 40, 2: 42, 3: 49, 4: 41}}}
    ctx = types.SimpleNamespace(snap=snap)
    assert read("lanes_past_budget_share", ctx) == pytest.approx(128 / 512)
    # routed in the window: 30, 30, 40, 30; mean 32.5
    assert read("route_imbalance", ctx) == pytest.approx(40 / 32.5)


def test_readers_find_nothing_on_a_harness_without_spans():
    """The harness as it stands gives no spans and no lane counters: the
    readers say so, and do not raise."""
    snap = {"t0": {"steps": 1, "routed": {}}, "t1": {"steps": 2,
                                                    "routed": {}}}
    ctx = types.SimpleNamespace(snap=snap, trace=None)
    for name in spans_run.READERS:
        assert read(name, ctx) is None, name


def test_idle_by_span_worked_by_hand():
    # replica 1's loop on the host clock, which lies 100 ns behind the
    # trace's: park 0-5, then one iteration 5-40 (admission 6-15: dispatch
    # 6-8, wait 8-14; block 15-38: dispatch 15-16, wait 16-36, emit 36-38;
    # flush 38-40); the window is 0-50 on the trace's clock
    h = -100
    recs = [("ham.loop.park", 1, -1, 0 + h, 5 + h),
            ("ham.loop.iter", 1, -1, 5 + h, 40 + h),
            ("ham.loop.admit", 1, 7, 6 + h, 15 + h),
            ("ham.admit.dispatch", 1, 7, 6 + h, 8 + h),
            ("ham.admit.wait", 1, 7, 8 + h, 14 + h),
            ("ham.loop.block", 1, -1, 15 + h, 38 + h),
            ("ham.block.dispatch", 1, -1, 15 + h, 16 + h),
            ("ham.block.wait", 1, -1, 16 + h, 36 + h),
            ("ham.block.emit", 1, -1, 36 + h, 38 + h),
            ("ham.loop.flush", 1, -1, 38 + h, 40 + h),
            # a request's wait crosses the others; it labels nothing
            ("ham.req.queued", 1, 7, -10 + h, 6 + h),
            # another replica's spans label nothing here
            ("ham.loop.iter", 2, -1, 40 + h, 50 + h)]
    ev = [(DEV, "XLA Ops", "fusion.1", 8.5, 5.0),     # busy 8.5-13.5
          (DEV, "XLA Ops", "fusion.2", 16.5, 10.0),   # busy 16.5-35
          (DEV, "XLA Ops", "fusion.3", 20.0, 15.0),
          ("/device:TPU:1", "XLA Ops", "fusion.9", 0.0, 50.0)]
    idle = pt.idle_by_span(ev, (0.0, 50.0), {1: DEV}, recs, 100.0)
    by = {k: v * 1e9 for k, v in idle["by_replica"][1].items()}
    assert by == {
        "ham.loop.park": pytest.approx(5.0),
        "ham.loop.iter": pytest.approx(1.0),
        "ham.admit.dispatch": pytest.approx(2.0),
        "ham.admit.wait": pytest.approx(1.0),     # 8-8.5 and 13.5-14
        "ham.loop.admit": pytest.approx(1.0),
        "ham.block.dispatch": pytest.approx(1.0),
        "ham.block.wait": pytest.approx(1.5),     # 16-16.5 and 35-36
        "ham.block.emit": pytest.approx(2.0),
        "ham.loop.flush": pytest.approx(2.0),
        pt.NO_SPAN: pytest.approx(10.0)}
    # host work with the device idle: 1 + 2 + 1 + 1 + 2 + 2 of 50
    assert pt.host_share(idle) == pytest.approx(9 / 50)
    assert pt.covered_share(idle) == pytest.approx(1 - 10 / 26.5)
    ctx = spans_ctx(recs, rids=[7], idle=idle)
    assert read("idle_host_share.lat", ctx) == pytest.approx(9 / 50)
    assert read("idle_host_share.tput", ctx) == pytest.approx(9 / 50)


def test_ttft_stages_tile_by_hand():
    ms = 1_000_000
    recs = [("ham.req.queued", 1, 3, 2 * ms, 10 * ms),
            ("ham.loop.admit", 1, 3, 10 * ms, 25 * ms),
            ("ham.req.held", 1, 3, 25 * ms, 400 * ms),
            ("ham.req.queued", 1, 4, 2 * ms, 10 * ms)]  # no admission yet
    reqs = {3: {"due": 0.0, "t_admit": 0.0025, "t_first": 0.401},
            4: {"due": 0.0, "t_admit": 0.0025, "t_first": None}}
    out = pt.ttft_stages(reqs, recs, to_perf_ns=0)
    assert list(out) == [3]
    assert {k: round(v, 6) for k, v in out[3].items()} == {
        "host_admission": 2.0, "loop_queue": 8.0, "admission": 15.0,
        "held": 375.0, "to_host": 1.0, "ttft": 401.0, "lease_ack": 0.5}
    # a host ack stamped before the worker enqueued reads negative
    reqs[3]["t_admit"] = 0.001
    assert pt.ttft_stages(reqs, recs, 0)[3]["lease_ack"] == pytest.approx(-1)


def test_innermost_names_each_segment():
    spans = [(0, 10, "a"), (2, 4, "b"), (4, 6, "c"), (5, 6, "d")]
    assert pt.innermost(spans) == [(0, 2, "a"), (2, 4, "b"), (4, 5, "c"),
                                   (5, 6, "d"), (6, 10, "a")]


def test_instrument_puts_the_harness_back():
    before = (harness.warm, harness.counters, harness.drive,
              harness.reduce_trace, harness.tr.load)
    with spans_run.instrument():
        assert harness.warm is not before[0]
    assert (harness.warm, harness.counters, harness.drive,
            harness.reduce_trace, harness.tr.load) == before


def test_spans_tile_the_time_to_first_token_on_the_cpu(no_cache):  # noqa: F811
    with spans_run.instrument() as box:
        res = tiny.run("qwen1.5-4b.chat.r80", tiny.OPEN)
    assert res["correct"], res["checks"]
    out = spans_run.program_view(box, log=lambda *a: None)
    assert out["dropped"] == 0 and out["records"] > 0
    assert out["ttft_tiled"] == len(box["run"]["measured"]) \
        == res["attempted"]
    st = out["ttft_stages_ms"]
    assert all(st[k] >= 0 for k in pt.STAGES)
    # the host's own lease-ack stamp follows the worker's enqueue
    assert out["lease_ack_before_enqueue"] == 0 and st["lease_ack"] >= 0
    assert st["host_admission"] + st["lease_ack"] < st["ttft"]
    m = out["metrics"]
    assert m["loop_queue_p95_ms"] >= 0 and m["first_token_held_p50_ms"] > 0
    assert 0 <= m["lanes_past_budget_share"] < 1
    assert m["idle_host_share.lat"] is None  # no trace in this run


def test_traced_run_labels_idle_time_on_the_cpu(no_cache):  # noqa: F811
    """The CPU has no device plane to trace: its whole window counts as
    idle, and nearly all of it lies in the decode loop's spans."""
    bench = tiny.benchmark()
    bench["workloads"].append({"name": "tiny.batch", "config": "tiny",
                               "traffic": "tiny.closed", "chips": 1,
                               "why": "a closed loop"})
    with spans_run.instrument() as box:
        res = tiny.run("tiny.batch", tiny.CLOSED, trace=True, bench=bench)
    assert res["correct"], res["checks"]
    out = spans_run.program_view(box, log=lambda *a: None)
    assert set(out["idle_by_span_s"]) == {1}
    assert out["idle_covered_share"] > 0.8
    assert 0 < out["metrics"]["idle_host_share.tput"] < 1
    assert pt.busy_intervals(box["events"], box["plane_of"][1],
                             box["window"]) == []
