"""The reduction from a profiler trace and the benchmark's host spans to
busy time, per-call device time and idle gaps."""

import gzip
import json
from pathlib import Path

import pytest

from bench import trace as tr

FIXTURE = Path(__file__).parent / "fixtures" / "trace_internlm2_batch.json.gz"
DEV = "/device:TPU:0"


def spans(work):
    s = tr.Spans()
    s.work.update(work)
    return s


def test_synthetic_trace_worked_by_hand():
    # two calls on one device: an admission (host 0-10) whose executable
    # the device clock puts 0.5 before its span, and a block (host 12-36)
    ev = [
        ("/host:CPU", "python3", "bench.admit.1.1", 0.0, 10.0),
        ("/host:CPU", "python3", "bench.block.1.2", 12.0, 24.0),
        (DEV, "XLA Modules", "jit_admit_fused(123)", -0.5, 8.0),
        (DEV, "XLA Modules", "jit_multi(456)", 13.0, 15.0),
        (DEV, "XLA Ops", "fusion.1", -0.5, 8.0),    # 0-7.5 in the window
        (DEV, "XLA Ops", "fusion.2", 13.0, 10.0),   # 13-23
        (DEV, "XLA Ops", "fusion.3", 20.0, 8.0),    # 20-28, overlaps .2
        (DEV, "XLA Modules", "jit_multiply(9)", 40.0, 1.0),  # another name
    ]
    red = tr.reduce(ev, (0.0, 40.0), {1: DEV},
                    spans({"bench.admit.1.1": (1.0, 2.0),
                           "bench.block.1.2": (3.0, 4.0)}))
    assert red["window_s"] == pytest.approx(40e-9)
    assert red["busy_s"] == pytest.approx((7.5 + 15.0) * 1e-9)
    assert sorted(red["calls"]) == [
        ("admit", 1.0, 2.0, pytest.approx(8e-9)),
        ("block", 3.0, 4.0, pytest.approx(15e-9))]
    # idle: 28-40 (host in the block call at its middle, 34), and 7.5-13
    # (host between the two calls at 10.25)
    assert red["idle_gaps"] == [["block call", pytest.approx(12e-9)],
                                ["outside admit and block calls",
                                 pytest.approx(5.5e-9)]]


def test_union_of_intervals():
    assert tr.union([(3, 4), (0, 2), (1, 3), (6, 7)]) == [(0, 4), (6, 7)]


@pytest.fixture(scope="module")
def recorded():
    return json.loads(gzip.decompress(FIXTURE.read_bytes()))


def test_recorded_trace_reduces(recorded):
    planes = {int(k): v for k, v in recorded["replica_plane"].items()}
    w0, w1 = recorded["window_ns"]
    red = tr.reduce([tuple(e) for e in recorded["events"]], (w0, w1), planes,
                    spans(recorded["work"]))
    ops = tr.union([(max(s, w0), min(s + d, w1)) for p, ln, _, s, d
                    in recorded["events"] if ln == tr.OPS_LINE
                    and s + d > w0 and s < w1])
    assert red["busy_s"] == pytest.approx(sum(b - a for a, b in ops) * 1e-9)
    assert 0 < red["busy_s"] < red["window_s"] == pytest.approx(0.6)
    kinds = [c[0] for c in red["calls"]]
    assert kinds.count("admit") >= 5 and kinds.count("block") >= 1
    # every matched call's device time lies inside its executable's events
    modules = sum(d for p, ln, n, s, d in recorded["events"]
                  if ln == tr.MODULE_LINE) * 1e-9
    assert 0 < sum(c[3] for c in red["calls"]) <= modules
    for kind, flops, nbytes, dev in red["calls"]:
        assert dev > 0 and flops > 0 and nbytes > 0
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    _, need, dev = tr.call_work(red, peak, "admit")
    assert 0 < need < dev  # a roofline share under 100 %
    assert len(red["idle_gaps"]) == 10
