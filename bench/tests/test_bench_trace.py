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


# -- device time and work by scope ---------------------------------------------


def test_scopes_worked_by_hand():
    # an admission (host 0-10, executable 1-9) and a block (host 12-36,
    # executable 13-28) on one device; a third executable (40-45) matches
    # no call.  Op names as the profiler gives them (``tf_op``).
    ev = [
        ("/host:CPU", "python3", "bench.admit.1.1", 0.0, 10.0),
        ("/host:CPU", "python3", "bench.block.1.2", 12.0, 24.0),
        (DEV, "XLA Modules", "jit_admit_fused(1)", 1.0, 8.0),
        (DEV, "XLA Modules", "jit_multi(2)", 13.0, 15.0),
        (DEV, "XLA Modules", "jit_other(3)", 40.0, 5.0),
        (DEV, "XLA Ops", "fusion.1", 1.0, 4.0),        # prefill
        (DEV, "XLA Ops", "fusion.2", 5.0, 1.0),        # prefill/attn
        (DEV, "XLA Ops", "copy.3", 6.0, 1.0),          # no op name
        (DEV, "XLA Ops", "fusion.4", 7.0, 2.0),        # cache_insert
        (DEV, "XLA Ops", "%while.5 = body", 13.0, 14.0),  # a container
        (DEV, "XLA Ops", "fusion.6", 13.0, 6.0),       # decode_step
        (DEV, "XLA Ops", "fusion.7", 19.0, 8.0),       # decode_step/moe
        (DEV, "XLA Ops", "fusion.6", 41.0, 3.0),       # in no matched call
    ]
    scopes = {
        (DEV, "fusion.1"): "jit(admit_fused)/prefill/while/body/"
                           "closed_call/checkpoint/bsd,dhk->bshk/dot_general:",
        (DEV, "fusion.2"): "jit(admit_fused)/prefill/attn/jit(_where)/"
                           "select_n:",
        (DEV, "fusion.4"): "jit(admit_fused)/cache_insert/"
                           "dynamic_update_slice:",
        (DEV, "%while.5 = body"): "jit(multi)/while:",
        (DEV, "fusion.6"): "jit(multi)/while/body/closed_call/decode_step/"
                           "dot_general:",
        (DEV, "fusion.7"): "jit(multi)/while/body/closed_call/decode_step/"
                           "moe/dot_general:",
    }
    s = spans({"bench.admit.1.1": (1.0, 2.0), "bench.block.1.2": (3.0, 4.0)})
    s.parts.update({"bench.admit.1.1": {"prefill": (1.0, 1.5)},
                    "bench.block.1.2": {"decode_step": (3.0, 3.5),
                                        "decode_step/moe": (2.0, 0.5)},
                    "bench.block.1.9": {"decode_step": (99.0, 99.0)}})
    red = tr.reduce(ev, (0.0, 50.0), {1: DEV}, s, scopes)
    assert red["op_time"] == pytest.approx({
        "fusion.1": 4e-9, "fusion.2": 1e-9, "copy.3": 1e-9, "fusion.4": 2e-9,
        "fusion.6": 9e-9, "fusion.7": 8e-9})
    assert list(red["op_time"]) == [n for n, _ in red["device_ops"]]
    assert red["scope_time"] == pytest.approx({
        "prefill": 5e-9, "prefill/attn": 1e-9, "cache_insert": 2e-9,
        "decode_step": 14e-9, "decode_step/moe": 8e-9})
    assert red["scope_work"] == {"prefill": (1.0, 1.5),
                                 "decode_step": (3.0, 3.5),
                                 "decode_step/moe": (2.0, 0.5)}
    # without parts there is no scope_work, without op names no scope_time
    bare = tr.reduce(ev, (0.0, 50.0), {1: DEV}, spans(s.work))
    assert "scope_work" not in bare and bare["scope_time"] == {}


@pytest.mark.parametrize("op_name,path", [
    ("jit(multi)/while/body/closed_call/decode_step/dot_general:",
     "decode_step"),
    ("jit(admit_fused)/prefill/while/body/closed_call/checkpoint/"
     "bthgk,bshk->bhgts/dot_general:", "prefill"),
    ("jit(multi)/while/body/closed_call/decode_step/jit(_threefry_split)/"
     "sampler/while/body/closed_call/add:", "decode_step/sampler"),
    ("jit(f)/broadcast_in_dim:", ""),
    ("jit(f)/expert layer/moe/cond/branch_1/mul", "expert layer/moe"),
])
def test_scope_path_keeps_only_named_scopes(op_name, path):
    assert tr.scope_path(op_name) == path


def _varint(n):
    out = b""
    while True:
        b, n = n & 0x7F, n >> 7
        out += bytes([b | (0x80 if n else 0)])
        if not n:
            return out


def _msg(*fields):
    """A protobuf message of ``(field, int | bytes | str)``."""
    out = b""
    for f, v in fields:
        if isinstance(v, int):
            out += _varint(f << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(f << 3 | 2) + _varint(len(v)) + v
    return out


def test_op_names_read_from_the_xplane_wire_format():
    stat_meta = [(5, _msg((1, k), (2, _msg((1, k), (2, n)))))
                 for k, n in ((7, "hlo_category"), (9, "tf_op"),
                              (11, "jit(g)/refd/add:"))]
    ev_meta = [
        (4, _msg((1, 1), (2, _msg((1, 1), (2, "fusion.1"),
                                  (5, _msg((1, 7), (5, "convolution"))),
                                  (5, _msg((1, 9), (5, "jit(f)/a/dot:"))))))),
        (4, _msg((1, 2), (2, _msg((1, 2), (2, "copy.2"),
                                  (5, _msg((1, 7), (5, "copy"))))))),
        (4, _msg((1, 3), (2, _msg((1, 3), (2, "add.3"),
                                  (5, _msg((1, 9), (7, 11))))))),
    ]
    device = _msg((1, 1), (2, "/device:TPU:0"),
                  (3, _msg((2, "XLA Ops"), (3, 5))), *stat_meta, *ev_meta)
    host = _msg((1, 2), (2, "/host:CPU"), *stat_meta, *ev_meta)
    space = _msg((1, device), (1, host), (4, "a-host"))
    assert tr.op_names(space) == {
        ("/device:TPU:0", "fusion.1"): "jit(f)/a/dot:",
        ("/device:TPU:0", "add.3"): "jit(g)/refd/add:"}


def test_recorded_trace_top_ten_of_op_time_are_device_ops(recorded):
    planes = {int(k): v for k, v in recorded["replica_plane"].items()}
    red = tr.reduce([tuple(e) for e in recorded["events"]],
                    tuple(recorded["window_ns"]), planes,
                    spans(recorded["work"]))
    assert list(red["op_time"].items())[:10] == red["device_ops"]
    assert len(red["op_time"]) > 10
    assert sum(red["op_time"].values()) >= red["busy_s"] * 0.99
