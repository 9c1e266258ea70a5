"""Worker-driven streaming serve: protocol-level tests (docs/serving.md).

Covers the delivery/ordering contract of the ``_serve/stream*`` path, the
fused multi-step decode block, mode equivalence (worker-driven transcripts
token-identical to the lockstep drive), elasticity under join/leave, and
the failure-model legs: kill-mid-decode replay, cancel, and deadlines.
"""

import threading
import time

import numpy as np
import pytest

from repro.configs import get_reduced
from repro.core.flags import STREAM_CANCELLED, STREAM_DONE, STREAM_EXPIRED
from repro.models.api import build_model
from repro.serve.engine import ClusterServingEngine, Request, ServingEngine
from repro.serve.handlers import _NODE_ENGINES, _NODE_LOOPS


@pytest.fixture(scope="module")
def model_and_params():
    import jax

    cfg = get_reduced("llama3-405b")
    model = build_model(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def _prompts(cfg, n, base=3):
    return [np.arange(base + i % 3) % cfg.vocab_size for i in range(n)]


def _reqs(cfg, n, max_new=8, base=3):
    return [Request(prompt=p, max_new_tokens=max_new, rid=i)
            for i, p in enumerate(_prompts(cfg, n, base))]


# -- engine: fused multi-step block ----------------------------------------


def test_step_many_matches_sequential_steps(model_and_params):
    """A fused block (lax.scan over the handler table) emits exactly the
    tokens k sequential steps would — including a slot whose budget ends
    mid-block (its surplus lane tokens are dropped, not recorded)."""
    model, params = model_and_params
    cfg = model.cfg

    def serve(block):
        eng = ServingEngine(model, params, num_slots=2, max_len=32)
        eng.admit(Request(prompt=np.arange(4) % cfg.vocab_size,
                          max_new_tokens=5, rid=0), 0)
        eng.admit(Request(prompt=np.arange(6) % cfg.vocab_size,
                          max_new_tokens=11, rid=1), 1)
        while any(r is not None for r in eng.slot_req):
            if block > 1:
                eng.step_many(block)
            else:
                eng.step()
        return eng.outputs

    ref = serve(1)
    out = serve(4)
    assert out == ref
    assert {r: len(v) for r, v in out.items()} == {0: 5, 1: 11}


def test_step_early_out_when_all_slots_idle(model_and_params):
    """An empty batch never dispatches — neither via step() nor a fused
    block — but an explicit noop key still does (bubble-filler path)."""
    model, params = model_and_params
    eng = ServingEngine(model, params, num_slots=2, max_len=16)
    assert eng.step() == []
    assert eng.step_many(4) == []
    assert eng.steps_dispatched == 0
    eng.step(key=eng.key_noop)
    assert eng.steps_dispatched == 1


# -- cluster: mode equivalence + stream ordering ---------------------------


@pytest.mark.slow
def test_worker_driven_token_identical_to_lockstep(model_and_params):
    """Same prompts, same seed: the worker-driven drive must produce the
    exact transcripts of the lockstep drive (greedy decode is deterministic
    and slot lanes are independent, so any divergence is a protocol bug)."""
    model, params = model_and_params
    cfg = model.cfg
    outs = {}
    for wd in (False, True):
        eng = ClusterServingEngine(model, params, num_workers=2,
                                   slots_per_worker=2, max_len=32,
                                   worker_driven=wd)
        try:
            outs[wd] = eng.run(_reqs(cfg, 6, max_new=9), timeout=120)
            if wd:
                # one admit RPC per request: the host never drove a step
                assert eng.sched.stats["submitted"] == 6
                # fused-oneway ordering held for every session
                assert all(ev.get("seq_ok", True)
                           for ev in eng._events.values())
        finally:
            eng.close()
    assert outs[True] == outs[False]
    assert {r: len(v) for r, v in outs[True].items()} == {
        i: 9 for i in range(6)
    }


@pytest.mark.slow
def test_join_leave_mid_batch_token_identical(model_and_params):
    """Elastic membership mid-batch: requests served across a join and a
    drained leave still match the lockstep transcripts token for token."""
    model, params = model_and_params
    cfg = model.cfg
    eng = ClusterServingEngine(model, params, num_workers=1,
                               slots_per_worker=2, max_len=32)
    try:
        rids = [eng.submit_request(r, shed=False)
                for r in _reqs(cfg, 6, max_new=8)]
        new = eng.pool.add_node()  # join while the batch is decoding
        eng.wait(rids, timeout=120.0)
        eng.pool.remove_node(new, drain=True)  # leave between batches
        late = [eng.submit_request(  # rid=-1: fresh ids, no transcript reuse
            Request(prompt=p, max_new_tokens=8), shed=False)
            for p in _prompts(cfg, 2)]
        eng.wait(late, timeout=120.0)
        with eng._wd:
            got = {r: list(eng._transcripts[r]) for r in rids}
            got_late = {i: list(eng._transcripts[r])
                        for i, r in enumerate(late)}
    finally:
        eng.close()
    ref = ServingEngine(model, params, num_slots=2, max_len=32).run(
        _reqs(cfg, 6, max_new=8))
    assert got == ref
    ref_late = ServingEngine(model, params, num_slots=2, max_len=32).run(
        _reqs(cfg, 2, max_new=8))
    assert got_late == ref_late


@pytest.mark.slow
def test_kill_mid_decode_replays_without_dup_or_loss(model_and_params):
    """Kill a worker while its loop is streaming: every request replays on
    the survivor and the final transcripts are exactly the reference — no
    duplicated, lost, or reordered tokens (seq_ok holds through the repin
    because the continuation admit offsets the stream's seq base)."""
    model, params = model_and_params
    cfg = model.cfg
    eng = ClusterServingEngine(model, params, num_workers=2,
                               slots_per_worker=2, max_len=64)
    killed = {}

    def killer():
        deadline = time.time() + 60
        while time.time() < deadline:
            with eng._wd:
                streamed = sum(len(t) for t in eng._transcripts.values())
                decoding = sorted(set(eng._placed.values()))
            if streamed >= 12 and decoding:  # loops are live and mid-decode
                victim = decoding[0]  # a worker holding live requests
                eng.pool.kill(victim)
                killed["node"] = victim
                return
            time.sleep(0.002)

    t = threading.Thread(target=killer)
    try:
        rids = [eng.submit_request(r, shed=False)
                for r in _reqs(cfg, 6, max_new=24)]
        t.start()
        eng.wait(rids, timeout=180.0)
        t.join()
        with eng._wd:
            got = {r: list(eng._transcripts[r]) for r in rids}
            events = {r: dict(eng._events[r]) for r in rids}
    finally:
        t.join(timeout=1.0)
        eng.close()
    assert "node" in killed, "the kill must land mid-run"
    ref = ServingEngine(model, params, num_slots=2, max_len=64).run(
        _reqs(cfg, 6, max_new=24))
    assert got == ref  # exact: no duplicated and no lost tokens
    assert any(ev.get("repins", 0) > 0 for ev in events.values())
    assert all(ev.get("seq_ok", True) for ev in events.values())


# -- failure model: cancel + deadline --------------------------------------


@pytest.mark.slow
def test_cancel_mid_decode_frees_slot(model_and_params):
    """Cancel a streaming request: the host keeps the partial transcript,
    the end-of-stream ack records STREAM_CANCELLED, and the freed slot
    serves a follow-up request to completion."""
    model, params = model_and_params
    cfg = model.cfg
    eng = ClusterServingEngine(model, params, num_workers=1,
                               slots_per_worker=1, max_len=450)
    try:
        rid = eng.submit_request(
            Request(prompt=np.arange(5) % cfg.vocab_size,
                    max_new_tokens=400), shed=False)
        deadline = time.time() + 60
        while time.time() < deadline:
            with eng._wd:
                if len(eng._transcripts.get(rid, ())) >= 4:
                    break
            time.sleep(0.002)
        assert eng.cancel(rid)
        eng.wait([rid], timeout=60.0)
        with eng._wd:
            assert eng._done[rid] == STREAM_CANCELLED
            assert 0 < len(eng._transcripts[rid]) < 400
        follow = eng.submit_request(
            Request(prompt=np.arange(4) % cfg.vocab_size,
                    max_new_tokens=3), shed=False)
        eng.wait([follow], timeout=60.0)
        with eng._wd:
            assert eng._done[follow] == STREAM_DONE
            assert len(eng._transcripts[follow]) == 3
    finally:
        eng.close()


@pytest.mark.slow
def test_deadline_expires_mid_decode(model_and_params):
    """A request whose decode budget outlives its deadline leaves the batch
    at a block boundary with STREAM_EXPIRED and a partial transcript
    (docs/failure-model.md: abandoned requests)."""
    model, params = model_and_params
    cfg = model.cfg
    eng = ClusterServingEngine(model, params, num_workers=1,
                               slots_per_worker=1, max_len=450)
    try:
        rid = eng.submit_request(
            Request(prompt=np.arange(5) % cfg.vocab_size,
                    max_new_tokens=400, deadline=0.15), shed=False)
        eng.wait([rid], timeout=120.0)
        with eng._wd:
            assert eng._done[rid] == STREAM_EXPIRED
            assert 0 < len(eng._transcripts[rid]) < 400
    finally:
        eng.close()


def test_decode_loop_failure_reaches_wait(model_and_params, monkeypatch):
    """An error inside a worker's decode loop (a compile, device or
    out-of-memory error on the chip) fails that loop's live and queued
    requests at once: ``wait`` raises it within seconds instead of timing
    out."""
    from repro.core.errors import OffloadError
    from repro.core.flags import STREAM_FAILED

    model, params = model_and_params
    cfg = model.cfg

    def boom(self, k):
        raise RuntimeError("boom: device step failed")

    monkeypatch.setattr(ServingEngine, "step_many", boom)
    eng = ClusterServingEngine(model, params, num_workers=1,
                               slots_per_worker=2, max_len=32)
    try:
        # 2 requests fill the slots and fail live; 2 more are queued
        rids = [eng.submit_request(r, shed=False) for r in _reqs(cfg, 4)]
        t0 = time.monotonic()
        with pytest.raises(OffloadError, match="boom") as info:
            eng.wait(rids, timeout=60.0)
        assert time.monotonic() - t0 < 10.0
        assert isinstance(info.value.__cause__, RuntimeError)
        with eng._wd:
            assert set(rids) <= eng._done.keys()
            assert eng._done[rids[0]] == STREAM_FAILED
    finally:
        eng.close()


# -- tracing: program spans and lane counters ------------------------------


def _serve_some(model, params, *, spans: bool, n=6, max_new=8):
    """Six requests through a worker-driven cluster of two replicas;
    returns (span log or None, rid -> host events)."""
    cfg = model.cfg
    eng = ClusterServingEngine(model, params, num_workers=2,
                               slots_per_worker=2, max_len=32,
                               decode_block=4)
    try:
        log = eng.enable_spans() if spans else None
        rids = [eng.submit_request(r, shed=False)
                for r in _reqs(cfg, n, max_new=max_new)]
        eng.wait(rids, timeout=120.0)
        with eng._wd:
            events = {r: dict(eng._events[r]) for r in rids}
            assert all(len(eng._transcripts[r]) == max_new for r in rids)
        holders = [h for n in eng._engine_keys.values()
                   for h in (_NODE_ENGINES[n], _NODE_LOOPS[n])]
        assert all(h.spans is log for h in holders)
    finally:
        eng.close()
    return log, events


def test_spans_off_record_nothing_and_open_no_annotation(model_and_params,
                                                          monkeypatch):
    """Spans are off by default: a served run holds no log, and the
    profiler's annotation is never entered."""
    import jax

    def refuse(*a, **k):
        raise AssertionError("TraceAnnotation entered with spans off")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    model, params = model_and_params
    log, events = _serve_some(model, params, spans=False)
    assert log is None and len(events) == 6


def test_spans_order_each_request_and_nest(model_and_params):
    """With spans on, each request has one ``ham.req.queued`` and one
    ``ham.req.held``, and enqueue <= admission start <= admission end <=
    flush <= the host's receipt of the first token; every child span lies
    inside its parent on the same replica."""
    import time

    model, params = model_and_params
    log, events = _serve_some(model, params, spans=True)
    recs = log.records()
    assert log.dropped == 0 and recs
    assert all(name.startswith("ham.") for name, *_ in recs)
    to_perf = time.perf_counter_ns() - time.monotonic_ns()
    for rid, ev in events.items():
        mine = {}
        for name, rep, r, a, b in recs:
            if r == rid and name in ("ham.req.queued", "ham.loop.admit",
                                     "ham.req.held"):
                mine.setdefault(name, []).append((rep, a, b))
        assert {k: len(v) for k, v in mine.items()} == {
            "ham.req.queued": 1, "ham.loop.admit": 1, "ham.req.held": 1}
        (_, enq, q_end), = mine["ham.req.queued"]
        (_, a0, a1), = mine["ham.loop.admit"]
        (_, h0, flush), = mine["ham.req.held"]
        first = ev["t_first"] * 1e9 + to_perf
        assert enq <= q_end == a0 <= a1 == h0 <= flush <= first + 1e3
    parents = {"ham.admit.dispatch": "ham.loop.admit",
               "ham.admit.wait": "ham.loop.admit",
               "ham.block.dispatch": "ham.loop.block",
               "ham.block.wait": "ham.loop.block",
               "ham.block.emit": "ham.loop.block",
               "ham.loop.admit": "ham.loop.iter",
               "ham.loop.block": "ham.loop.iter",
               "ham.loop.flush": "ham.loop.iter"}
    seen = set()
    for name, rep, rid, a, b in recs:
        assert a <= b
        if name not in parents:
            continue
        seen.add(name)
        assert any(n == parents[name] and rp == rep and pa <= a and b <= pb
                   and (r < 0 or r == rid)
                   for n, rp, r, pa, pb in recs), (name, rep, rid)
    assert seen == set(parents)
    assert any(name == "ham.loop.park" for name, *_ in recs)


def test_span_ring_counts_what_it_drops():
    from repro.serve.spans import SpanLog

    log = SpanLog(capacity=4)
    for i in range(10):
        log.record("ham.x", 1, i, i, i + 1)
    assert log.dropped == 6
    assert [r[2] for r in log.records()] == [6, 7, 8, 9]
    with pytest.raises(ValueError):
        SpanLog(capacity=0)


def test_lane_counters_split_the_block_by_hand(model_and_params):
    """2 slots, budgets 3 and 20, blocks of 16.  Admission emits each
    request's first token.  Block 1: slot 0 emits 2 and is past its budget
    for 14 lanes, slot 1 emits 16.  Block 2: slot 0 is empty (16 lanes),
    slot 1 emits its last 3 and is past its budget for 13."""
    model, params = model_and_params
    cfg = model.cfg
    eng = ServingEngine(model, params, num_slots=2, max_len=64)
    eng.admit(Request(prompt=np.arange(4) % cfg.vocab_size,
                      max_new_tokens=3, rid=0), 0)
    eng.admit(Request(prompt=np.arange(5) % cfg.vocab_size,
                      max_new_tokens=20, rid=1), 1)
    emitted = len(eng.step_many(16))
    assert (eng.lanes_stepped, emitted, eng.lanes_past_budget) == (32, 18, 14)
    emitted += len(eng.step_many(16))
    assert (eng.lanes_stepped, emitted, eng.lanes_past_budget) == (64, 21, 27)
    empty = eng.lanes_stepped - emitted - eng.lanes_past_budget
    assert empty == 16
    assert {r: len(v) for r, v in eng.outputs.items()} == {0: 3, 1: 20}
    assert eng.step_many(16) == [] and eng.lanes_stepped == 64


def test_executable_names_match_the_trace_reduction(model_and_params):
    """The benchmark's trace reduction finds fused admission and the fused
    block by their module names (bench/trace.py ``EXECUTABLES``); a rename
    fails here.  The named scopes reach the lowered program's metadata."""
    import re

    from bench import trace as tr
    from repro.serve.engine import ServeProgram, _spec

    model, params = model_and_params
    prog = ServeProgram(model, _spec(params), num_slots=2, max_len=32)
    pl = prog.init_payload(0)
    lowered = {
        "admit": prog.admit.lower(params, pl["cache"], pl["tokens"],
                                  pl["pos"], np.zeros((1, 8), np.int32),
                                  np.int32(0)),
        "block": prog.multi(16).lower(pl, params),
    }
    scopes = {"admit": ("prefill", "cache_insert", "first_token"),
              "block": ("decode_step",)}
    assert set(lowered) == set(tr.EXECUTABLES)
    for kind, low in lowered.items():
        module = re.search(r"module @(\S+)", low.as_text()).group(1)
        assert tr._executable(f"{module}(7)") == kind, module
        text = low.as_text(debug_info=True)
        for scope in scopes[kind]:
            assert f'"{scope}' in text or f"/{scope}" in text, scope
