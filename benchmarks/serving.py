"""Cluster serving under load: worker-driven continuous batching, measured.

Three legs over the REDUCED llama3-405b config (tiny layers — the point is
the *control plane*: at toy decode cost the per-token host RPC of the
lockstep drive is a first-order term, which is exactly the regime the
worker-driven path removes):

* ``throughput`` — the same prompt set served by the **lockstep** drive
  (host submits one ``_serve/step`` per worker per token step) and by the
  **worker-driven** drive (one ``_serve/admit_stream`` lease per request,
  tokens return as fused oneways).  Records aggregate tokens/s for each,
  the speedup, host RPCs per emitted token, and that the two transcripts
  are token-identical (greedy decode — same prompts, same tokens, by
  construction of the protocol, not by luck).
* ``poisson`` — an **open-loop** heavy-traffic harness: sticky sessions
  arrive as a Poisson process at a configured fraction of measured
  capacity (open-loop = arrivals do not wait for completions, so queueing
  is real), through a bounded admission queue that sheds with
  ``OffloadError`` on overflow.  Records TTFT and per-token latency
  p50/p99 against SLO targets.
* ``kill_recovery`` — kill one of four workers under live traffic.  The
  host transcript replays every victim request on a survivor (session
  repin + continuation admit); records sessions repinned, requests lost
  (acceptance: zero), completed fraction, and whether the SLO held
  through the failure.

Writes ``BENCH_serving.json`` (schema ``serving-v1``); the ``serving.*``
leaves are gated by ``benchmarks/trend_gate.py`` — speedup and kill
recovery as trends (recovery at zero tolerance), host RPCs per token
against an absolute ceiling of 0.1.
"""

from __future__ import annotations

import json
import time
from pathlib import Path

import numpy as np

from benchmarks._stats import percentiles

_REPO_ROOT = Path(__file__).resolve().parents[1]
_JSON_PATH = _REPO_ROOT / "BENCH_serving.json"

WORKERS = 4
SLOTS_PER_WORKER = 2
PROMPT_LEN = 8          # fixed: prefill jit-compiles per prompt length
MAX_NEW = 32            # decode budget per request (throughput leg)
POISSON_MAX_NEW = 16
#: kill-leg requests live for several fused decode blocks, so the victim
#: is guaranteed to hold live sessions when it dies (a 16-token request
#: fits in ONE block and would often finish before the kill lands)
KILL_MAX_NEW = 96
POISSON_LOAD = 0.6      # offered load as a fraction of measured capacity
ADMISSION_LIMIT = 64    # bounded admission queue (shed past this depth)

#: SLO targets the open-loop leg reports against.  Generous on purpose:
#: they must hold on a loaded single-core CI runner; the *trend* gate is
#: what catches creep, the SLO booleans catch collapse.
SLO_TTFT_P99_MS = 2500.0
SLO_PER_TOKEN_P99_MS = 250.0
#: the kill leg gets a looser TTFT bound — a request admitted just before
#: the kill pays death-detection + repin + replayed prefill
SLO_KILL_TTFT_P99_MS = 6000.0


def _build_model():
    import jax

    from repro.configs import get_reduced
    from repro.models.api import build_model

    cfg = get_reduced("llama3-405b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def _make_prompts(n: int, seed: int = 7) -> list[np.ndarray]:
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 100, size=PROMPT_LEN).astype(np.int32)
            for _ in range(n)]


def _make_engine(model, params, *, worker_driven: bool,
                 admission_limit: int | None = None, max_new: int = MAX_NEW):
    from repro.serve.engine import ClusterServingEngine

    return ClusterServingEngine(
        model, params, num_workers=WORKERS,
        slots_per_worker=SLOTS_PER_WORKER,
        max_len=PROMPT_LEN + max_new + 8,
        worker_driven=worker_driven, admission_limit=admission_limit,
    )


def _warm(eng) -> None:
    """Compile prefill + decode on EVERY replica before the measured
    region.  Session placement is a rendezvous hash, so driving warm
    requests through the front door cannot guarantee coverage — a replica
    that missed warmup would bill ~2s of jit to the first measured request
    landing on it.  The replicas are in-process (thread workers), so warm
    each engine directly: admit one short request and step it out through
    BOTH decode paths — single-step and the fused step_many block — so
    neither compiles inside the measured region (the decode loops are
    parked — nothing else touches the replica)."""
    from repro.serve.engine import Request
    from repro.serve.handlers import _NODE_ENGINES

    block = getattr(eng, "decode_block", 1)
    for key in list(eng._engine_keys.values()):
        rep = _NODE_ENGINES[key]
        rep.admit(Request(prompt=np.arange(1, 1 + PROMPT_LEN,
                                           dtype=np.int32),
                          max_new_tokens=block + 3, rid=999_983), 0)
        rep.step()
        if block > 1:
            rep.step_many(block)
        rep.evict(999_983)
        rep.outputs.pop(999_983, None)


def _throughput_section(model, params, smoke: bool) -> dict:
    from repro.serve.engine import Request

    # smoke shrinks the request count only: max_new stays at the full
    # budget so the host-RPCs-per-token ceiling is judged at the real
    # admit/token amortisation (and a fused block still fills)
    n_req = 8 if smoke else 32
    max_new = MAX_NEW
    prompts = _make_prompts(n_req)

    def reqs():
        return [Request(prompt=p, max_new_tokens=max_new, rid=i)
                for i, p in enumerate(prompts)]

    results = {}
    for mode, worker_driven in (("lockstep", False), ("worker_driven", True)):
        eng = _make_engine(model, params, worker_driven=worker_driven)
        try:
            _warm(eng)
            sub0 = eng.sched.stats["submitted"]
            one0 = eng.sched.stats["oneways"]
            t0 = time.perf_counter()
            out = eng.run(reqs(), timeout=300.0)
            dt = time.perf_counter() - t0
            tokens = sum(len(v) for v in out.values())
            rpcs = (eng.sched.stats["submitted"] - sub0
                    + eng.sched.stats["oneways"] - one0)
            results[mode] = {
                "out": out,
                "tokens": tokens,
                "tokens_per_s": round(tokens / dt, 1),
                "host_rpcs": rpcs,
                "host_rpcs_per_token": round(rpcs / max(tokens, 1), 4),
            }
        finally:
            eng.close()
    lock, wd = results["lockstep"], results["worker_driven"]
    identical = lock["out"] == wd["out"]
    section = {
        "requests": n_req,
        "max_new_tokens": max_new,
        "tokens": wd["tokens"],
        "lockstep_tokens_per_s": lock["tokens_per_s"],
        "worker_driven_tokens_per_s": wd["tokens_per_s"],
        "speedup_vs_lockstep": round(
            wd["tokens_per_s"] / max(lock["tokens_per_s"], 1e-9), 2),
        "lockstep_host_rpcs_per_token": lock["host_rpcs_per_token"],
        "host_rpcs_per_token": wd["host_rpcs_per_token"],
        "token_identical": identical,
    }
    return section


def _latency_stats(eng, rids) -> dict:
    """TTFT and per-token latency percentiles from the engine's per-request
    event stamps (ms)."""
    ttft, per_tok = [], []
    with eng._wd:
        for rid in rids:
            ev = eng._events.get(rid, {})
            if "t_first" in ev and "t_submit" in ev:
                ttft.append((ev["t_first"] - ev["t_submit"]) * 1e3)
            ts = ev.get("token_ts", ())
            if len(ts) >= 2:
                per_tok.append((ts[-1] - ts[0]) / (len(ts) - 1) * 1e3)
    out = {}
    if ttft:
        out["ttft_ms"] = {k: round(v, 1)
                          for k, v in percentiles(ttft, (50, 99)).items()}
    if per_tok:
        out["per_token_ms"] = {
            k: round(v, 2) for k, v in percentiles(per_tok, (50, 99)).items()
        }
    return out


def _poisson_section(model, params, capacity_tokens_per_s: float,
                     smoke: bool) -> dict:
    from repro.core.errors import OffloadError
    from repro.serve.engine import Request

    n_req = 48 if smoke else 1000
    max_new = POISSON_MAX_NEW
    cap_req_per_s = max(capacity_tokens_per_s / max_new, 1.0)
    offered = POISSON_LOAD * cap_req_per_s
    rng = np.random.default_rng(11)
    gaps = rng.exponential(1.0 / offered, size=n_req)
    prompts = _make_prompts(n_req, seed=13)

    eng = _make_engine(model, params, worker_driven=True,
                       admission_limit=ADMISSION_LIMIT)
    try:
        _warm(eng)
        submitted: list[int] = []
        shed = 0
        t0 = time.perf_counter()
        next_t = t0
        for i in range(n_req):
            next_t += gaps[i]
            delay = next_t - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            try:
                submitted.append(eng.submit_request(Request(
                    prompt=prompts[i], max_new_tokens=max_new, rid=i,
                )))
            except OffloadError:
                shed += 1  # bounded admission queue: overload is shed, not
                # queued without limit (open-loop back-pressure contract)
        eng.wait(submitted, timeout=600.0)
        dt = time.perf_counter() - t0
        with eng._wd:
            tokens = sum(len(eng._transcripts[r]) for r in submitted)
        stats = _latency_stats(eng, submitted)
        ttft_p99 = stats.get("ttft_ms", {}).get("p99", float("inf"))
        ptok_p99 = stats.get("per_token_ms", {}).get("p99", float("inf"))
        return {
            "arrivals": n_req,
            "offered_req_per_s": round(offered, 1),
            "offered_load_fraction": POISSON_LOAD,
            "admission_limit": ADMISSION_LIMIT,
            "max_new_tokens": max_new,
            "completed": len(submitted),
            "shed": shed,
            "tokens": tokens,
            "tokens_per_s": round(tokens / dt, 1),
            **stats,
            "slo": {
                "ttft_p99_ms_target": SLO_TTFT_P99_MS,
                "per_token_p99_ms_target": SLO_PER_TOKEN_P99_MS,
                "ttft_p99_met": ttft_p99 <= SLO_TTFT_P99_MS,
                "per_token_p99_met": ptok_p99 <= SLO_PER_TOKEN_P99_MS,
            },
        }
    finally:
        eng.close()


def _kill_section(model, params, smoke: bool) -> dict:
    from repro.serve.engine import Request

    n_req = 24 if smoke else 200
    max_new = KILL_MAX_NEW
    prompts = _make_prompts(n_req, seed=17)
    eng = _make_engine(model, params, worker_driven=True, max_new=max_new)
    try:
        _warm(eng)
        rids = [eng.submit_request(Request(
            prompt=prompts[i], max_new_tokens=max_new, rid=i), shed=False)
            for i in range(n_req)]
        # let traffic flow, then kill a worker that is actively serving
        target_tokens = n_req * max_new // 4
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            with eng._wd:
                if sum(len(t) for t in eng._transcripts.values()) \
                        >= target_tokens:
                    break
            time.sleep(0.005)
        victim = eng.serving_nodes()[0]
        t_kill = time.perf_counter()
        eng.pool.kill(victim)
        eng.wait(rids, timeout=600.0)
        recovery_s = time.perf_counter() - t_kill
        with eng._wd:
            lost = sum(1 for r in rids
                       if len(eng._transcripts.get(r, ())) != max_new)
            repinned = sum(1 for r in rids
                           if eng._events.get(r, {}).get("repins", 0) > 0)
            seq_violations = sum(
                1 for r in rids
                if eng._events.get(r, {}).get("seq_ok") is False)
        stats = _latency_stats(eng, rids)
        ttft_p99 = stats.get("ttft_ms", {}).get("p99", float("inf"))
        completed_fraction = (n_req - lost) / n_req
        slo_held = (lost == 0 and seq_violations == 0
                    and ttft_p99 <= SLO_KILL_TTFT_P99_MS)
        return {
            "requests": n_req,
            "max_new_tokens": max_new,
            "kill": f"worker {victim} of {WORKERS}, mid-decode",
            "recovery_s": round(recovery_s, 2),
            "sessions_repinned": repinned,
            "router_replaced": eng.sched.sessions.stats["replaced"],
            "lost_requests": lost,
            "seq_violations": seq_violations,
            "completed_fraction": round(completed_fraction, 3),
            **stats,
            "slo_kill_ttft_p99_ms_target": SLO_KILL_TTFT_P99_MS,
            "slo_held": slo_held,
        }
    finally:
        eng.close()


def run(smoke: bool = False) -> list[tuple[str, float, str]]:
    model, params = _build_model()
    throughput = _throughput_section(model, params, smoke)
    poisson = _poisson_section(
        model, params, throughput["worker_driven_tokens_per_s"], smoke)
    kill = _kill_section(model, params, smoke)
    report = {
        "schema": "serving-v1",
        "smoke": smoke,
        "model": "llama3-405b (REDUCED)",
        "workers": WORKERS,
        "slots_per_worker": SLOTS_PER_WORKER,
        "throughput": throughput,
        "poisson": poisson,
        "kill_recovery": kill,
        # flat gate-friendly section (trend_gate TRACKED/CEILINGS paths)
        "serving": {
            "tokens_per_s": throughput["worker_driven_tokens_per_s"],
            "speedup_vs_lockstep": throughput["speedup_vs_lockstep"],
            "host_rpcs_per_token": throughput["host_rpcs_per_token"],
            "kill_recovery": {
                "slo_held": kill["slo_held"],
                "completed_fraction": kill["completed_fraction"],
            },
        },
        "acceptance": {
            "worker_driven_ge_2x_lockstep_at_4_workers":
                throughput["speedup_vs_lockstep"] >= 2.0,
            "host_rpcs_per_token_lt_0_1":
                throughput["host_rpcs_per_token"] < 0.1,
            "token_identical_to_lockstep": throughput["token_identical"],
            "poisson_slo_met": poisson["slo"]["ttft_p99_met"]
                and poisson["slo"]["per_token_p99_met"],
            "kill_zero_lost_requests": kill["lost_requests"] == 0,
            "kill_slo_held": kill["slo_held"],
        },
    }
    _JSON_PATH.write_text(json.dumps(report, indent=2) + "\n")
    rows = [
        ("serving/worker_driven_tokens_per_s",
         throughput["worker_driven_tokens_per_s"],
         f"{throughput['speedup_vs_lockstep']}x vs lockstep, "
         f"{throughput['host_rpcs_per_token']} host RPCs/token"),
        ("serving/poisson_ttft_p99_ms",
         poisson.get("ttft_ms", {}).get("p99", -1.0),
         f"{poisson['arrivals']} arrivals at "
         f"{poisson['offered_req_per_s']} req/s, {poisson['shed']} shed"),
        ("serving/kill_recovery_s", kill["recovery_s"],
         f"{kill['sessions_repinned']} repinned, "
         f"{kill['lost_requests']} lost, SLO held: {kill['slo_held']}"),
        ("serving/speedup_vs_lockstep", throughput["speedup_vs_lockstep"],
         f"-> {_JSON_PATH.name}"),
    ]
    return rows


if __name__ == "__main__":
    import sys

    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    for name, val, note in run(smoke="--smoke" in sys.argv):
        print(f"{name},{val:.3f},{note}")
