#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; it names a
configuration (``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``), and takes as many chips as it says, one
serving replica per chip.  In order, in this one process:

1. the weights are drawn from ``--seed`` on the device in bf16, and
   ``ClusterServingEngine`` is built in worker-driven mode;
2. every shape the mix uses is warmed on every replica: fused admission at
   each prompt length of the mix, and the fused decode block;
3. the mix runs for its ``warmup_s`` to reach steady state;
4. the window: ``--seconds`` of traffic through ``submit_request``, every
   request timed on the host clock (an open loop from when it was due);
5. the served tokens of a sample of the window's requests are checked
   against the float32 reference (``bench/reference``), once the program's
   state is freed, and the last line is printed.

With ``--trace 1`` the profiler records a few seconds in the middle of the
window, and the cell's per-layer metrics are printed instead of its
end-to-end ones.  Each metric is a reader ``bench/metrics/<name>.py``.  The
last line of standard output is one JSON object; the numbers compared for
``correct`` end standard error and the line, each beside its limit.  With
no TPU, or fewer chips than the cell asks for, the run prints no result and
exits 2.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # noqa: E402  (set-up is timed from here)

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from bench import counts, traffic  # noqa: E402
from bench.stats import percentile  # noqa: E402
from bench import trace as tr  # noqa: E402

BENCH = ROOT / "bench"
#: seconds the profiler records, in the middle of the window
TRACE_S = 4.0
#: a request due in the window may finish this long after it closes
GRACE_S = 60.0
#: requests the reference checks: the longest served, and the rest drawn
#: from the seed among those the window finished
CHECK_ROWS = 16


def load_json(path: Path) -> dict:
    return json.loads(path.read_text())


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def metrics_of(bench: dict, section: str, cell: str) -> list[dict]:
    return [m for m in bench[section]
            if cell in m.get("workloads", [cell])]


def load_config(name: str, base: Path = BENCH) -> dict:
    return load_json(base / "configs" / f"{name}.json")


def reader(name: str, base: Path = BENCH):
    """``read(ctx)`` of ``bench/metrics/<name>.py``."""
    path = base / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"no reader for metric {name!r} ({path})")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peak_of(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def program_config(c: dict):
    """The program's ``ModelConfig`` for a dense decoder configuration,
    with every setting the file states."""
    from repro.models.config import ModelConfig

    if c["family"] != "dense":
        raise ValueError(f"no builder for family {c['family']!r}")
    return ModelConfig(
        name=Path(c.get("name", "bench")).name, family="dense",
        num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], qkv_bias=bool(c["qkv_bias"]),
        mlp="swiglu", rope_theta=float(c["rope_theta"]),
        norm_eps=float(c["rms_norm_eps"]),
        tie_embeddings=bool(c["tie_word_embeddings"]),
        dtype="bfloat16", param_dtype=c["serving"]["param_dtype"],
    )


# -- the program under test -------------------------------------------------


def replicas(eng) -> list[tuple[int, object, object]]:
    """(node, ServingEngine, WorkerDecodeLoop) of every replica."""
    from repro.serve.handlers import _NODE_ENGINES, _NODE_LOOPS

    return [(n, _NODE_ENGINES[k], _NODE_LOOPS.get(k))
            for n, k in sorted(eng._engine_keys.items())]


def counters(eng) -> dict:
    reps = replicas(eng)
    st = eng.sched.stats
    return {
        "submitted": st["submitted"], "oneways": st["oneways"],
        "routed": dict(st["routed"]),
        "frames": sum(lp.stats["frames"] for _, _, lp in reps),
        "loop_tokens": sum(lp.stats["tokens"] for _, _, lp in reps),
        "steps": sum(e.steps_dispatched for _, e, _ in reps),
    }


def build(c: dict, seed: int, devices):
    import jax

    from repro.models.api import build_model
    from repro.serve.engine import ClusterServingEngine

    from bench import weights

    shapes = counts.Shapes.from_config(c)
    model = build_model(program_config(c))
    key = jax.device_put(weights.root_key(seed), devices[0])
    params = weights.to_program(
        jax.block_until_ready(weights.make(key, shapes)))
    weights.check_tree(params, jax.eval_shape(model.init,
                                              jax.random.PRNGKey(0)))
    sv = c["serving"]
    eng = ClusterServingEngine(
        model, params, num_workers=len(devices),
        slots_per_worker=sv["slots_per_replica"], max_len=sv["max_len"],
        seed=seed & 0x7FFFFFFF, worker_driven=True,
        decode_block=sv["decode_block"], devices=list(devices))
    return eng


def warm(eng, lengths: list[int], block: int) -> None:
    """Compile (or load) fused admission at every prompt length and the
    fused decode block on every replica, while the decode loops are parked
    (nothing else touches a replica then)."""
    import jax

    from repro.serve.engine import Request

    for _, rep, _ in replicas(eng):
        for i, n in enumerate(lengths):
            rep.admit(Request(prompt=np.zeros(n, np.int32),
                              max_new_tokens=block + 1, rid=-1), 0)
            if i == 0:
                rep.step_many(block)
            rep.evict(-1)
            rep.outputs.pop(-1, None)
        jax.block_until_ready(rep.payload)


def install_spans(eng, shapes: counts.Shapes, spans: tr.Spans) -> None:
    """Wrap each replica's ``admit`` and ``step_many`` in a named host span
    that the profiler records, noting the work each call needed."""
    import jax

    for node, rep, _ in replicas(eng):
        admit, step_many = rep.admit, rep.step_many

        def admit_spanned(req, slot, _admit=admit, _node=node):
            name = spans.name("admit", _node)
            spans.host_start[name] = time.perf_counter_ns()
            with jax.profiler.TraceAnnotation(name):
                out = _admit(req, slot)
            spans.work[name] = shapes.prefill(len(req.prompt))
            return out

        def block_spanned(k, _step=step_many, _rep=rep, _node=node):
            before = {r.rid: len(r.prompt) + len(_rep.outputs[r.rid]) - 1
                      for r in _rep.slot_req if r is not None}
            name = spans.name("block", _node)
            spans.host_start[name] = time.perf_counter_ns()
            with jax.profiler.TraceAnnotation(name):
                emitted = _step(k)
            n = {}
            for rid, _ in emitted:
                n[rid] = n.get(rid, 0) + 1
            lanes = [before[rid] + j for rid, m in n.items()
                     for j in range(m)]
            spans.work[name] = shapes.decode_steps(max(n.values(), default=0),
                                                   lanes)
            return emitted

        rep.admit, rep.step_many = admit_spanned, block_spanned


# -- driving the traffic ----------------------------------------------------


def drive(eng, gen: traffic.Traffic, mix: dict, seconds: float,
          on_tick=None) -> dict:
    """Run the mix: ``warmup_s`` of steady state, then the window.  Returns
    the window, the requests (with their due times) and counter snapshots.
    After the window closes the traffic goes on, unmeasured, until every
    request of the window has finished or ``GRACE_S`` has passed."""
    from repro.serve.engine import Request

    done = eng._done
    reqs: dict[int, dict] = {}
    t_start = time.monotonic()
    t0 = t_start + mix["warmup_s"]
    t1 = t0 + seconds
    snap = {}
    i = 0

    def submit(due: float) -> None:
        nonlocal i
        spec = gen.spec(i)
        i += 1
        rid = eng.submit_request(Request(prompt=spec.prompt,
                                         max_new_tokens=spec.max_new),
                                 shed=False)
        reqs[rid] = {"prompt": spec.prompt, "budget": spec.max_new,
                     "due": due, "sent": time.monotonic()}

    def measured() -> list[int]:
        return [r for r, q in reqs.items() if t0 <= q["due"] < t1]

    if mix["loop"] == "closed":
        for _ in range(mix["clients"]):
            submit(time.monotonic())
    while True:
        now = time.monotonic()
        if "t0" not in snap and now >= t0:
            snap["t0"] = counters(eng)
        if "t1" not in snap and now >= t1:
            snap["t1"] = counters(eng)
        if on_tick is not None:
            on_tick(now, t0, t1)
        if now >= t1:
            with eng._wd:
                left = [r for r in measured() if r not in done]
            if not left or now >= t1 + GRACE_S:
                break
        if mix["loop"] == "open":
            while t_start + gen.due(i) <= now:
                submit(t_start + gen.due(i))
            time.sleep(max(0.0, min(t_start + gen.due(i) - now, 0.005)))
        else:
            with eng._wd:
                eng._wd.wait(0.005)
                free = [r for r in reqs if r in done and
                        not reqs[r].get("replaced")]
            for r in free:
                reqs[r]["replaced"] = True
                if now < t1:
                    submit(time.monotonic())
    with eng._wd:
        for rid, q in reqs.items():
            ev = eng._events.get(rid, {})
            q["t_admit"] = ev.get("t_admit")
            q["t_first"] = ev.get("t_first")
            q["token_ts"] = list(ev.get("token_ts", ()))
            q["status"] = eng._done.get(rid)
            q["error"] = eng._errors.get(rid)
            q["served"] = list(eng._transcripts.get(rid, ()))
    return {"t0": t0, "t1": t1, "requests": reqs, "measured": measured(),
            "snap": snap}


# -- correctness ------------------------------------------------------------


def finished(q: dict) -> bool:
    from repro.core.flags import STREAM_DONE

    return (q["status"] == STREAM_DONE and q["error"] is None
            and len(q["served"]) == q["budget"])


def check(c: dict, seed: int, run: dict) -> tuple[bool, dict, int]:
    """Compare a sample of the window's finished requests with the float32
    reference: the widest gap by which a served token's logit lies below
    the reference's best at its position.  Returns ``correct``, each
    number compared beside its limit, and how many tokens were compared."""
    reqs = run["requests"]
    ok = [r for r in run["measured"] if finished(reqs[r])]
    vocab = c["vocab_size"]
    bad_vocab = sum(1 for r in ok for t in reqs[r]["served"]
                    if not 0 <= t < vocab)
    sample = []
    if ok:
        longest = max(ok, key=lambda r: (len(reqs[r]["prompt"])
                                         + len(reqs[r]["served"]), r))
        rest = [r for r in sorted(ok) if r != longest]
        rng = np.random.default_rng([seed & (2**64 - 1), 3])
        pick = rng.permutation(len(rest))[: CHECK_ROWS - 1]
        sample = [longest] + [rest[j] for j in sorted(pick)]
    ref = importlib.import_module(f"bench.reference.{c['reference']}")
    widest = None
    served = 0
    if sample and not bad_vocab:
        items = [(reqs[r]["prompt"], np.asarray(reqs[r]["served"]))
                 for r in sample]
        g = ref.gaps(c, seed, items, rows=CHECK_ROWS,
                     length=c["serving"]["max_len"])
        widest = float(max(float(np.max(x)) for x, _ in g))
        served = sum(len(x) for x, _ in g)
    limit = c["limits"]["widest_gap"]
    failed = len(run["measured"]) - len(ok)
    checks = {
        "widest_gap": {"value": widest, "limit": limit},
        "requests_failed": {"value": failed, "limit": 0},
        "tokens_out_of_vocab": {"value": bad_vocab, "limit": 0},
    }
    correct = (limit is not None and widest is not None and widest <= limit
               and failed == 0 and bad_vocab == 0)
    return correct, checks, served


# -- one run ----------------------------------------------------------------


def run_cell(bench: dict, cell: dict, c: dict, mix: dict, *, seed: int,
             seconds: float, trace: bool, devices, peak: dict,
             t_process: float = T_PROCESS, log=None,
             base: Path = BENCH) -> dict:
    """Build, warm, drive, measure and check one cell on ``devices``;
    return the result line's object."""
    import jax

    log = log or (lambda *a: print(*a, file=sys.stderr, flush=True))
    from repro.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    _listen_for_compiles()

    shapes = counts.Shapes.from_config(c)
    eng = build(c, seed, devices)
    spans = None
    try:
        block = c["serving"]["decode_block"]
        warm(eng, traffic.prompt_lengths(mix), block)
        gen = traffic.Traffic(mix, seed, c["vocab_size"])
        trace_box = {}
        if trace:
            spans = tr.Spans()
            install_spans(eng, shapes, spans)
            trace_box["dir"] = tempfile.mkdtemp(prefix="bench-trace-")
            mid = max(0.0, (seconds - TRACE_S) / 2)

            def start():
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0  # it would slow the host
                jax.profiler.start_trace(trace_box["dir"],
                                         profiler_options=opts)
                trace_box["w0"] = time.perf_counter_ns()

            def stop():
                trace_box["w1"] = time.perf_counter_ns()
                jax.profiler.stop_trace()

            def on_tick(now, t0, t1):
                # the profiler starts and stops on threads of its own: either
                # call takes seconds, which the generator must not wait out
                if "start" not in trace_box and now >= t0 + mid:
                    trace_box["start"] = threading.Thread(target=start)
                    trace_box["start"].start()
                elif "w0" in trace_box and "stop" not in trace_box and (
                        now >= t0 + mid + TRACE_S or now >= t1):
                    trace_box["stop"] = threading.Thread(target=stop)
                    trace_box["stop"].start()
        else:
            on_tick = None
        t_window = {}

        def tick(now, t0, t1):
            if "setup" not in t_window and now >= t0:
                t_window["setup"] = time.perf_counter() - t_process
            if on_tick is not None:
                on_tick(now, t0, t1)

        run = drive(eng, gen, mix, seconds, on_tick=tick)
        if trace:
            trace_box["start"].join()
            if "stop" not in trace_box:
                stop()
            else:
                trace_box["stop"].join()
        setup_s = t_window["setup"]
        in_window = [t for t in _COMPILES if run["t0"] <= t < run["t1"]]
        placed = {n: str(rep.device) for n, rep, _ in replicas(eng)}
        mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                  for d in devices)
        plane_of = {n: f"/device:TPU:{rep.device.id}"
                    for n, rep, _ in replicas(eng)}
    finally:
        eng.close()
        del eng
        gc.collect()
    reqs, measured = run["requests"], run["measured"]
    log(f"cell {cell['name']}: replicas {placed}, compile cache {cache_dir}")
    log(f"window {seconds} s: {len(measured)} requests due, "
        f"{sum(1 for r in measured if finished(reqs[r]))} finished; "
        f"compiles inside the window: {len(in_window)}")
    if mix["loop"] == "open":
        late = sorted(reqs[r]["sent"] - reqs[r]["due"] for r in measured)
        if late:
            log("generator lateness (s): p50 {} p95 {} max {}".format(
                percentile(late, 50), percentile(late, 95), late[-1]))
    ctx = types.SimpleNamespace(
        cell=cell, config=c, shapes=shapes, peak=peak, mix=mix,
        chips=len(devices), slots=c["serving"]["slots_per_replica"],
        t0=run["t0"], t1=run["t1"], seconds=seconds, setup_s=setup_s,
        requests=[reqs[r] for r in measured], all_requests=list(reqs.values()),
        snap=run["snap"], trace=None)
    result = {"device": device_record(devices, mem)}
    if trace:
        ctx.trace = reduce_trace(trace_box, spans, plane_of)
        shutil.rmtree(trace_box["dir"], ignore_errors=True)
        result["device"]["busy_s"] = ctx.trace["busy_s"]
        result["device"]["window_s"] = ctx.trace["window_s"]
        result["breakdown"] = {"device_ops": ctx.trace["device_ops"],
                               "idle_gaps": ctx.trace["idle_gaps"]}
        log(f"trace: {len(ctx.trace['calls'])} calls matched, busy "
            f"{ctx.trace['busy_s']} s of {ctx.trace['window_s']} s")
    section = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in metrics_of(bench, section, cell["name"]):
        value = reader(m["name"], base)(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    correct, checks, compared = check(c, seed, run)
    log(f"reference: {compared} served tokens of {CHECK_ROWS} requests "
        "compared")
    attempted = len(measured)
    result = {"correct": correct, "attempted": attempted,
              "failed": checks["requests_failed"]["value"],
              "metrics": metrics, **result, "checks": checks}
    for k, v in checks.items():
        log(f"check {k}: {v['value']} (limit {v['limit']})")
    return result


_COMPILES: list[float] = []


def _listen_for_compiles() -> None:
    """Note the time of every backend compile in this process (once)."""
    import jax

    if _listen_for_compiles.__dict__.get("on"):
        return
    _listen_for_compiles.on = True
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: _COMPILES.append(time.monotonic())
        if "backend_compile" in event else None)


def reduce_trace(box: dict, spans: tr.Spans, plane_of: dict) -> dict:
    events = tr.load(box["dir"])
    offset = tr.host_to_trace_offset(events, spans.host_start)
    if offset is None:
        raise RuntimeError("no benchmark span found in the trace")
    window = (box["w0"] + offset, box["w1"] + offset)
    return tr.reduce(events, window, plane_of, spans)


def device_record(devices, mem: int) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "memory_peak_bytes": int(mem)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = load_json(ROOT / "BENCHMARK.json")
    cell = find_cell(bench, args.workload)
    c = load_config(cell["config"])
    mix = traffic.load_mix(cell["traffic"])

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: JAX found no TPU ({devices[0].platform}); no result",
              file=sys.stderr)
        return 2
    if len(devices) < cell["chips"]:
        print(f"bench: {cell['name']} needs {cell['chips']} chips, JAX found "
              f"{len(devices)}; no result", file=sys.stderr)
        return 2
    result = run_cell(bench, cell, c, mix, seed=args.seed,
                      seconds=args.seconds, trace=bool(args.trace),
                      devices=devices[: cell["chips"]],
                      peak=peak_of(devices[0].device_kind))
    print(json.dumps(result, default=float), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
