#!/usr/bin/env python3
"""Chip smoke: serve qwen1.5-4b at full width on a TPU through the cluster
serving path.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four replicas, one per chip

One chip: qwen1.5-4b at its published widths and full depth (40 layers),
bf16 weights drawn from ``--seed``, served by ``ClusterServingEngine``
(worker-driven, 2 workers x 4 slots, ``max_len`` 512) through
``submit_request`` and ``wait``.  Eight greedy requests of 128 prompt
tokens and 64 new tokens each must get their whole budget of
in-vocabulary tokens.  Then one prompt's bf16 prefill logits are compared
with the same model's forward pass at float32 compute on the same weights.

Four chips (``--chips 4``, and no other phase): the same kind of requests
served by four replicas, replica *i* on device *i* with its own copy of the
weights and its own cache, must be token-identical under greedy decode to
one replica on one device.

Earlier lines report what was run and measured; the last line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
The script exits non-zero, without that line, when JAX finds no TPU, when a
phase raises and when a check fails.  Everything runs in this one process
(the replicas are thread workers), so the chips belong to it alone.  This is
a smoke run, not a benchmark: its times include compilation and a shared
host.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "qwen1.5-4b"
WORKERS, SLOTS, MAX_LEN = 2, 4, 512
REQUESTS, PROMPT_LEN, MAX_NEW = 8, 128, 64
#: four replicas of SLOTS slots: this many requests fill every slot at once
FOUR_CHIP_REQUESTS = 4 * SLOTS
#: bound on max|bf16 logits - float32 logits| / max|float32 logits| over the
#: prompt.  bf16 keeps 8 significant bits (relative rounding 2**-9), and
#: every matmul, norm and residual add of the 40 layers rounds its output to
#: bf16 once more, so the errors of some 300 roundings reach the logits.
#: With random weights the float32 logits are O(1) and the error of such a
#: stack is a few per cent; 0.1 leaves room for that and still fails a wrong
#: layer, a wrong cast or a cache written in the wrong place, which give
#: errors of order one.
LOGIT_TOL = 0.1
WAIT_S = 900.0


class SmokeFailure(RuntimeError):
    pass


def device_record() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def build(seed: int):
    """The published qwen1.5-4b widths and depth with bf16 weights (the
    published checkpoint's dtype), drawn from ``seed`` in one jitted init."""
    import jax

    from repro.configs import get_config
    from repro.models.api import build_model

    cfg = dataclasses.replace(get_config(ARCH), param_dtype="bfloat16")
    model = build_model(cfg)
    t0 = time.perf_counter()
    params = jax.block_until_ready(model.init(jax.random.PRNGKey(seed)))
    leaves = jax.tree_util.tree_leaves(params)
    print(f"model: {cfg.name} layers={cfg.num_layers} d_model={cfg.d_model} "
          f"heads={cfg.num_heads}/{cfg.num_kv_heads} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab_size} param_dtype={cfg.param_dtype} "
          f"compute_dtype={cfg.dtype}")
    print(f"params: {sum(x.size for x in leaves)} "
          f"({sum(x.nbytes for x in leaves)} bytes), "
          f"init_s={time.perf_counter() - t0:.3f}")
    return model, params


def make_prompts(n: int, vocab: int, seed: int):
    import numpy as np

    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, PROMPT_LEN, dtype=np.int32)
            for _ in range(n)]


def serve(model, params, prompts, *, workers: int, devices=None,
          label: str) -> dict[int, list[int]]:
    """Serve ``prompts`` greedily through ``ClusterServingEngine``; return
    each request's transcript, in prompt order."""
    from repro.serve.engine import ClusterServingEngine, Request

    eng = ClusterServingEngine(
        model, params, num_workers=workers, slots_per_worker=SLOTS,
        max_len=MAX_LEN, worker_driven=True, devices=devices,
    )
    try:
        placed = {n: str(d) for n, d in sorted(eng.replica_devices().items())}
        print(f"{label}: replicas (worker -> device) {placed}")
        # one request first: it compiles fused admission at this prompt
        # length and the fused decode block, which every replica on the
        # device then shares
        t0 = time.perf_counter()
        warm = eng.submit_request(Request(
            prompt=prompts[0], max_new_tokens=eng.decode_block + 1))
        eng.wait([warm], timeout=WAIT_S)
        print(f"{label}: compile_s={time.perf_counter() - t0:.3f} "
              "(first request, compilation included)")
        t0 = time.perf_counter()
        rids = [eng.submit_request(Request(prompt=p, max_new_tokens=MAX_NEW),
                                   shed=False) for p in prompts]
        eng.wait(rids, timeout=WAIT_S)
        wall = time.perf_counter() - t0
        out = eng.transcripts(rids)
        routed = dict(sorted(eng.sched.stats["routed"].items()))
    finally:
        eng.close()
    tokens = sum(len(t) for t in out.values())
    print(f"{label}: requests={len(rids)} tokens_served={tokens} "
          f"wall_s={wall:.3f} admits_per_worker={routed}")
    vocab = model.cfg.vocab_size
    bad = [r for r, t in out.items()
           if len(t) != MAX_NEW or not all(0 <= x < vocab for x in t)]
    if bad:
        raise SmokeFailure(
            f"{label}: requests {bad} did not get {MAX_NEW} in-vocabulary "
            f"tokens: { {r: out[r] for r in bad[:2]} }")
    return [out[r] for r in rids]


def logit_error(model, params, prompt) -> float:
    """max|bf16 prefill logits - reference| / max|reference| over every
    position of ``prompt``.  The reference is the same model at float32
    compute under ``highest`` matmul precision, on the same weights."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models.api import build_model

    batch = {"tokens": jnp.asarray(prompt[None])}
    got = jax.jit(model.prefill)(params, batch)[0]
    ref_model = build_model(dataclasses.replace(model.cfg, dtype="float32"))
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(ref_model.forward)(params, batch)
    got = np.asarray(got.astype(jnp.float32))
    ref = np.asarray(ref)
    if got.shape != ref.shape or got.shape[-1] != model.cfg.vocab_size:
        raise SmokeFailure(f"logit shapes {got.shape} vs {ref.shape}")
    if not (np.isfinite(got).all() and np.isfinite(ref).all()):
        raise SmokeFailure("non-finite prefill logits")
    return float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))


def peak_bytes() -> dict[str, int | None]:
    import jax

    return {str(d): (d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.devices()}


def one_chip(seed: int) -> None:
    import gc

    model, params = build(seed)
    prompts = make_prompts(REQUESTS, model.cfg.vocab_size, seed)
    serve(model, params, prompts, workers=WORKERS, label="serve")
    gc.collect()  # the replicas' caches go before the reference runs
    t0 = time.perf_counter()
    err = logit_error(model, params, prompts[0])
    print(f"logits: max_abs_err/max_abs_ref={err:.6f} (tolerance "
          f"{LOGIT_TOL}), check_s={time.perf_counter() - t0:.3f}")
    print(f"peak_bytes_in_use: {peak_bytes()}")
    if not err <= LOGIT_TOL:
        raise SmokeFailure(f"bf16 logits off the float32 reference by {err}")


def four_chips(seed: int) -> None:
    import jax

    devs = jax.devices()
    if len(devs) < 4:
        raise SmokeFailure(f"--chips 4 needs 4 devices, JAX found {len(devs)}")
    model, params = build(seed)
    prompts = make_prompts(FOUR_CHIP_REQUESTS, model.cfg.vocab_size, seed)
    one = serve(model, params, prompts, workers=1, devices=devs[:1],
                label="1 replica on 1 chip")
    four = serve(model, params, prompts, workers=4, devices=devs[:4],
                 label="4 replicas on 4 chips")
    same = sum(a == b for a, b in zip(one, four))
    print(f"transcripts identical: {same}/{len(prompts)}")
    print(f"peak_bytes_in_use: {peak_bytes()}")
    if same != len(prompts):
        raise SmokeFailure("4-replica transcripts differ from 1 replica")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    device = device_record()
    print(f"device: platform={device['platform']} kind={device['kind']} "
          f"count={device['count']}")
    if device["platform"] != "tpu":
        print("chip_smoke: JAX found no TPU; nothing was run",
              file=sys.stderr)
        return 2

    from repro.compile_cache import use_compile_cache

    print(f"compile cache: {use_compile_cache()}")
    print(f"jax {jax.__version__}")
    t0 = time.perf_counter()
    try:
        (four_chips if args.chips == 4 else one_chip)(args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(f"total_s={time.perf_counter() - t0:.3f}")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
