"""Serving engine: device-table dispatch, continuous batching, consistency."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.core.device_table import DeviceHandlerTable
from repro.core.errors import RegistryError
from repro.models.api import build_model
from repro.serve.engine import Request, ServingEngine


@pytest.fixture(scope="module")
def model_and_params():
    cfg = get_reduced("llama3-405b")
    model = build_model(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def test_device_table_keys_sorted_and_stable():
    t = DeviceHandlerTable()
    t.register("z", lambda x: x)
    t.register("a", lambda x: x + 1)
    t.register("m", lambda x: x * 2)
    assert [h.stable_name for h in t.handlers] == ["a", "m", "z"]
    assert t.key_of("a") == 0 and t.key_of("z") == 2


def test_device_table_rejects_mismatched_results():
    t = DeviceHandlerTable()
    t.register("a", lambda x: x)
    t.register("b", lambda x: (x, x))  # different result structure
    with pytest.raises(RegistryError):
        t.validate(jax.ShapeDtypeStruct((4,), jnp.float32))


def test_device_table_dispatch_selects_branch():
    t = DeviceHandlerTable()
    t.register("id", lambda x: x)
    t.register("neg", lambda x: -x)
    d = t.build(jax.ShapeDtypeStruct((3,), jnp.float32))
    x = jnp.arange(3.0)
    np.testing.assert_array_equal(d(jnp.int32(t.key_of("id")), x), x)
    np.testing.assert_array_equal(d(jnp.int32(t.key_of("neg")), x), -x)


def test_engine_greedy_matches_manual_decode(model_and_params):
    model, params = model_and_params
    cfg = model.cfg
    prompt = np.arange(6) % cfg.vocab_size
    eng = ServingEngine(model, params, num_slots=1, max_len=32)
    out = eng.run([Request(prompt=prompt, max_new_tokens=5)])
    # manual: prefill + greedy loop
    logits, cache0 = model.prefill(params, {"tokens": jnp.asarray(prompt[None])})
    cache = model.init_cache(1, 32)
    cache = jax.tree_util.tree_map(
        lambda full, part: jax.lax.dynamic_update_slice(
            full, part.astype(full.dtype), (0,) * full.ndim),
        cache, cache0)
    tok = int(jnp.argmax(logits[0, -1]))
    manual = [tok]
    pos = len(prompt)
    for _ in range(4):
        lg, cache = model.decode_step(
            params, cache,
            {"tokens": jnp.asarray([[tok]], jnp.int32),
             "pos": jnp.asarray([pos], jnp.int32)})
        tok = int(jnp.argmax(lg[0, -1]))
        manual.append(tok)
        pos += 1
    assert out[0] == manual


def test_engine_continuous_batching_mixed_lengths(model_and_params):
    model, params = model_and_params
    cfg = model.cfg
    reqs = [
        Request(prompt=np.arange(4) % cfg.vocab_size, max_new_tokens=3),
        Request(prompt=np.arange(9) % cfg.vocab_size, max_new_tokens=6),
        Request(prompt=np.arange(2) % cfg.vocab_size, max_new_tokens=4),
        Request(prompt=np.arange(5) % cfg.vocab_size, max_new_tokens=2),
    ]
    eng = ServingEngine(model, params, num_slots=2, max_len=32)
    out = eng.run(reqs)
    assert sorted(out) == [0, 1, 2, 3]
    for i, r in enumerate(reqs):
        assert len(out[i]) == r.max_new_tokens
    # continuous batching admits late requests into freed slots: the total
    # dispatched steps must be < sum of per-request lengths (batched)
    assert eng.steps_dispatched < sum(r.max_new_tokens for r in reqs)


def test_engine_isolation_between_slots(model_and_params):
    """A request's output must not depend on what shares the batch."""
    model, params = model_and_params
    cfg = model.cfg
    p = np.arange(5) % cfg.vocab_size
    solo = ServingEngine(model, params, num_slots=1, max_len=32).run(
        [Request(prompt=p, max_new_tokens=4)])[0]
    other = np.arange(7)[::-1] % cfg.vocab_size
    mixed = ServingEngine(model, params, num_slots=2, max_len=32).run(
        [Request(prompt=p, max_new_tokens=4),
         Request(prompt=other, max_new_tokens=4)])[0]
    assert solo == mixed


def test_engine_sampling_temperature(model_and_params):
    model, params = model_and_params
    cfg = model.cfg
    p = np.arange(5) % cfg.vocab_size
    eng = ServingEngine(model, params, num_slots=1, max_len=32, seed=7)
    out = eng.run([Request(prompt=p, max_new_tokens=8, temperature=1.5)])
    assert len(out[0]) == 8
    assert all(0 <= t < cfg.vocab_size for t in out[0])


@pytest.mark.slow
def test_cluster_serving_matches_single_engine_lengths(model_and_params):
    """Continuous batching through the worker pool: same requests, same
    output lengths as the single engine, decode steps overlapping across
    two workers."""
    from repro.serve.engine import ClusterServingEngine

    model, params = model_and_params
    cfg = model.cfg
    mk = lambda: [  # noqa: E731 — fresh Request objects per engine (rids mutate)
        Request(prompt=np.arange(3 + i % 3) % cfg.vocab_size,
                max_new_tokens=2 + i % 3)
        for i in range(6)
    ]
    eng = ClusterServingEngine(model, params, num_workers=2,
                               slots_per_worker=2, max_len=24)
    try:
        out = eng.run(mk())
    finally:
        eng.close()
    ref = ServingEngine(model, params, num_slots=2, max_len=24).run(mk())
    assert sorted(out) == sorted(ref)
    assert {r: len(v) for r, v in out.items()} == {
        r: len(v) for r, v in ref.items()
    }
    # both workers actually served traffic
    assert all(n > 0 for n in eng.sched.stats["routed"].values())


@pytest.mark.slow
def test_cluster_serving_survives_resize(model_and_params):
    """Serving elasticity (ROADMAP): engine replicas follow pool membership
    — a node added mid-life takes admissions, a drained removal retires its
    replica, and serving continues across both."""
    from repro.serve.engine import ClusterServingEngine

    model, params = model_and_params
    cfg = model.cfg
    eng = ClusterServingEngine(model, params, num_workers=1,
                               slots_per_worker=2, max_len=24)
    try:
        assert eng.serving_nodes() == [1]
        new = eng.pool.add_node()
        assert new in eng.serving_nodes()  # replica created on join
        reqs = [
            Request(prompt=np.arange(3 + i % 3) % cfg.vocab_size,
                    max_new_tokens=3)
            for i in range(6)
        ]
        out = eng.run(reqs)
        assert {r: len(v) for r, v in out.items()} == {
            i: 3 for i in range(6)
        }
        assert eng.sched.stats["routed"].get(new, 0) > 0  # newcomer served
        eng.pool.remove_node(new, drain=True)
        assert eng.serving_nodes() == [1]  # replica retired with the node
        out2 = eng.run([
            Request(prompt=np.arange(4) % cfg.vocab_size, max_new_tokens=2)
        ])
        assert len(out2[0]) == 2  # serving survived the shrink
    finally:
        eng.close()


@pytest.mark.slow
def test_cluster_serving_recovers_requests_from_dead_worker(model_and_params):
    """Session recovery: kill a serving worker mid-decode; its requests
    re-admit on the survivor from the host-held transcript (prompt +
    tokens so far) and every request still reaches full length."""
    import threading
    import time

    from repro.serve.engine import ClusterServingEngine

    model, params = model_and_params
    cfg = model.cfg
    eng = ClusterServingEngine(model, params, num_workers=2,
                               slots_per_worker=2, max_len=48)
    killed = {}

    def killer():
        deadline = time.time() + 60
        while time.time() < deadline:
            if eng.sched.stats["completed"] >= 6:  # mid-run, decode going
                victim = eng.serving_nodes()[0]
                eng.pool.kill(victim)
                killed["node"] = victim
                return
            time.sleep(0.005)

    t = threading.Thread(target=killer)
    t.start()
    try:
        reqs = [
            Request(prompt=np.arange(3 + i % 3) % cfg.vocab_size,
                    max_new_tokens=10)
            for i in range(6)
        ]
        out = eng.run(reqs, timeout=120)
    finally:
        t.join()
        eng.close()
    assert "node" in killed, "the kill must land mid-run"
    assert sorted(out) == list(range(6))
    assert {r: len(v) for r, v in out.items()} == {i: 10 for i in range(6)}


def test_noop_branch_preserves_state(model_and_params):
    model, params = model_and_params
    eng = ServingEngine(model, params, num_slots=1, max_len=16)
    before = jax.tree_util.tree_map(lambda a: np.asarray(a).copy(),
                                    eng.payload)
    eng.step(key=eng.key_noop)
    for a, b in zip(jax.tree_util.tree_leaves(before),
                    jax.tree_util.tree_leaves(eng.payload)):
        if a.dtype == np.uint32:  # rng key unchanged by noop too
            pass
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _largest_constant(hlo_text: str) -> int:
    """Element count of the largest ``stablehlo.constant`` in ``hlo_text``."""
    import re

    sizes = [0]
    for dims in re.findall(r"stablehlo\.constant dense<.*?> : tensor<([^>]*)>",
                           hlo_text):
        n = 1
        for d in dims.split("x")[:-1]:  # the last field is the dtype
            n *= int(d)
        sizes.append(n)
    return max(sizes)


def test_serve_executables_embed_no_weights(model_and_params):
    """Weights enter every serve executable as an argument: a closed-over
    array would be lowered into the executable as a constant, one copy of
    the model per executable."""
    model, params = model_and_params
    eng = ServingEngine(model, params, num_slots=2, max_len=16)
    prog, payload = eng.program, eng.payload
    smallest_weight = min(a.size for a in jax.tree_util.tree_leaves(params)
                          if a.ndim >= 2)
    lowered = {
        "decode_step": prog.dispatch.lower(np.int32(prog.key_greedy),
                                           payload, params),
        "decode_block": prog.multi(4).lower(payload, params),
        "admission": prog.admit.lower(
            params, payload["cache"], payload["tokens"], payload["pos"],
            np.zeros((1, 5), np.int32), np.int32(0)),
    }
    for name, low in lowered.items():
        text = low.as_text()
        assert _largest_constant(text) < smallest_weight, name
        # and the weights are parameters of the executable
        assert f"tensor<{model.cfg.vocab_size}x{model.cfg.d_model}x" in text
