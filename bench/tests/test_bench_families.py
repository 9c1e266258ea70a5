"""What moving the dense decoder into ``bench/families/dense.py`` must not
change: the weight bits drawn from a seed, the work counts of both
published configurations, and the program's configuration."""

import hashlib
import json
import shutil
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import weights
from bench.families import dense
from bench.tests import tiny
from bench.tests.tiny import no_cache  # noqa: F401  (fixture)

CONFIGS = Path(__file__).resolve().parents[1] / "configs"
SEED = 2**31 + 7


def digest(tree, *, with_meta: bool) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        if with_meta:
            h.update(str(a.dtype).encode())
            h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def config(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


# the values bench/weights.py gave for the tiny configuration before the
# dense family moved into bench/families/dense.py
@pytest.mark.parametrize("tree,want", [
    ("drawn", "91b6611c30143b066c29ea40547eb0a168ff83c6c5e0d9e1e978e1e6753b063b"),
    ("program", "273c56f9b567a4de0c553b02af26d8279e7d91e9d77d629fc87005d26263dadc"),
])
def test_dense_weight_bits_are_pinned(tree, want):
    w = dense.make_weights(weights.root_key(SEED), dense.shapes(tiny.CONFIG))
    if tree == "program":
        w = dense.to_program(w)
    assert digest(w, with_meta=tree == "drawn") == want


@pytest.mark.parametrize("name,want", [
    ("qwen1.5-4b", dict(layers=40, d=2560, heads=20, kv_heads=20,
                        head_dim=128, d_ff=6912, vocab=151936, qkv_bias=True,
                        dtype_bytes=2)),
    ("internlm2-20b-l8", dict(layers=8, d=6144, heads=48, kv_heads=8,
                              head_dim=128, d_ff=16384, vocab=92544,
                              qkv_bias=False, dtype_bytes=2)),
])
def test_published_shapes_are_pinned(name, want):
    s = dense.shapes(config(name))
    assert vars(s) == want


@pytest.mark.parametrize("name,want", [
    ("qwen1.5-4b", dict(num_layers=40, d_model=2560, num_heads=20,
                        num_kv_heads=20, d_ff=6912, vocab_size=151936,
                        qkv_bias=True, rope_theta=5_000_000.0,
                        norm_eps=1e-6)),
    ("internlm2-20b-l8", dict(num_layers=8, d_model=6144, num_heads=48,
                              num_kv_heads=8, d_ff=16384, vocab_size=92544,
                              qkv_bias=False, rope_theta=1_000_000.0,
                              norm_eps=1e-5)),
])
def test_dense_program_config_is_the_former_one(name, want):
    """The ``ModelConfig`` that ``bench/run.py``'s ``program_config`` built
    before it moved here."""
    from repro.models.config import ModelConfig

    former = ModelConfig(name="bench", family="dense", mlp="swiglu",
                         tie_embeddings=False, dtype="bfloat16",
                         param_dtype="bfloat16", **want)
    assert dense.program_config(config(name)) == former


# -- a family added as new files alone ----------------------------------------

FIXTURE = Path(__file__).parent / "fixtures" / "tiny_moe"
MOE_CELL = "tiny_moe.chat"
MOE_CONFIG = {
    "hidden_size": 128, "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "vocab_size": 1024, "num_local_experts": 4,
    "num_experts_per_tok": 2, "moe_intermediate_size": 64,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-6, "family": "tiny_moe",
    "reference": "tiny_moe",
    "serving": {"param_dtype": "bfloat16", "slots_per_replica": 4,
                "max_len": 128, "decode_block": 4},
    # bf16 serving read 0.003-0.39 over 10 seeds on the CPU (an expert
    # choice flips at a near tie), the float8-weights control 0.53-1.23
    "limits": {"widest_gap": 0.5},
}


def moe_bench(tmp_path) -> tuple[Path, dict]:
    """A copy of ``bench/`` with the tiny_moe family, its reference, its
    configuration and a reader of its counter added as files, and the
    benchmark with a cell and the metric added as entries."""
    from bench import run as harness

    base = tmp_path / "bench"
    shutil.copytree(harness.BENCH, base,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    for sub in ("families", "reference", "metrics"):
        for f in (FIXTURE / sub).iterdir():
            shutil.copy(f, base / sub / f.name)
    (base / "configs" / "tiny_moe.json").write_text(json.dumps(MOE_CONFIG))
    bench = tiny.benchmark()
    bench["configs"].append({"name": "tiny_moe", "source": "a test",
                             "file": "bench/configs/tiny_moe.json",
                             "reduced": [], "why": "a family from new files"})
    bench["workloads"].append({"name": MOE_CELL, "config": "tiny_moe",
                               "traffic": "tiny.open", "chips": 1,
                               "why": "a family from new files"})
    for m in bench["end_to_end"]:
        if m["name"] in ("ttft_p50_ms", "ttft_p95_ms", "tpot_p95_ms"):
            m["workloads"].append(MOE_CELL)
    bench["per_layer"].append({"name": "tiny_moe_lanes", "unit": "lanes",
                               "better": "higher",
                               "source": "program_counter",
                               "layer": "worker decode loop",
                               "moves": "ttft_p95_ms",
                               "workloads": [MOE_CELL]})
    return base, bench


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_a_family_from_new_files_runs_through_the_harness(trace, tmp_path,
                                                          no_cache):  # noqa: F811
    from bench import run as harness

    base, bench = moe_bench(tmp_path)
    config = harness.load_config("tiny_moe", base)
    assert harness.family(config, base).__file__ == str(
        (base / "families" / "tiny_moe.py").resolve())
    res = tiny.run(MOE_CELL, tiny.OPEN, config=config, bench=bench,
                   base=base, trace=trace)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 5
    m = res["metrics"]
    if trace:
        # lanes stepped, from the family's counter summed over replicas
        assert m["tiny_moe_lanes"]["value"] > 0
        assert m["tiny_moe_lanes"]["value"] % MOE_CONFIG["serving"][
            "slots_per_replica"] == 0
    else:
        assert set(m) == {"ttft_p50_ms", "ttft_p95_ms", "tpot_p95_ms",
                          "setup_s"}


def test_program_counters_are_summed_over_replicas(monkeypatch):
    from types import SimpleNamespace

    from bench import run as harness

    eng = SimpleNamespace(sched=SimpleNamespace(stats={
        "submitted": 3, "oneways": 1, "routed": {1: 2, 2: 1}}))
    reps = [(1, SimpleNamespace(steps_dispatched=4, lanes_stepped=16),
             SimpleNamespace(stats={"frames": 5, "tokens": 9})),
            (2, SimpleNamespace(steps_dispatched=2, lanes_stepped=8),
             SimpleNamespace(stats={"frames": 1, "tokens": 3}))]
    monkeypatch.setattr(harness, "replicas", lambda _: reps)
    plain = harness.counters(eng)
    summed = harness.counters(eng, lambda e: {"lanes": e.lanes_stepped,
                                              "one": 1})
    assert plain == {"submitted": 3, "oneways": 1, "routed": {1: 2, 2: 1},
                     "frames": 6, "loop_tokens": 12, "steps": 6,
                     "program": {}}
    assert summed == dict(plain, program={"lanes": 24, "one": 2})
