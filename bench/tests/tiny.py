"""A reduced dense decoder and mix for running the harness on the CPU.

The widths are cut, so that a run takes seconds here; nothing else about
the path changes: the same weights generator, program, serving engine,
traffic generator, readers and reference as a cell on the chip.
"""

import json
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]

CONFIG = {
    "hidden_size": 128, "intermediate_size": 288, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 1024,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": False, "family": "dense", "qkv_bias": True,
    "reference": "dense_decoder",
    "serving": {"param_dtype": "bfloat16", "slots_per_replica": 4,
                "max_len": 128, "decode_block": 4},
    # bf16 serving of this model reads below 0.02 and its fp8 control above
    # 0.15 (bench/tests/test_bench_control.py)
    "limits": {"widest_gap": 0.05},
}

OPEN = {"loop": "open", "rate_per_s": 20, "warmup_s": 0.3, "cycle": 16,
        "prompt": {"dist": "choice", "values": [16, 32]},
        "output": {"dist": "uniform_int", "min": 8, "max": 24},
        "max_total": 128}

CLOSED = dict(OPEN, loop="closed", clients=8)

PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def run(cell_name: str, mix: dict, *, seed: int = 2**31 + 5, trace=False,
        config=None, bench=None, base=None, seconds=1.0) -> dict:
    """One run of the harness on the CPU, past its look for a chip."""
    import jax

    from bench import run as harness

    bench = bench or benchmark()
    cell = harness.find_cell(bench, cell_name)
    kw = {} if base is None else {"base": base}
    return harness.run_cell(bench, cell, config or CONFIG, mix, seed=seed,
                            seconds=seconds, trace=trace,
                            devices=jax.devices("cpu")[:1], peak=PEAK,
                            log=lambda *a: None, **kw)


@pytest.fixture
def no_cache(monkeypatch, tmp_path):
    """Keep the harness's compilation cache out of the checkout."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))
