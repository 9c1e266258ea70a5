"""The benchmark's FLOP and byte counts against values worked by hand."""

import json
from pathlib import Path

import pytest

from bench.counts import Shapes, least_seconds

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def shapes(name):
    return Shapes.from_config(json.loads((CONFIGS / f"{name}.json").read_text()))


def test_qwen15_4b_counts():
    s = shapes("qwen1.5-4b")
    # q, k, v, o: 2560 x (3 x 2560) + 2560 x 2560; gate, up, down: 3 x 2560 x 6912
    assert s.layer_matmul_params == 19_660_800 + 6_553_600 + 53_084_160
    # + q/k/v biases (3 x 2560) + two norms (2 x 2560)
    assert s.layer_params == 79_298_560 + 7_680 + 5_120
    # the published parameter count: 40 layers, final norm, head, embedding
    assert s.stack_weight_bytes // 2 + 151_936 * 2560 == 3_950_369_280
    # K and V, 20 heads of 128, 40 layers, bf16
    assert s.kv_bytes_per_token == 2 * 40 * 20 * 128 * 2 == 409_600
    flops, nbytes = s.prefill(128)
    # 2 x 128 x 40 x 79,298,560 + causal attention 2 x 20 x 128 x 128 x 129 x 40
    # + the head at the last position 2 x 2560 x 151,936
    assert flops == 812_017_254_400 + 3_381_657_600 + 777_912_320
    # every weight + 128 embedding rows + 128 positions of cache
    assert nbytes == 7_122_826_240 + 655_360 + 52_428_800
    flops, nbytes = s.decode_lane(200)
    assert flops == 6_343_884_800 + 4 * 20 * 128 * 201 * 40 + 777_912_320
    assert nbytes == 2560 * 2 + 201 * 409_600


def test_internlm2_20b_l8_counts():
    s = shapes("internlm2-20b-l8")
    # GQA 48/8: q 6144 x 6144, k and v 6144 x 1024 each, o 6144 x 6144
    assert s.layer_matmul_params == 50_331_648 + 37_748_736 + 301_989_888
    assert s.layer_params == 390_070_272 + 2 * 6144  # no biases
    assert s.stack_weight_bytes == 2 * (8 * 390_082_560 + 6144 + 6144 * 92_544)
    assert s.kv_bytes_per_token == 2 * 8 * 8 * 128 * 2 == 32_768
    flops, nbytes = s.prefill(128)
    assert flops == 798_863_917_056 + 1_623_195_648 + 1_137_180_672
    assert nbytes == 7_378_513_920 + 128 * 6144 * 2 + 128 * 32_768


def test_decode_steps_reads_weights_once_per_step():
    s = shapes("qwen1.5-4b")
    flops, nbytes = s.decode_steps(2, [10, 11])
    a, b = s.decode_lane(10), s.decode_lane(11)
    assert flops == a[0] + b[0]
    assert nbytes == 2 * s.stack_weight_bytes + a[1] + b[1]


@pytest.mark.parametrize("flops,nbytes,want", [
    (197e12, 1.0, 1.0),      # bound by FLOPs
    (1.0, 819e9, 1.0),       # bound by bandwidth
    (197e12, 2 * 819e9, 2.0),
])
def test_least_seconds_is_the_larger_bound(flops, nbytes, want):
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert least_seconds(flops, nbytes, peak) == pytest.approx(want)
