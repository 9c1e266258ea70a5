"""The harness end to end on the CPU at a reduced size, and the discovery by
name of configurations, mixes and metrics."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import run as harness
from bench import traffic
from bench.tests import tiny
from bench.tests.tiny import no_cache  # noqa: F401  (fixture)

CHAT = "qwen1.5-4b.chat.r80"


def test_open_loop_run_is_correct_and_reports_its_metrics(no_cache):  # noqa: F811
    res = tiny.run(CHAT, tiny.OPEN)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 5
    assert set(res["metrics"]) == {"ttft_p50_ms", "ttft_p95_ms",
                                   "tpot_p95_ms", "setup_s"}
    m = res["metrics"]
    assert 0 < m["ttft_p50_ms"]["value"] <= m["ttft_p95_ms"]["value"]
    assert list(res)[-1] == "checks"
    assert res["checks"]["widest_gap"]["value"] <= tiny.CONFIG["limits"]["widest_gap"]
    assert res["device"]["platform"] == "cpu"


def test_closed_loop_traced_run_reads_host_counters(no_cache):  # noqa: F811
    bench = tiny.benchmark()
    bench["workloads"].append({"name": "tiny.batch", "config": "tiny",
                               "traffic": "tiny.closed", "chips": 1,
                               "why": "a closed loop"})
    readers = ["lane_util", "host_msgs_per_token", "decode_roofline.tput",
               "mfu.tput"]
    bench["per_layer"] = [m for m in bench["per_layer"]
                          if m["name"] not in readers] + [
        {"name": n, "unit": "x", "better": "higher", "source": "host_clock",
         "layer": "x", "moves": "setup_s", "workloads": ["tiny.batch"]}
        for n in readers]
    res = tiny.run("tiny.batch", tiny.CLOSED, trace=True, bench=bench)
    assert res["correct"], res["checks"]
    m = res["metrics"]
    # the CPU has no device trace: those readers find nothing and say so
    assert "decode_roofline.tput" not in m and "mfu.tput" not in m
    assert 0 < m["lane_util"]["value"] <= 1
    assert 0 < m["host_msgs_per_token"]["value"] < 1
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert "breakdown" in res


def test_a_new_config_mix_and_metric_are_found_by_name(tmp_path, no_cache):  # noqa: F811
    """A later change adds files and entries; no existing file changes."""
    base = tmp_path / "bench"
    shutil.copytree(harness.BENCH, base,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    (base / "configs" / "dummy.json").write_text(json.dumps(tiny.CONFIG))
    (base / "traffic" / "dummy.mix.json").write_text(json.dumps(tiny.OPEN))
    (base / "metrics" / "dummy_requests.py").write_text(
        "def read(ctx):\n    return float(len(ctx.requests))\n")
    bench = tiny.benchmark()
    bench["workloads"].append({"name": "dummy.cell", "config": "dummy",
                               "traffic": "dummy.mix", "chips": 1,
                               "why": "a dummy"})
    bench["per_layer"].append({"name": "dummy_requests", "unit": "requests",
                               "better": "higher", "source": "host_clock",
                               "layer": "admission and routing",
                               "moves": "ttft_p95_ms",
                               "workloads": ["dummy.cell"]})
    config = harness.load_config("dummy", base)
    mix = traffic.load_mix("dummy.mix", base / "traffic")
    res = tiny.run("dummy.cell", mix, config=config, bench=bench, base=base,
                   trace=True)
    assert res["metrics"]["dummy_requests"]["value"] == res["attempted"]


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(harness.BENCH / "run.py"),
                        "--workload", CHAT, "--seed", "1", "--seconds", "1",
                        "--trace", "0"], capture_output=True, text=True,
                       env=env, timeout=120)
    assert p.returncode == 2
    assert "{" not in p.stdout
