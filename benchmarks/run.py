"""Benchmark harness — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:

* ``offload/*``    — paper Fig. 3: empty-function offload cost, HAM vs the
  vendor-analogue naive RPC, across transports (THE paper metric)
* ``dispatch/*``   — device-side handler-table dispatch (TPU-native HAM)
* ``registry/*``   — §5.2 init/lookup complexity
* ``serialise/*``  — static bitwise pack vs self-describing vs pickle
* ``putget/*``     — offload data-plane bandwidth
* ``cluster/*``    — pipelined scheduler throughput vs serial round trips
* ``serving/*``    — worker-driven continuous batching vs the lockstep
  drive, open-loop Poisson SLOs, kill-under-traffic recovery

``--smoke`` runs every section at tiny sizes with one repeat — a CI
tripwire, not a measurement: the ``BENCH_*.json`` files it writes are
uploaded as PR artifacts so perf regressions leave a trace, but only
full runs produce comparable numbers.

Roofline terms per (arch × shape × mesh) are produced by the dry-run
(``python -m repro.launch.dryrun --all``), not here — they need the
512-device XLA_FLAGS environment.
"""

from __future__ import annotations

import argparse
import sys
import traceback


def main(argv=None) -> None:
    args = argparse.ArgumentParser(description=__doc__)
    args.add_argument("--smoke", action="store_true",
                      help="tiny sizes, 1 repeat (CI tripwire)")
    opts = args.parse_args(argv)

    from repro.compile_cache import use_compile_cache

    use_compile_cache()

    from benchmarks import (
        batching,
        cluster,
        device_dispatch,
        offload_overhead,
        putget,
        registry_scaling,
        serialisation,
        serving,
    )

    # the serialisation section's rows are reused by batching.run (which
    # persists them into BENCH_hotpath.json) — measure once, record twice
    serialise_rows: list = []

    def serialisation_section(smoke=False):
        serialise_rows[:] = serialisation.run(smoke=smoke)
        return serialise_rows

    # the sections that fork worker processes run first: a child forked
    # after this process initialised a JAX backend would contend for (or
    # hang on) the accelerator the parent holds
    sections = [
        ("offload_overhead (paper Fig. 3)", offload_overhead.run),
        ("registry_scaling", registry_scaling.run),
        ("serialisation", serialisation_section),
        ("putget", putget.run),
        ("batching (coalesced hot path + rpc fast path -> BENCH_hotpath.json)",
         lambda smoke=False: batching.run(
             smoke=smoke, serialise_rows=serialise_rows or None)),
        ("cluster (scheduler pipelining -> BENCH_cluster.json)", cluster.run),
        ("device_dispatch", device_dispatch.run),
        ("serving (worker-driven continuous batching -> BENCH_serving.json)",
         serving.run),
    ]
    failures = 0
    print("name,us_per_call,derived")
    for title, fn in sections:
        print(f"# --- {title} ---")
        try:
            for name, val, note in fn(smoke=opts.smoke):
                print(f"{name},{val:.3f},{note}", flush=True)
        except Exception:  # noqa: BLE001
            failures += 1
            traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
