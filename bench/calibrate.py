#!/usr/bin/env python3
"""Read what a cell's correctness limit is set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 10

Run by hand when a cell or its configuration is added, not by the benchmark.
In one process, for each seed, it runs the cell as ``bench/run.py`` does
(with a short window at the cell's own load) and, on the same sample of
served requests, also computes the control: the reference in float8, the
precision step below the configuration's bf16 (``bench/reference``).  It
prints one line per seed with the program's widest gap (the lower reading
is the largest over the seeds) and the control's (the upper reading is the
smallest), and last the two readings.  The limit in the configuration file
is set between them, as ``PERF.md`` records.
"""

from __future__ import annotations

import argparse
import importlib
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import run, traffic  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)

    import jax

    bench = run.load_json(run.ROOT / "BENCHMARK.json")
    cell = run.find_cell(bench, args.workload)
    c = run.load_config(cell["config"])
    mix = traffic.load_mix(cell["traffic"])
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print("calibrate: needs the cell's TPU chips", file=sys.stderr)
        return 2
    ref = importlib.import_module(f"bench.reference.{c['reference']}")
    plain = ref.gaps
    seen: list = []

    def with_control(*a, **kw):
        out = plain(*a, **dict(kw, control=True))
        seen.append(out)
        return out

    ref.gaps = with_control
    served, control = [], []
    for seed in [int(s) for s in args.seeds.split(",")]:
        seen.clear()
        res = run.run_cell(bench, cell, c, mix, seed=seed,
                           seconds=args.seconds, trace=False,
                           devices=devices[: cell["chips"]],
                           peak=run.peak_of(devices[0].device_kind))
        if not seen:
            print(json.dumps({"seed": seed, "checks": res["checks"]}))
            continue
        (g,) = seen
        s = max(float(x.max()) for x, _ in g)
        k = max(float(y.max()) for _, y in g)
        served.append(s)
        control.append(k)
        print(json.dumps({"seed": seed, "served_widest_gap": s,
                          "control_widest_gap": k,
                          "tokens": sum(len(x) for x, _ in g),
                          "failed": res["failed"]}), flush=True)
    print(json.dumps({"lower_reading": max(served, default=None),
                      "upper_reading": min(control, default=None),
                      "seeds": len(served)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
