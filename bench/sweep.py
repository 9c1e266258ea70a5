#!/usr/bin/env python3
"""Find an open-loop cell's knee: the highest offered rate one build of its
replicas sustains.

    python3 bench/sweep.py --workload <cell> --rates 4,6,8 --seconds 20

Run once on the chip when a rate cell is added, not by the benchmark: the
rate a cell offers is fixed in its mix file.  One process builds and warms
the cell's replicas once, then offers each rate in turn for ``--seconds``
(after the mix's ``warmup_s``), and prints one line per rate: requests due
and finished in the window, the admission queue at the window's open and
close, and the TTFT percentiles.  The knee is the highest rate at which the
queue does not grow across the window.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import run, traffic  # noqa: E402
from bench.stats import percentile  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    import jax

    from repro.compile_cache import use_compile_cache

    bench = run.load_json(run.ROOT / "BENCHMARK.json")
    cell = run.find_cell(bench, args.workload)
    c = run.load_json(run.BENCH / "configs" / f"{cell['config']}.json")
    mix = traffic.load_mix(cell["traffic"])
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print("sweep: needs the cell's TPU chips", file=sys.stderr)
        return 2
    use_compile_cache()
    eng = run.build(c, args.seed, devices[: cell["chips"]])
    try:
        run.warm(eng, traffic.prompt_lengths(mix), c["serving"]["decode_block"])
        slots = c["serving"]["slots_per_replica"] * cell["chips"]
        for rate in [float(r) for r in args.rates.split(",")]:
            m = dict(mix, rate_per_s=rate)
            queue = {}

            def tick(now, t0, t1, queue=queue):
                for key, t in (("open", t0), ("close", t1)):
                    if key not in queue and now >= t:
                        with eng._wd:
                            queue[key] = max(0, len(eng._budget)
                                             - len(eng._done) - slots)

            t = time.monotonic()
            r = run.drive(eng, traffic.Traffic(m, args.seed, c["vocab_size"]),
                          m, args.seconds, on_tick=tick)
            reqs = [r["requests"][i] for i in r["measured"]]
            ttft = [(q["t_first"] - q["due"]) * 1e3 for q in reqs
                    if q["t_first"] is not None]
            print(json.dumps({
                "rate_per_s": rate, "due": len(reqs),
                "finished": sum(1 for q in reqs if run.finished(q)),
                "queue_open": queue.get("open"),
                "queue_close": queue.get("close"),
                "ttft_p50_ms": percentile(ttft, 50) if ttft else None,
                "ttft_p95_ms": percentile(ttft, 95) if ttft else None,
                "wall_s": time.monotonic() - t}), flush=True)
            # let the queue drain before the next rate
            drain = time.monotonic() + run.GRACE_S
            while time.monotonic() < drain:
                with eng._wd:
                    if not eng._pending and set(eng._budget) <= set(eng._done):
                        break
                time.sleep(0.1)
    finally:
        eng.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
