#!/usr/bin/env python3
"""Compile each cell's serving executables for a described TPU v5e.

    JAX_PLATFORMS=cpu python3 bench/compile_cells.py [cell ...]

Run by hand, not a test: no chip is needed, and nothing runs.  For every
cell of ``BENCHMARK.json`` (or those named) it compiles, at the cell's
slots and ``max_len``, the program's fused admission at each prompt length
of the cell's mix and its fused decode block, and prints each one's
``memory_analysis()``: the bytes a replica needs on its chip, which is how
the slots of a configuration were chosen.  Sizes, never times.
"""

from __future__ import annotations

import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv: list[str]) -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from repro.models.api import build_model
    from repro.serve.engine import ServeProgram

    from bench import traffic
    from bench.run import program_config

    jax.config.update("jax_enable_compilation_cache", False)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
            tree)

    for cell in bench["workloads"]:
        if argv and cell["name"] not in argv:
            continue
        c = json.loads((ROOT / "bench" / "configs"
                        / f"{cell['config']}.json").read_text())
        sv = c["serving"]
        model = build_model(program_config(c))
        params = on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
        prog = ServeProgram(model, params, num_slots=sv["slots_per_replica"],
                            max_len=sv["max_len"])
        payload = on_chip(prog.payload_spec)
        nbytes = sum(a.size * a.dtype.itemsize
                     for a in jax.tree_util.tree_leaves(params))
        cache = sum(a.size * a.dtype.itemsize
                    for a in jax.tree_util.tree_leaves(payload["cache"]))
        print(f"{cell['name']}: weights {nbytes} B, KV cache {cache} B "
              f"({sv['slots_per_replica']} slots x {sv['max_len']})",
              flush=True)
        rows = [("block", prog.multi(sv["decode_block"]).lower(
            payload, params))]
        i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)
        for n in traffic.prompt_lengths(traffic.load_mix(cell["traffic"])):
            prompt = jax.ShapeDtypeStruct((1, n), jnp.int32, sharding=chip)
            rows.append((f"admit {n}", prog.admit.lower(
                params, payload["cache"], payload["tokens"], payload["pos"],
                prompt, i32)))
        for label, lowered in rows:
            m = lowered.compile().memory_analysis()
            print(f"  {label}: arguments {m.argument_size_in_bytes} B, "
                  f"outputs {m.output_size_in_bytes} B, temporaries "
                  f"{m.temp_size_in_bytes} B, aliased "
                  f"{m.alias_size_in_bytes} B", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
