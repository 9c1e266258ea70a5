"""The reference of the tiny mixture-of-experts family (``tiny_moe``).

For the harness's own plumbing test only, this reference is the program's
own forward pass (``repro.models``), in float32 under
``jax.default_matmul_precision("highest")``, on the family's weights
widened to float32: it checks that a family added as new files reaches
``check``, not the program.  A configuration the benchmark runs has a
reference written without the program.  ``control=True`` also computes the
same pass with every weight rounded to float8 (e4m3, one scale a tensor).
"""

from __future__ import annotations

import dataclasses
import importlib.util
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

F8_MAX = 448.0


def _family():
    path = Path(__file__).resolve().parents[1] / "families" / "tiny_moe.py"
    name = f"_tiny_moe_family_{abs(hash(str(path)))}"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


def _fp8(a):
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / F8_MAX
    return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def gaps(c: dict, seed: int, items, *, rows: int, length: int,
         control: bool = False) -> list[tuple[np.ndarray, np.ndarray]]:
    from repro.models.api import build_model

    fam = _family()
    cfg = dataclasses.replace(fam.program_config(c), dtype="float32",
                              param_dtype="float32")
    fwd = jax.jit(build_model(cfg).forward)
    w = fam.to_program(fam.make_weights(W.root_key(seed), fam.shapes(c)))
    w32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), w)
    w8 = jax.tree_util.tree_map(_fp8, w32) if control else None
    if len(items) > rows:
        raise ValueError(f"{len(items)} requests > {rows} reference rows")
    out = []
    for prompt, served in items:
        seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        tokens = np.zeros((1, length), np.int32)
        tokens[0, : seq.shape[0]] = seq
        p, n = len(prompt), len(served)
        with jax.default_matmul_precision("highest"):
            ref = np.asarray(fwd(w32, {"tokens": tokens}))[0, p - 1: p - 1 + n]
            low = None if w8 is None else np.asarray(
                fwd(w8, {"tokens": tokens}))[0, p - 1: p - 1 + n]
        best = ref.max(-1)
        g = best - ref[np.arange(n), np.asarray(served)]
        gc = np.zeros_like(g) if low is None else \
            best - ref[np.arange(n), low.argmax(-1)]
        out.append((g, gc))
    return out
