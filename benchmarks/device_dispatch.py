"""Device-side dispatch cost: the TPU-native half of the Fig. 3 story.

Selecting which computation runs next, three ways:

* ``switch_table``   — HAM device handler table: ONE compiled executable,
  ``lax.switch`` over N branches, key as device data (our mechanism)
* ``dict_dispatch``  — N separately-jitted executables, Python picks one
  per call (executable-swap cost, the "good vendor" case)
* ``retrace``        — re-jit the function every call (the worst case:
  what naive frameworks pay when the step function changes shape/identity)

Plus ``switch_scaling``: table dispatch cost vs table size (O(1) claim).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.device_table import DeviceHandlerTable

from benchmarks._stats import median_us


def _median_us(fn, n=300, warmup=20) -> float:
    return median_us(fn, n, warmup)


def _make_branches(k: int):
    def mk(i):
        def fn(x):
            return x * (i + 1) + i
        return fn
    return [mk(i) for i in range(k)]


def bench_switch_table(num_handlers=8, dim=1024) -> float:
    table = DeviceHandlerTable()
    for i, fn in enumerate(_make_branches(num_handlers)):
        table.register(f"h{i:03d}", fn)
    x = jnp.ones((dim,), jnp.float32)
    spec = jax.ShapeDtypeStruct(x.shape, x.dtype)
    dispatch = table.build(spec)
    keys = [jnp.asarray(i % num_handlers, jnp.int32) for i in range(num_handlers)]
    i = [0]

    def call():
        i[0] = (i[0] + 1) % num_handlers
        dispatch(keys[i[0]], x).block_until_ready()

    return _median_us(call)


def bench_dict_dispatch(num_handlers=8, dim=1024) -> float:
    fns = {i: jax.jit(fn) for i, fn in enumerate(_make_branches(num_handlers))}
    x = jnp.ones((dim,), jnp.float32)
    for f in fns.values():
        f(x).block_until_ready()
    i = [0]

    def call():
        i[0] = (i[0] + 1) % num_handlers
        fns[i[0]](x).block_until_ready()

    return _median_us(call)


def bench_retrace(dim=1024) -> float:
    x = jnp.ones((dim,), jnp.float32)
    i = [0]

    def call():
        i[0] += 1
        k = i[0]

        def fn(x):
            return x * (k % 7 + 1) + k % 3

        jax.jit(fn)(x).block_until_ready()

    return _median_us(call, n=50, warmup=2)


def bench_switch_scaling(sizes=(2, 16, 64, 256), dim=256) -> list[tuple[int, float]]:
    out = []
    for k in sizes:
        table = DeviceHandlerTable()
        for i, fn in enumerate(_make_branches(k)):
            table.register(f"h{i:04d}", fn)
        x = jnp.ones((dim,), jnp.float32)
        dispatch = table.build(jax.ShapeDtypeStruct(x.shape, x.dtype))
        key = jnp.asarray(k // 2, jnp.int32)
        us = _median_us(lambda: dispatch(key, x).block_until_ready(), n=200)
        out.append((k, us))
    return out


def run(smoke: bool = False) -> list[tuple[str, float, str]]:
    rows = []
    k = 2 if smoke else 8
    sw = bench_switch_table(num_handlers=k)
    dd = bench_dict_dispatch(num_handlers=k)
    rt = bench_retrace()
    rows.append(("dispatch/switch_table", sw, f"HAM device table, {k} branches"))
    rows.append(("dispatch/dict_jitted", dd, "executable swap per call"))
    rows.append(("dispatch/retrace", rt, "re-jit per call"))
    rows.append(("dispatch/SPEEDUP_vs_retrace", rt / sw, "ratio"))
    for k, us in bench_switch_scaling(sizes=(2, 16) if smoke else (2, 16, 64, 256)):
        rows.append((f"dispatch/switch_{k}_branches", us, "O(1) table scaling"))
    return rows


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    for name, val, note in run():
        print(f"{name},{val:.2f},{note}")
