"""Compile for a described TPU v5e, no chip attached.

The serve executables at qwen1.5-4b's published widths and full depth, and
every Pallas kernel at a realistic shape, go through the TPU compiler here:
what it would refuse on the chip (a block that breaks the (8, 128) tiling,
a program that does not fit the chip's memory) fails here first.  Nothing
runs, so nothing here is a time.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

#: one v5e chip's HBM as the TPU compiler counts it
V5E_HBM_BYTES = 15.75 * 2**30


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A sharding on one described chip, with JAX's persistent compilation
    cache off: an executable for a described chip could be written to it
    but never read back without the chip."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


@pytest.fixture(scope="module")
def serve_shapes(one_chip):
    """The chip smoke's program: qwen1.5-4b, bf16 weights, 4 slots x 512."""
    from repro.configs import get_config
    from repro.models.api import build_model
    from repro.serve.engine import ServeProgram

    cfg = dataclasses.replace(get_config("qwen1.5-4b"),
                              param_dtype="bfloat16")
    model = build_model(cfg)
    params = _on(one_chip, jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    program = ServeProgram(model, params, num_slots=4, max_len=512)
    return program, params, _on(one_chip, program.payload_spec)


def _fits(compiled) -> int:
    m = compiled.memory_analysis()
    need = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert need < V5E_HBM_BYTES, f"{need / 2**30:.2f} GiB exceeds one v5e"
    return need


def test_decode_step_compiles_and_fits(serve_shapes, one_chip):
    program, params, payload = serve_shapes
    key = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    _fits(program.dispatch.lower(key, payload, params).compile())


def test_fused_decode_block_compiles_and_fits(serve_shapes):
    program, params, payload = serve_shapes
    _fits(program.multi(16).lower(payload, params).compile())


def test_fused_admission_compiles_and_fits(serve_shapes, one_chip):
    program, params, payload = serve_shapes
    prompt = jax.ShapeDtypeStruct((1, 128), jnp.int32, sharding=one_chip)
    slot = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    _fits(program.admit.lower(params, payload["cache"], payload["tokens"],
                              payload["pos"], prompt, slot).compile())


#: ``temp_size_in_bytes`` of ``multi(16)`` at these shapes while the decode
#: step still copied each layer's K/V slab out of the stacked cache and
#: back; writing the rows in place must need no more
SLAB_COPY_BLOCK_TEMP_BYTES = {"mha": 1007270400, "gqa": 202319360}


def _top_level(hlo: str):
    """``(name, result dims)`` of every array-valued instruction of a
    compiled module that lies outside the bodies of fused computations."""
    bodies: dict[str, list[str]] = {}
    fused: set[str] = set()
    name = None
    for line in hlo.splitlines():
        if not line.startswith(" "):  # a computation opens or closes
            m = re.match(r"(?:ENTRY )?%([\w.\-]+) ", line)
            name = m.group(1) if m and line.endswith("{") else None
            if name:
                bodies[name] = []
        elif name:
            bodies[name].append(line)
            if " fusion(" in line:
                fused.update(re.findall(r"calls=%([\w.\-]+)", line))
    for comp, lines in bodies.items():
        if comp in fused:
            continue
        for line in lines:
            m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = \w+\[([\d,]*)\]", line)
            if m:
                yield m.group(1), tuple(int(d) for d in m.group(2).split(",")
                                        if d)


@pytest.mark.parametrize("attn", ["mha", "gqa"])
def test_decode_block_moves_no_kv_slab(attn, serve_shapes, one_chip):
    """The fused decode block writes each token's K/V rows into the stacked
    cache in place: no dynamic-slice or dynamic-update-slice instruction
    or fusion at the top level of the block's computations yields a
    layer's K/V slab ``(B, S, hk, hd)`` or the stacked cache ``(L, B, S,
    hk, hd)``, as the copy out of the stack and the write back did, and the
    block needs no more temporaries than it did with those copies.

    MHA is the chip smoke's qwen1.5-4b; GQA is internlm2-20b at 2 of its 48
    layers (same widths, a shorter compile).  A GQA layer's score dot reads
    its slab through a ``(1, B, S, hk, hd)`` dynamic slice staged in on-chip
    memory: the one read of the layer's cache that attention needs, which
    the MHA scores fuse instead."""
    if attn == "mha":
        program, params, payload = serve_shapes
        cfg = program.model.cfg
    else:
        from repro.configs import get_config
        from repro.models.api import build_model
        from repro.serve.engine import ServeProgram

        cfg = dataclasses.replace(get_config("internlm2-20b"), num_layers=2,
                                  param_dtype="bfloat16")
        model = build_model(cfg)
        params = _on(one_chip,
                     jax.eval_shape(model.init, jax.random.PRNGKey(0)))
        program = ServeProgram(model, params, num_slots=4, max_len=512)
        payload = _on(one_chip, program.payload_spec)
    slab = (program.B, program.max_len, cfg.num_kv_heads,
            cfg.resolved_head_dim)
    compiled = program.multi(16).lower(payload, params).compile()
    copies = [(n, d) for n, d in _top_level(compiled.as_text())
              if "dynamic" in n and d in (slab, (cfg.num_layers,) + slab)]
    assert not copies, f"the decode block copies KV slabs: {copies}"
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= SLAB_COPY_BLOCK_TEMP_BYTES[attn], temp


def _kernel_cases():
    """name -> (kernel, argument shapes, static keywords): one realistic
    shape per kernel, from the configuration that would use it."""
    from repro.kernels.decode_attention import decode_attention
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.grouped_matmul import grouped_matmul
    from repro.kernels.mamba2_ssd import ssd_chunked_kernel
    from repro.kernels.mlstm import mlstm_chunked_kernel

    bf16, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    return {
        # qwen1.5-4b prefill of 2048 tokens: 20 heads of 128
        "flash_attention": (flash_attention, [
            ((20, 2048, 128), bf16)] * 3, {"causal": True}),
        # qwen1.5-4b decode: 4 slots x 20 kv heads x 512 positions
        "decode_attention": (decode_attention, [
            ((4, 20, 1, 128), bf16), ((4, 20, 512, 128), bf16),
            ((4, 20, 512, 128), bf16), ((4,), i32)], {}),
        # olmoe-1b-7b experts: 64 x (2048 -> 1024), 128 tokens each
        "grouped_matmul": (grouped_matmul, [
            ((64, 128, 2048), bf16), ((64, 2048, 1024), bf16)], {}),
        # zamba2-2.7b mamba2: 80 heads of 64, state 64, 2048 tokens
        "mamba2_ssd": (ssd_chunked_kernel, [
            ((80, 2048, 64), bf16), ((80, 2048), f32), ((80, 2048), f32),
            ((80, 2048, 64), bf16), ((80, 2048, 64), bf16),
            ((80, 64, 64), f32)], {"chunk": 256}),
        # xlstm-1.3b mLSTM: 4 heads, qk 512, v 1024, 2048 tokens
        "mlstm": (mlstm_chunked_kernel, [
            ((4, 2048, 512), bf16), ((4, 2048, 512), bf16),
            ((4, 2048, 1024), bf16), ((4, 2048), f32), ((4, 2048), f32)],
            {"chunk": 256}),
    }


@pytest.mark.parametrize("name", ["flash_attention", "decode_attention",
                                  "grouped_matmul", "mamba2_ssd", "mlstm"])
def test_pallas_kernel_compiles(name, one_chip):
    kernel, shapes, static = _kernel_cases()[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = kernel.lower(*args, **static).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the kernel, not XLA
