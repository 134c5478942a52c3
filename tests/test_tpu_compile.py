"""Ahead-of-time compiles for a TPU v5e: the SME Pallas kernels, and the
serving engine's decode step.

The TPU compiler is installed with JAX, so each kernel is compiled here
for a described (not attached) ``v5e:2x2`` topology, at the projection
shapes of qwen1.5-0.5b (d_model 1024, d_ff 2816), with interpret mode
off.  A compile refuses what interpret-mode numerics tests cannot see:
block shapes the TPU lowering rejects, unsupported casts or reshapes,
too much VMEM.  Each test asserts that the compiled program holds the
kernel (``tpu_custom_call``).  Only shapes are needed, so the operands
are abstract.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and every test
worker imports every test file.
"""
import dataclasses
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core import backend as B
from repro.models.model import build_model

#: (K, N) of qwen1.5-0.5b's projections: fused qkv, o, MLP in, MLP out
SHAPES = [(1024, 3072), (1024, 1024), (1024, 2816), (2816, 1024)]
#: M per path: the matmul grid at one 128-row block (prefill), the
#: decode kernel at 8 rows (a padded decode batch)
CASES = [("v1", 128), ("v2", 128), ("v3", 128), ("v3", 8)]
T = 128            # weight tile edge
LIVE_PLANES = 7    # 8-bit codes with one plane squeezed out


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # smelint: disable=EXC001 — any failure to describe the topology means "no TPU compiler here": skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _operand_shapes(backend: str, k: int, n: int):
    """Kernel operand (shape, dtype) per name for a dense K x N weight:
    every tile occupied, every live plane of every tile occupied."""
    nr, nc = k // T, n // T
    u8, i32, f32 = jnp.uint8, jnp.int32, jnp.float32
    if backend == "v1":
        L = nr
        return {"codes": ((nc, L, T, T), u8),
                "sign": ((nc, L, T // 8, T), u8),
                "rowscale": ((nc, L, T), f32),
                "rowid": ((nc, L), i32), "nnz": ((nc,), i32)}
    if backend == "v2":
        L = nr
        return {"packed": ((nc, L, 3 * T // 4, T), u8),
                "rowscale": ((nc, L, T), f32),
                "rowid": ((nc, L), i32), "nnz": ((nc,), i32)}
    L = nr * LIVE_PLANES
    return {"planes": ((nc, L, T // 8, T), u8),
            "sign": ((nr, nc, T // 8, T), u8),
            "rowscale": ((nr, nc, T), f32),
            "rowid": ((nc, L), i32), "shift": ((nc, L), i32),
            "last": ((nc, L), i32), "nnz": ((nc,), i32)}


@pytest.mark.parametrize("k,n", SHAPES, ids=[f"{k}x{n}" for k, n in SHAPES])
@pytest.mark.parametrize("backend,m", CASES,
                         ids=["v1", "v2", "v3", "v3-decode"])
def test_kernel_compiles_for_v5e(one_chip, backend, m, k, n):
    be = B.get_backend(backend)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    ops = {name: spec(*sd)
           for name, sd in _operand_shapes(backend, k, n).items()}
    param = {"sme_scale": spec((1, n), jnp.float32),
             "sme_sign": spec((k, n // 8), jnp.uint8),
             "sme_nbits": spec((), jnp.int32),
             "sme_squeezed": spec((), jnp.int32)}
    x = spec((m, k), jnp.bfloat16)

    def matmul(x, ops, param):
        return be.matmul2d(x, ops, param, bm=128, interpret=False)

    compiled = jax.jit(matmul).lower(x, ops, param).compile()
    assert "tpu_custom_call" in compiled.as_text()
    if backend == "v3":
        # M picks the path: the decode kernel has its own grid
        assert B._use_decode_kernel(m, 128) == (m == 8)


def test_decode_step_moves_no_cache_layer(one_chip):
    """The engine's step program (``decode_chunk``, one position per row)
    at qwen1.5-0.5b widths with 2 layers, 16 slots and ``s_max`` 1024,
    its cache donated in the device's default layout: the compiled
    program holds no ``copy`` as large as one layer's K.  A layer loop
    that slices each layer's cache out and stacks a new one, or a row
    write that wants the cache in another layout than the program's
    boundary has, copies whole layers every step."""
    cfg = dataclasses.replace(get_config("qwen1.5-0.5b"), n_layers=2)
    api = build_model(cfg)
    slots, s_max, i32 = 16, 1024, jnp.int32

    def spec(leaf):
        return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                    sharding=one_chip)

    def step(p, tokens, caches, pos, nvalid, active):
        logits, live, caches = api.decode_chunk(p, tokens, caches, pos,
                                                nvalid, active)
        return jnp.argmax(logits, axis=-1), live, caches

    row = jax.ShapeDtypeStruct((slots,), i32)
    compiled = jax.jit(step, donate_argnums=(2,)).lower(
        jax.tree.map(spec, api.abstract_params()),
        spec(jax.ShapeDtypeStruct((slots, 1), i32)),
        jax.tree.map(spec, api.abstract_cache(batch=slots, s_max=s_max)),
        spec(row), spec(row),
        spec(jax.ShapeDtypeStruct((slots,), jnp.bool_))).compile()

    layer_k = slots * s_max * cfg.n_kv_heads * cfg.hd
    copies = re.findall(r"= \w+\[([\d,]*)\]\{[^}]*\} copy\(",
                        compiled.as_text())
    big = [dims for dims in copies
           if math.prod(int(d) for d in dims.split(",") if d) >= layer_k]
    assert not big, f"copies of a cache layer or more: {big}"
