# smelint: exact-module
"""Pallas TPU kernel v2: minifloat-6 block-sparse dequant-matmul.

Same CSC-of-tiles structure as ``sme_spmm`` (v1) but the weight payload is
the 6-bit minifloat re-encoding of squeezed SME codes (sign+exp+mant packed
4-codes-per-3-bytes): HBM moves **0.75 B/weight** instead of v1's
1 B codes + sign bitmap (~1.13 B) or bf16's 2 B.  Decode runs on the VPU:

    c   = unpack6(bytes)      # 3 [bk/4, bn] byte rows -> 4 row quarters
    w   = (e>0) * sign * (4+m) * 2^-(e+squeezed+2)

followed by one MXU matmul per tile, with the ``2^row_exp`` compensation
applied to the input block.  A tile is stored row-blocked
(``core.minifloat.pack6``): every byte row is a whole lane vector,
so the unpack is aligned sublane slices and shifts.  Grid scaffolding
shared via ``csc_grid``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .csc_grid import csc_pallas_call, csc_step, column_spec, scale_rows, \
    slot_spec

__all__ = ["sme_spmm6"]


def _kernel(rowid_ref, nnz_ref, x_ref, packed_ref, rowscale_ref,
            o_ref, acc_ref, *, squeezed: int, bk: int, bn: int):
    def accum(j, l):
        t = packed_ref[0, 0].astype(jnp.int32)         # [3*bk/4, bn]
        q = bk // 4
        b0, b1, b2 = t[:q], t[q:2 * q], t[2 * q:]
        c0 = b0 & 63
        c1 = ((b0 >> 6) | (b1 << 2)) & 63
        c2 = ((b1 >> 4) | (b2 << 4)) & 63
        c3 = (b2 >> 2) & 63
        c = jnp.concatenate([c0, c1, c2, c3], axis=0)  # [bk, bn]
        m = (c & 3).astype(jnp.float32)
        e = ((c >> 2) & 7).astype(jnp.float32)
        s = 1.0 - 2.0 * ((c >> 5) & 1).astype(jnp.float32)
        mag = (4.0 + m) * jnp.exp2(-(e + (squeezed + 2.0)))
        w = jnp.where(e > 0, s * mag, 0.0)
        # [1, bk] = 2^row_exp of this slot's tile rows
        x = scale_rows(x_ref[...], rowscale_ref[0, pl.ds(l, 1), :])
        acc_ref[...] += jax.lax.dot_general(
            x, w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    csc_step(nnz_ref, o_ref, acc_ref, accum)


def sme_spmm6(
    x: jax.Array,            # [M, K_pad]
    packed: jax.Array,       # u8 [Nt, L, 3*bk/4, bn] (pack6 tiles)
    rowscale: jax.Array,     # f32 [Nt, L, bk]
    rowid: jax.Array,        # i32 [Nt, L]
    nnz: jax.Array,          # i32 [Nt]
    *,
    squeezed: int,
    bm: int = 128,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    nt, L, bk3, bn = packed.shape
    bk = bk3 * 4 // 3
    kernel = functools.partial(_kernel, squeezed=squeezed, bk=bk, bn=bn)
    return csc_pallas_call(
        kernel, x, scalars=(rowid, nnz),
        tensors=(packed, rowscale),
        tensor_specs=[slot_spec(bk3, bn), column_spec(L, bk)],
        nt=nt, L=L, bm=bm, bk=bk, bn=bn,
        out_dtype=out_dtype, interpret=interpret)
