# smelint: exact-module
"""Pallas TPU kernel: SME packed block-sparse dequant-matmul.

Computes ``y[M, N] = x[M, K] @ W_eff`` where ``W_eff`` is an SME-compressed
weight matrix stored as CSC-of-128x128-tiles (see
``core.sme.SMEWeight.pack_csc``):

  * occupied tiles hold uint8 *shifted codewords* (1 byte/weight from HBM
    instead of 2-4 for bf16/f32 — the TPU analogue of the paper's crossbar
    savings, DESIGN.md §2);
  * dequantization (codes -> f32, sign bits) happens **in VMEM on the
    VPU**, with the ``2^row_exp`` squeeze-out compensation applied to the
    input block, so the MXU sees one dense f32 matmul per tile;
  * empty tiles are never stored; a scalar-prefetch CSC index
    (``rowid``/``nnz``) drives the BlockSpec index maps (megablocks-style)
    so padding slots are skipped with ``pl.when``.

Grid: ``(M_tiles, N_tiles, L)`` with L innermost — each output block stays
resident in a VMEM f32 scratch accumulator across its column's tile list
and is flushed once.  The grid/init/accum/flush scaffolding is shared with
the v2/v3 kernels (``csc_grid``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .csc_grid import csc_pallas_call, csc_step, column_spec, scale_rows, \
    slot_spec, unpack_row_bits

__all__ = ["sme_spmm"]


def _kernel(rowid_ref, nnz_ref, x_ref, codes_ref, sign_ref, rowscale_ref,
            o_ref, acc_ref, *, n_bits: int, bk: int, bn: int):
    def accum(j, l):
        codes = codes_ref[0, 0]                              # [bk, bn] u8
        mag = codes.astype(jnp.int32).astype(jnp.float32) * (2.0 ** -n_bits)
        # sign bits packed along rows, MSB-first (np.packbits axis=0)
        bits = unpack_row_bits(sign_ref[0, 0], bk, bn)
        sgn = 1.0 - 2.0 * bits.astype(jnp.float32)
        w = mag * sgn
        # [1, bk] f32 = 2^row_exp of this slot's tile rows
        x = scale_rows(x_ref[...], rowscale_ref[0, pl.ds(l, 1), :])
        acc_ref[...] += jax.lax.dot_general(
            x, w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    csc_step(nnz_ref, o_ref, acc_ref, accum)


def sme_spmm(
    x: jax.Array,            # [M, K_pad] (K padded to row-tile multiple)
    codes: jax.Array,        # u8 [Nt, L, bk, bn]
    sign: jax.Array,         # u8 [Nt, L, bk//8, bn]
    rowscale: jax.Array,     # f32 [Nt, L, bk]
    rowid: jax.Array,        # i32 [Nt, L]
    nnz: jax.Array,          # i32 [Nt]
    *,
    n_bits: int,
    bm: int = 128,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    """Returns y [M, Nt*bn].  M must be a multiple of ``bm``."""
    nt, L, bk, bn = codes.shape
    kernel = functools.partial(_kernel, n_bits=n_bits, bk=bk, bn=bn)
    return csc_pallas_call(
        kernel, x, scalars=(rowid, nnz),
        tensors=(codes, sign, rowscale),
        tensor_specs=[slot_spec(bk, bn), slot_spec(bk // 8, bn),
                      column_spec(L, bk)],
        nt=nt, L=L, bm=bm, bk=bk, bn=bn,
        out_dtype=out_dtype, interpret=interpret)
