# smelint: exact-module
"""Pallas TPU kernel v3: plane-CSC block-sparse dequant-matmul.

The unit of storage, DMA and skipping is the *(bit-plane, tile)* pair —
the TPU analogue of the paper's one-crossbar-per-bit-slice mapping
(§III-B), where squeeze-out frees whole crossbars *per plane*.  Per
occupied plane-tile the HBM payload is a **1-bit bitmap** (2 KB for a
128x128 tile = 0.125 B/weight-plane); signs travel once per weight and the
``2^row_exp`` squeeze compensation once per tile row, both indexed through
the scalar-prefetched ``rowid`` so only occupied tiles' slices are ever
fetched.

Splice epilogue (the peripheral splice circuits of paper Fig. 6 mapped to
VMEM): the per-column list is sorted by ``(row_tile, plane)``, so the
planes of one (row, col) tile arrive on consecutive grid steps.  Each step
accumulates its bitmap at the plane's integer bit value (``2^shift``) into
a VMEM weight scratch — an *exact* splice: partial sums of distinct
powers of two with <= Nq significant bits are exact in f32 — and on the
group's ``last`` entry the spliced codeword tile is signed, row-scaled and
fed to **one** MXU matmul, bit-identical to the v1 bytecode kernel's
per-tile matmul.  Accumulation order over tiles matches v1's CSC order,
so the whole kernel output is bit-identical to v1 (and therefore to v2,
whose minifloat-6 re-encoding is lossless).

Grid: ``(M_tiles, N_tiles, L)``, L = max occupied plane-tiles per column;
scaffolding shared with v1/v2 via ``csc_grid``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .csc_grid import csc_pallas_call, csc_step, resident_spec, scale_rows, \
    slot_spec, tile_spec, unpack_row_bits

__all__ = ["sme_spmm_planes"]


def _kernel(rowid_ref, shift_ref, last_ref, nnz_ref, x_ref, planes_ref,
            sign_ref, rowscale_ref, o_ref, acc_ref, wacc_ref,
            *, bk: int, bn: int):
    l = pl.program_id(2)

    @pl.when(l == 0)
    def _init_splice():
        wacc_ref[...] = jnp.zeros_like(wacc_ref)

    def accum(j, l):
        # splice this plane's bits into the codeword at its bit value;
        # 2^shift with shift in [0, Nq) and <= Nq set planes keeps every
        # partial sum exactly representable in f32
        bits = unpack_row_bits(planes_ref[0, 0], bk, bn).astype(jnp.float32)
        wacc_ref[...] += bits * jnp.exp2(shift_ref[j, l].astype(jnp.float32))

        @pl.when(last_ref[j, l] == 1)
        def _splice_matmul():
            # last plane of this (row, col) tile group: sign + squeeze
            # compensation, one MXU matmul for the whole group, reset
            sgn = 1.0 - 2.0 * unpack_row_bits(sign_ref[0, 0], bk, bn
                                              ).astype(jnp.float32)
            w = wacc_ref[...] * sgn
            # [1, bk] = 2^row_exp of tile (rowid, j), VMEM-resident
            x = scale_rows(x_ref[...],
                           rowscale_ref[rowid_ref[j, l], pl.ds(j, 1), :])
            acc_ref[...] += jax.lax.dot_general(
                x, w, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            wacc_ref[...] = jnp.zeros_like(wacc_ref)

    csc_step(nnz_ref, o_ref, acc_ref, accum)


def sme_spmm_planes(
    x: jax.Array,            # [M, K_pad]
    planes: jax.Array,       # u8 [Nt, L, bk//8, bn] bit-packed plane maps
    sign: jax.Array,         # u8 [nr, nc, bk//8, bn] dense packed signs
    rowscale: jax.Array,     # f32 [nr, nc, bk] dense 2^row_exp
    rowid: jax.Array,        # i32 [Nt, L]
    shift: jax.Array,        # i32 [Nt, L] plane bit-value exponent
    last: jax.Array,         # i32 [Nt, L] 1 = final plane of its tile group
    nnz: jax.Array,          # i32 [Nt]
    *,
    bm: int = 128,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    """Returns y [M, Nt*bn] — **unscaled**: the caller applies the dequant
    scale and the 2^-n_bits code step (folded like v1's ``n_bits=0``
    contract, so the kernel needs no value-dependent static argument)."""
    nt, L, bk8, bn = planes.shape
    bk = bk8 * 8
    kernel = functools.partial(_kernel, bk=bk, bn=bn)
    return csc_pallas_call(
        kernel, x, scalars=(rowid, shift, last, nnz),
        tensors=(planes, sign, rowscale),
        tensor_specs=[slot_spec(bk // 8, bn), tile_spec(bk // 8, bn),
                      resident_spec(rowscale.shape)],
        nt=nt, L=L, bm=bm, bk=bk, bn=bn,
        out_dtype=out_dtype, interpret=interpret,
        extra_scratch=[pltpu.VMEM((bk, bn), jnp.float32)])
