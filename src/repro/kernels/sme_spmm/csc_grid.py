# smelint: exact-module
"""Shared scaffolding for the CSC-of-tiles Pallas kernels (DESIGN.md §2).

All three SME kernels (``sme_spmm`` v1 bytecode, ``sme_spmm6`` v2
minifloat-6, ``sme_spmm_planes`` v3 plane-CSC) walk the same grid:
``(M_tiles, N_tiles, L)`` with the per-column occupied-unit list ``L``
innermost, scalar-prefetched ``rowid``/``nnz`` index arrays driving the
BlockSpec index maps, and one VMEM f32 accumulator per output block that
is initialized at ``l == 0`` and flushed at ``l == L - 1``.  This module
holds that skeleton once:

  * :func:`csc_step` — the init / guarded-accumulate / flush kernel body
    scaffolding (``pl.when`` structure);
  * spec builders (:func:`x_spec`, :func:`slot_spec`, :func:`tile_spec`,
    :func:`column_spec`, :func:`resident_spec`, :func:`out_spec`) —
    index-map lambdas written against ``*scalars`` so they work for any
    number of scalar-prefetch arguments, with ``scalars[0]`` always the
    ``rowid`` array;
  * :func:`scale_rows` — the ``2^row_exp`` squeeze compensation applied
    to the input block (one lane vector per list slot);
  * :func:`csc_pallas_call` — grid-spec assembly + ``pl.pallas_call``;
  * :func:`unpack_row_bits` — the row-major bitmap decode shared by the
    v1 sign bitmap and the v3 plane bitmaps (``np.packbits(axis=rows)``
    layout, MSB-first).
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["csc_step", "x_spec", "slot_spec", "tile_spec", "column_spec",
           "resident_spec", "out_spec", "csc_pallas_call", "unpack_row_bits",
           "scale_rows"]


def csc_step(nnz_ref, o_ref, acc_ref, accum) -> None:
    """Run one grid step of a CSC kernel: zero the accumulator on the
    first list slot, call ``accum(j, l)`` on real (non-padding) slots, and
    flush the accumulator to the output block on the last slot.

    ``accum`` is traced inside ``pl.when(l < nnz[j])`` — padding slots are
    skipped entirely (their DMAs point at slot 0 of the operand arrays,
    a no-op by construction).
    """
    j = pl.program_id(1)
    l = pl.program_id(2)

    @pl.when(l == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(l < nnz_ref[j])
    def _accum():
        accum(j, l)

    @pl.when(l == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def x_spec(bm: int, bk: int) -> pl.BlockSpec:
    """Input block [bm, bk] at the row tile the current list entry names —
    ``scalars[0]`` is the prefetched ``rowid`` array by convention."""
    return pl.BlockSpec((bm, bk),
                        lambda mi, j, l, *scalars: (mi, scalars[0][j, l]))


def slot_spec(*block: int) -> pl.BlockSpec:
    """Per-list-slot operand [Nt, L, *block]: one block per (j, l)."""
    pad = (0,) * len(block)
    return pl.BlockSpec((1, 1) + tuple(block),
                        lambda mi, j, l, *scalars, _p=pad: (j, l) + _p)


def tile_spec(*block: int) -> pl.BlockSpec:
    """Dense per-(row, col)-tile operand [nr, nc, *block], indexed through
    the prefetched ``rowid`` — consecutive list entries of one tile group
    map to the same block, so Pallas re-uses the buffer without re-DMA."""
    pad = (0,) * len(block)
    return pl.BlockSpec((1, 1) + tuple(block),
                        lambda mi, j, l, *scalars, _p=pad:
                        (scalars[0][j, l], j) + _p)


def column_spec(L: int, *block: int) -> pl.BlockSpec:
    """Per-column operand [Nt, L, *block]: the whole list of column ``j``
    in one block.  Used for the f32 ``rowscale`` rows, whose ``(1, bk)``
    per-slot block the TPU lowering refuses (a second-minor block dim
    must be a multiple of 8 or the whole axis); the block only changes
    with ``j``, so it is fetched once per output column."""
    pad = (0,) * len(block)
    return pl.BlockSpec((1, L) + tuple(block),
                        lambda mi, j, l, *scalars, _p=pad: (j, 0) + _p)


def resident_spec(shape: Sequence[int]) -> pl.BlockSpec:
    """Whole-array operand that stays in VMEM for the entire grid (one
    fetch) — the dense per-tile ``rowscale`` of the plane-CSC kernels,
    whose ``[nr, nc, bk]`` layout has no legal per-tile block."""
    zeros = (0,) * len(shape)
    return pl.BlockSpec(tuple(shape), lambda *_, _z=zeros: _z)


def out_spec(bm: int, bn: int) -> pl.BlockSpec:
    return pl.BlockSpec((bm, bn), lambda mi, j, l, *scalars: (mi, j))


def csc_pallas_call(kernel, x: jax.Array, scalars: Sequence[jax.Array],
                    tensors: Sequence[jax.Array],
                    tensor_specs: Sequence[pl.BlockSpec], *,
                    nt: int, L: int, bm: int, bk: int, bn: int,
                    out_dtype, interpret: bool,
                    extra_scratch: Sequence = ()) -> jax.Array:
    """Assemble the (M_tiles, Nt, L) grid and run ``kernel``.

    ``scalars`` ride the scalar-prefetch path (``scalars[0]`` must be the
    ``rowid`` array — :func:`x_spec`/:func:`tile_spec` index through it);
    ``tensors``/``tensor_specs`` are the per-kernel payload operands.  The
    f32 [bm, bn] accumulator scratch is always allocated first, followed
    by any ``extra_scratch``.  Returns y [M, Nt * bn].
    """
    m, k_pad = x.shape
    if m % bm:
        raise ValueError(f"M={m} not a multiple of bm={bm}")
    if k_pad % bk:
        raise ValueError(f"K_pad={k_pad} not a multiple of bk={bk}")
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(m // bm, nt, L),
        in_specs=[x_spec(bm, bk)] + list(tensor_specs),
        out_specs=out_spec(bm, bn),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)]
        + list(extra_scratch),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, nt * bn), out_dtype),
        interpret=interpret,
    )(*scalars, x, *tensors)


def unpack_row_bits(packed, bk: int, bn: int):
    """u8 [bk//8, bn] row-packed bitmap (np.packbits along rows, MSB
    first) -> i32 0/1 bits [bk, bn].  Shared by the v1 sign bitmap and the
    v3 plane bitmaps.  The bit arithmetic runs on i32: each [8, bn] slab
    of the expansion is one 32-bit vreg tile, so the final reshape is
    layout-trivial on the TPU."""
    p = packed.astype(jnp.int32)
    shifts = 7 - jax.lax.broadcasted_iota(jnp.int32, (1, 8, 1), 1)
    return ((p[:, None, :] >> shifts) & 1).reshape(bk, bn)


def scale_rows(x, rowscale):
    """``x [bm, bk] * rowscale [1, bk]`` in f32: the ``2^row_exp`` squeeze
    compensation of one weight tile's rows, folded into the input block
    instead of the weight (a lane vector broadcast over sublanes, which
    the TPU lowers directly).  ``rowscale`` holds exact powers of two, so
    ``(x * 2^e) @ w == x @ (2^e * w)`` term by term."""
    return x.astype(jnp.float32) * rowscale
