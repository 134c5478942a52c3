# smelint: exact-module
"""Pallas TPU kernel v3-decode: GEMV-shaped plane-CSC dequant-matmul.

Decode is the serving hot path — activations are ``[B, 1]`` reshaped to a
single short ``[M, K]`` row block with ``M <= bm`` — and it is HBM-bound:
the whole weight streams per token while the MXU sits mostly idle.  The
matmul-shaped ``sme_spmm_planes`` grid ``(M_tiles, Nt, L)`` is the wrong
shape for it twice over: the M loop degenerates to one padded 128-row
tile, and one grid step per *(plane, tile)* list slot pays a grid-step
round trip per 1-bit bitmap even though the MXU work only happens on the
group's ``last`` slot.

This variant re-shapes the grid to ``(Nt, G)`` over *tile groups* — all
planes of one (row, col) tile are spliced inside a single grid step:

  * the plane bitmaps stay in HBM (``pltpu.MemorySpace.ANY``) and are
    streamed by a
    manually double-buffered ``make_async_copy`` loop (2-slot VMEM buffer
    + DMA semaphore pair), so splicing plane ``i`` overlaps the fetch of
    plane ``i + 1``;
  * the scalar-prefetched group index (``g_rowid``/``g_start``/
    ``g_count``/``g_nnz``, derived from the v3 ``rowid``/``last``/``nnz``
    operands by :func:`plane_group_index` — the packed format does not
    change) drives the x/sign/rowscale BlockSpecs, so only occupied
    tiles' slices are ever fetched;
  * the epilogue is fused: the flush multiplies by a per-column
    ``colscale = scale * 2^-n_bits`` operand, so the caller-side rescale
    of the matmul path disappears.  ``2^-n_bits`` is an exact power of
    two and scaling by an exact power of two commutes with f32 rounding,
    so ``acc * (scale * qscale)`` is bit-identical to the matmul path's
    external ``(acc * scale) * qscale``.

Accumulation order over tiles and planes matches ``sme_spmm_planes`` —
groups walk the same (col, row, plane)-sorted CSC list — so the output
is bit-identical to v3 and therefore to v1/v2 (DESIGN.md §8).

**Truncated-plane drafts** (``plane_depth``, DESIGN.md §11).  The plane
list of one tile group is sorted by ascending plane index ``q``, and the
splice value of plane ``q`` is ``2^(Nq-1-q)`` — so a group's entries run
most-significant-first and a *prefix* of the group is exactly the top-k
most significant occupied planes of that tile.  Clamping ``g_count`` to
``plane_depth`` therefore dispatches the same kernel over a truncated
operand view — fewer splice iterations, fewer HBM bitmap DMAs, no
repack — computing the top-``plane_depth``-planes dequant of every tile
(the self-speculative *draft* pass).  ``plane_depth`` may be a traced
scalar: the clamp is a host-level ``jnp.minimum`` on the group index,
outside the Pallas grid.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .csc_grid import resident_spec, scale_rows, unpack_row_bits

__all__ = ["sme_spmm_planes_decode", "plane_group_index"]


def plane_group_index(rowid: jax.Array, last: jax.Array, nnz: jax.Array,
                      G: int) -> Tuple[jax.Array, jax.Array, jax.Array,
                                       jax.Array]:
    """Tile-group view of a v3 plane-CSC list (jit-safe, static ``G``).

    The plane list of column ``j`` is sorted by (row_tile, plane), so a
    *group* — the planes of one (row, col) tile — is a maximal run that
    ends at a ``last == 1`` slot.  Returns ``(g_rowid, g_start, g_count)``
    each ``i32 [Nt, G]`` plus ``g_nnz i32 [Nt]`` (groups per column).

    Scatters use order-independent combiners only (``min``/``add``/
    ``max`` with ``mode="drop"``) so the derivation is deterministic
    under jit; padding slots map to group index ``G`` and drop out.
    """
    nt, L = rowid.shape
    iota = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32)[None, :], (nt, L))
    valid = iota < nnz[:, None]
    prev_last = jnp.concatenate(
        [jnp.ones((nt, 1), last.dtype), last[:, :-1]], axis=1)
    is_start = (prev_last == 1) & valid
    gidx = jnp.where(valid, jnp.cumsum(is_start, axis=1) - 1, G)
    rows = jnp.broadcast_to(jnp.arange(nt, dtype=jnp.int32)[:, None], (nt, L))
    g_start = jnp.full((nt, G), L, jnp.int32).at[rows, gidx].min(
        iota, mode="drop")
    g_start = jnp.where(g_start == L, 0, g_start)   # unused-slot padding
    g_count = jnp.zeros((nt, G), jnp.int32).at[rows, gidx].add(
        valid.astype(jnp.int32), mode="drop")
    g_rowid = jnp.zeros((nt, G), jnp.int32).at[rows, gidx].max(
        jnp.where(valid, rowid, 0), mode="drop")
    g_nnz = is_start.sum(axis=1).astype(jnp.int32)
    return g_rowid, g_start, g_count, g_nnz


def _kernel(g_rowid_ref, g_start_ref, g_count_ref, g_nnz_ref, shift_ref,
            x_ref, planes_hbm, sign_ref, rowscale_ref, colscale_ref,
            o_ref, acc_ref, wacc_ref, pbuf, sem, *, bk: int, bn: int):
    j = pl.program_id(0)
    g = pl.program_id(1)

    @pl.when(g == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(g < g_nnz_ref[j])
    def _group():
        start = g_start_ref[j, g]
        count = g_count_ref[j, g]

        def dma(i, slot):
            # plane bitmaps never leave HBM as a block operand: each
            # occupied slot's 1-bit map is pulled on demand into one of
            # two VMEM slots so the splice of plane i overlaps the fetch
            # of plane i + 1
            return pltpu.make_async_copy(
                planes_hbm.at[j, start + i], pbuf.at[slot], sem.at[slot])

        dma(0, 0).start()
        wacc_ref[...] = jnp.zeros_like(wacc_ref)

        def splice(i, carry):
            slot = jax.lax.rem(i, 2)

            @pl.when(i + 1 < count)
            def _prefetch():
                dma(i + 1, jax.lax.rem(i + 1, 2)).start()

            dma(i, slot).wait()
            # same exact-splice argument as sme_spmm_planes: partial sums
            # of distinct powers of two stay exact in f32
            bits = unpack_row_bits(pbuf[slot], bk, bn).astype(jnp.float32)
            wacc_ref[...] += bits * jnp.exp2(
                shift_ref[j, start + i].astype(jnp.float32))
            return carry

        jax.lax.fori_loop(0, count, splice, 0)

        sgn = 1.0 - 2.0 * unpack_row_bits(sign_ref[0, 0], bk, bn
                                          ).astype(jnp.float32)
        w = wacc_ref[...] * sgn
        # [1, bk] = 2^row_exp of tile (g_rowid, j), VMEM-resident
        x = scale_rows(x_ref[...],
                       rowscale_ref[g_rowid_ref[j, g], pl.ds(j, 1), :])
        acc_ref[...] += jax.lax.dot_general(
            x, w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(g == pl.num_programs(1) - 1)
    def _flush():
        # fused epilogue: colscale = scale * 2^-n_bits per output column;
        # exact-pow2 scaling commutes with rounding, so this equals the
        # matmul path's caller-side (y * scale) * qscale bitwise
        o_ref[...] = (acc_ref[...] * colscale_ref[0]).astype(o_ref.dtype)


def sme_spmm_planes_decode(
    x: jax.Array,            # [M, K_pad], M small (decode rows), mult of 8
    planes: jax.Array,       # u8 [Nt, L, bk//8, bn] bit-packed plane maps
    sign: jax.Array,         # u8 [nr, nc, bk//8, bn] dense packed signs
    rowscale: jax.Array,     # f32 [nr, nc, bk] dense 2^row_exp
    colscale: jax.Array,     # f32 [Nt, 1, bn] dequant scale * 2^-n_bits
    rowid: jax.Array,        # i32 [Nt, L]
    shift: jax.Array,        # i32 [Nt, L] plane bit-value exponent
    last: jax.Array,         # i32 [Nt, L] 1 = final plane of its tile group
    nnz: jax.Array,          # i32 [Nt]
    *,
    G: int | None = None,
    plane_depth=None,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    """Returns y [M, Nt*bn] — fully scaled (unlike ``sme_spmm_planes``,
    whose caller applies scale/qscale after the kernel): the ``colscale``
    operand carries ``scale * 2^-n_bits`` into the flush.

    ``G`` is the static tile-group grid bound (max groups per column);
    defaults to ``L``, always safe — a tighter bound from concrete
    operands just trims padded grid steps.

    ``plane_depth`` (``None`` = full precision; int or traced i32 scalar)
    truncates every tile group to its first ``plane_depth`` entries — the
    top-k most significant occupied planes, since groups are sorted
    MSB-first (module docstring).  Any value >= the deepest group is an
    exact no-op (bit-identical to ``plane_depth=None``).
    """
    nt, L, bk8, bn = planes.shape
    bk = bk8 * 8
    m, k_pad = x.shape
    if m % 8:
        raise ValueError(f"M={m} not a multiple of 8 (pad decode rows)")
    if k_pad % bk:
        raise ValueError(f"K_pad={k_pad} not a multiple of bk={bk}")
    G = L if G is None else max(min(int(G), L), 1)
    g_rowid, g_start, g_count, g_nnz = plane_group_index(rowid, last, nnz, G)
    if plane_depth is not None:
        # the truncated draft: each group splices only its plane_depth
        # most significant occupied planes (a prefix of the same list —
        # identical operands, fewer DMA'd bitmaps)
        g_count = jnp.minimum(
            g_count, jnp.maximum(jnp.asarray(plane_depth, jnp.int32), 1))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(nt, G),
        in_specs=[
            pl.BlockSpec((m, bk), lambda j, g, *s: (0, s[0][j, g])),
            # planes stay in HBM
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
            pl.BlockSpec((1, 1, bk // 8, bn),
                         lambda j, g, *s: (s[0][j, g], j, 0, 0)),
            resident_spec(rowscale.shape),
            pl.BlockSpec((1, 1, bn), lambda j, g, *s: (j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((m, bn), lambda j, g, *s: (0, j)),
        scratch_shapes=[
            pltpu.VMEM((m, bn), jnp.float32),            # output accumulator
            pltpu.VMEM((bk, bn), jnp.float32),           # splice scratch
            pltpu.VMEM((2, bk // 8, bn), jnp.uint8),     # double buffer
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, bk=bk, bn=bn),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, nt * bn), out_dtype),
        interpret=interpret,
    )(g_rowid, g_start, g_count, g_nnz, shift,
      x, planes, sign, rowscale, colscale)
