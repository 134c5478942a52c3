# smelint: exact-module
"""Minifloat-6 re-encoding of squeezed SME codes (kernel v2, §Perf C).

The S-window property means a squeezed SME codeword has at most S
significant bits anchored at its leading one — i.e. it IS a tiny float.
With the default pipeline (Nq=8, S<=3, squeeze x>=1) the re-encoding

    code6 = sign(1b) | exponent(3b) | mantissa(2b)

is **lossless**: live leading-bit positions span x+1..8 (<=7 values, fits
3 bits with 0 reserved for zero), and the window leaves <=2 bits below the
implicit leading one.  Four codes pack into 3 bytes -> exactly 6 bits per
weight *including the sign* (vs 9.06 bits for the v1 bytecode format and
16 for bf16).

This is the TPU-native endpoint of the paper's squeeze-out idea: squeezing
bits shrinks the exponent range until the whole weight fits a byte-packed
minifloat.
"""
from __future__ import annotations

from typing import Tuple

import numpy as np

from .sme import SMEWeight

__all__ = ["encode6", "decode6_value", "pack6", "unpack6",
           "minifloat_from_sme", "minifloat_dequant", "bits_per_weight6"]


def encode6(codes: np.ndarray, signs_neg: np.ndarray, n_bits: int = 8,
            squeezed: int = 1) -> np.ndarray:
    """codes: uint8 shifted codewords (top ``squeezed`` bits zero);
    signs_neg: 0/1 (1 = negative). Returns uint8 6-bit codes (top 2 bits 0).

    Requires live leading positions to span <= 7 values (n_bits - squeezed
    <= 7) and window <= 3 (mantissa 2 bits) — asserted by the caller via
    lossless round-trip tests.
    """
    c = codes.astype(np.int64)
    nz = c > 0
    lead_pow = np.zeros_like(c)
    lead_pow[nz] = np.floor(np.log2(c[nz])).astype(np.int64)
    # leading position p (1-indexed from MSB): byte bit (n_bits-p) == lead_pow
    p = n_bits - lead_pow                      # in [squeezed+1 .. n_bits]
    e = np.where(nz, p - squeezed, 0)          # 1..(n_bits - squeezed); 0=zero
    # mantissa: the two bits below the leading one
    cshift = (c << (p - 1)) & ((1 << n_bits) - 1)
    m = (cshift >> (n_bits - 3)) & 3
    code6 = (signs_neg.astype(np.int64) << 5) | (e << 2) | np.where(nz, m, 0)
    return code6.astype(np.uint8)


def decode6_value(code6: np.ndarray, n_bits: int = 8,
                  squeezed: int = 1) -> np.ndarray:
    """Signed magnitude in the value domain (pre row-exp, pre scale)."""
    c = code6.astype(np.int64)
    m = c & 3
    e = (c >> 2) & 7
    s = 1.0 - 2.0 * ((c >> 5) & 1)
    p = e + squeezed                           # leading-bit position
    mag = (4.0 + m) * np.exp2(-(p + 2.0))
    return np.where(e > 0, s * mag, 0.0)


def pack6(code6: np.ndarray) -> np.ndarray:
    """[..., R, C] uint8 6-bit codes -> [..., 3R/4, C] bytes (R % 4 == 0).

    Row-blocked: the four row quarters ``c0..c3`` of a tile share one
    byte triple per column (``c0 | c1 << 6``, ``c1 >> 2 | c2 << 4``,
    ``c2 >> 4 | c3 << 2``) — so the v2 kernel unpacks with aligned
    sublane slices and no lane shuffles (the TPU lowering has no
    lane-splitting reshape)."""
    r = code6.shape[-2]
    if r % 4:
        raise ValueError(f"pack6 needs a row count divisible by 4, got {r}")
    g = code6.astype(np.uint16).reshape(
        code6.shape[:-2] + (4, r // 4) + code6.shape[-1:])
    c0, c1, c2, c3 = (g[..., t, :, :] for t in range(4))
    b0 = (c0 | (c1 << 6)) & 0xFF
    b1 = ((c1 >> 2) | (c2 << 4)) & 0xFF
    b2 = ((c2 >> 4) | (c3 << 2)) & 0xFF
    return np.concatenate([b0, b1, b2], axis=-2).astype(np.uint8)


def unpack6(packed: np.ndarray) -> np.ndarray:
    """Inverse of :func:`pack6` (numpy reference for the kernel)."""
    q = packed.shape[-2] // 3
    t = packed.astype(np.uint16)
    b0, b1, b2 = (t[..., i * q:(i + 1) * q, :] for i in range(3))
    c0 = b0 & 63
    c1 = ((b0 >> 6) | (b1 << 2)) & 63
    c2 = ((b1 >> 4) | (b2 << 4)) & 63
    c3 = (b2 >> 2) & 63
    return np.concatenate([c0, c1, c2, c3], axis=-2).astype(np.uint8)


def minifloat_from_sme(smew: SMEWeight) -> dict:
    """SMEWeight -> packed minifloat-6 arrays (per-tile layout).

    Returns {packed u8 [nr, nc, 3*tr/4, tc], rowscale f32 [nr, nc, tr],
    scale f32 [1, N], meta}.
    """
    if smew.live_bits > 7:
        raise ValueError("minifloat-6 requires squeeze >= 1 (3-bit exponent)")
    if smew.window > 3:
        raise ValueError("minifloat-6 requires S <= 3 (2-bit mantissa)")
    nr, nc = smew.grid
    tr, tc = smew.tile
    k, n = smew.shape
    # dense sign bits tiled like the codes
    signs = (np.unpackbits(smew.sign_packed, axis=1)[:, :n]).astype(np.uint8)
    from .bitslice import tile_codes
    signs_t = tile_codes(signs, smew.tile)
    code6 = encode6(smew.tiled_codes, signs_t, smew.n_bits, smew.squeezed)
    packed = pack6(code6.reshape(nr, nc, tr, tc))
    rowscale = np.exp2(smew.row_exp.astype(np.float32))
    return {
        "packed": packed,
        "rowscale": rowscale,
        "scale": np.broadcast_to(smew.scale, (1, n)).astype(np.float32),
        "n_bits": smew.n_bits, "squeezed": smew.squeezed,
        "shape": smew.shape, "tile": smew.tile,
    }


def minifloat_dequant(mf: dict) -> np.ndarray:
    """Packed minifloat-6 -> dense effective weights [K, N] (numpy oracle)."""
    code6 = unpack6(mf["packed"])                   # [nr, nc, tr, tc]
    val = decode6_value(code6, mf["n_bits"], mf["squeezed"])
    val = val * mf["rowscale"][..., None]
    nr, nc, tr, tc = code6.shape
    k, n = mf["shape"]
    dense = val.transpose(0, 2, 1, 3).reshape(nr * tr, nc * tc)[:k, :n]
    return dense * mf["scale"]


def bits_per_weight6(mf: dict) -> float:
    k, n = mf["shape"]
    payload = mf["packed"].size * 8 + mf["rowscale"].size * 32 \
        + mf["scale"].size * 32
    return payload / (k * n)
