"""Attention: GQA (+ SWA / local-global), MLA (deepseek), cross-attn.

Design notes (large-scale posture):

* train/prefill self-attention is **blockwise** (flash-style online softmax
  via ``lax.scan`` over KV blocks) so 32k-token prefill never materializes
  the [S, S] logits;
* sliding-window layers use a per-q-block **dynamic slice** of K/V instead
  of masking the full sequence (no O(S^2) waste at 32k for window 1k);
* decode uses fixed-size KV caches; windowed layers keep a **ring buffer**
  of ``window`` entries whose positions are derived (slot j at step t holds
  position p = largest p <= t with p % W == j), so no position array is stored;
* decode positions are **per batch row**: ``pos`` is a ``[B]`` int32 vector
  (a scalar broadcasts) and ``active`` a ``[B]`` bool mask — each row writes
  its own ring/linear cache slot and inactive rows never write at all, so a
  ragged serving batch cannot clobber another slot's cache (DESIGN.md §6);
* MLA caches the **compressed** c_kv/k_pe (paper-faithful memory win) and
  decodes in the absorbed form (q folded through W_uk, output through W_uv).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.parallel.policy import constrain

from .common import (
    Initializer, apply_rope, linear, linear_init, norm_pos_active,
)

__all__ = [
    "gqa_init", "gqa_prefill", "gqa_decode",
    "mla_init", "mla_prefill", "mla_decode",
    "cross_init", "cross_apply", "cross_decode",
    "blockwise_attention", "NEG_INF",
]

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# blockwise (flash-style) attention core
# ---------------------------------------------------------------------------

def _attend_block(q, k, v, qpos, kpos, causal, window, scale):
    """One (q-block, k-block) tile. q:[B,Bq,H,hd] k/v:[B,Bk,KV,hd]."""
    b, bq, h, hd = q.shape
    kv = k.shape[2]
    g = h // kv
    qh = q.reshape(b, bq, kv, g, hd)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qh.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    mask = jnp.ones((bq, kpos.shape[0]), bool)
    if causal:
        mask &= qpos[:, None] >= kpos[None, :]
    if window:
        mask &= qpos[:, None] - kpos[None, :] < window
    mask &= kpos[None, :] >= 0
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    return s  # [B, KV, G, Bq, Bk]


def _online_update(carry, s, v):
    """Online softmax update. carry = (m, l, acc)."""
    m, l, acc = carry
    b, kv, g, bq, bk = s.shape
    m_new = jnp.maximum(m, s.max(axis=-1))
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m - m_new)
    l = l * corr + p.sum(axis=-1)
    pv = jnp.einsum("bkgqs,bskd->bkgqd", p, v.astype(jnp.float32))
    acc = acc * corr[..., None] + pv
    return (m_new, l, acc)


def blockwise_attention(
    q, k, v, *, causal: bool = True, window: int = 0,
    q_offset: int = 0, block_q: int = 512, block_k: int = 512,
):
    """q:[B,Sq,H,hd], k/v:[B,Sk,KV,hd] -> [B,Sq,H,hd].

    ``q_offset``: absolute position of q[0] (cross/self prefill alignment).
    Windowed attention slices K/V per q block instead of scanning all of it.
    """
    b, sq, h, hd = q.shape
    hd_v = v.shape[-1]
    sk = k.shape[1]
    kvh = k.shape[2]
    g = h // kvh
    scale = 1.0 / (hd ** 0.5)
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    nq = -(-sq // block_q)
    pad_q = nq * block_q - sq
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))

    if window and window < sk:
        # per-q-block K/V slice: [start - window + 1, start + block_q)
        span = window - 1 + block_q
        span = min(span, sk)

        @jax.checkpoint
        def q_block(i):
            qi = jax.lax.dynamic_slice_in_dim(q, i * block_q, block_q, 1)
            qpos = q_offset + i * block_q + jnp.arange(block_q)
            start = jnp.clip(q_offset + i * block_q - (window - 1), 0, sk - span)
            ki = jax.lax.dynamic_slice_in_dim(k, start, span, 1)
            vi = jax.lax.dynamic_slice_in_dim(v, start, span, 1)
            kpos = start + jnp.arange(span)
            s = _attend_block(qi, ki, vi, qpos, kpos, causal, window, scale)
            m = s.max(axis=-1)
            p = jnp.exp(s - m[..., None])
            l = p.sum(axis=-1)
            acc = jnp.einsum("bkgqs,bskd->bkgqd", p, vi.astype(jnp.float32))
            out = acc / jnp.maximum(l[..., None], 1e-30)
            return out  # [B, KV, G, Bq, hd]

        outs = jax.lax.map(q_block, jnp.arange(nq))          # [nq, B, KV, G, Bq, hd_v]
        out = jnp.moveaxis(outs, 0, 3)                       # [B, KV, G, nq, Bq, hd_v]
        out = out.reshape(b, kvh, g, nq * block_q, hd_v)
    else:
        nk = -(-sk // block_k)
        pad_k = nk * block_k - sk
        if pad_k:
            k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        k4 = k.reshape(b, nk, block_k, kvh, hd)
        v4 = v.reshape(b, nk, block_k, kvh, hd_v)

        def q_block(i):
            qi = jax.lax.dynamic_slice_in_dim(q, i * block_q, block_q, 1)
            qpos = q_offset + i * block_q + jnp.arange(block_q)

            # checkpoint: backward recomputes the [Bq, Bk] score tile instead
            # of saving one per (q, kv) block pair (flash-attention memory)
            @jax.checkpoint
            def kv_step(carry, j):
                kj, vj = k4[:, j], v4[:, j]
                kpos = jnp.where(j * block_k + jnp.arange(block_k) < sk,
                                 j * block_k + jnp.arange(block_k), -1)
                s = _attend_block(qi, kj, vj, qpos, kpos, causal, window, scale)
                return _online_update(carry, s, vj), None

            m0 = jnp.full((b, kvh, g, block_q), NEG_INF, jnp.float32)
            l0 = jnp.zeros((b, kvh, g, block_q), jnp.float32)
            a0 = jnp.zeros((b, kvh, g, block_q, hd_v), jnp.float32)
            (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), jnp.arange(nk))
            return acc / jnp.maximum(l[..., None], 1e-30)    # [B,KV,G,Bq,hd]

        outs = jax.lax.map(q_block, jnp.arange(nq))
        out = jnp.moveaxis(outs, 0, 3).reshape(b, kvh, g, nq * block_q, hd_v)

    out = out.reshape(b, h, nq * block_q, hd_v)[:, :, :sq]
    out = jnp.moveaxis(out, 1, 2)                            # [B, Sq, H, hd]
    return out.astype(q.dtype)


@jax.named_scope("attention")
def _decode_attend(q, k, v, kpos, pos, window, scale):
    """Single-step attention. q:[B,1,H,hd]; k/v:[B,W,KV,hd]; kpos:[B?,W];
    pos:[B] (per-row query position).  Its ops carry the ``attention``
    scope in the step program's metadata (DESIGN.md §9)."""
    b, _, h, hd = q.shape
    kvh = k.shape[2]
    g = h // kvh
    qh = q.reshape(b, kvh, g, hd)
    s = jnp.einsum("bkgd,bskd->bkgs", qh.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    valid = (kpos >= 0) & (kpos <= pos[:, None])
    if window:
        valid &= pos[:, None] - kpos < window
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p, v.astype(jnp.float32))
    return o.reshape(b, 1, h, hd).astype(q.dtype)


@jax.named_scope("kv_cache")
def _masked_row_write(cache, new, slot, active):
    """cache:[B,W,...] <- new:[B,...] at per-row ``slot`` [B], only where
    ``active`` [B]; inactive rows keep their cache bytes untouched.

    An elementwise select, not a scatter: a select leaves the cache in
    the device's default layout (a scatter's row window wants another,
    and the compiler relayouts a scanned layer stack on the way in and
    out of every step), and the attention reads it fused (DESIGN.md §6)."""
    hit = (jnp.arange(cache.shape[1])[None] == slot[:, None]) \
        & active[:, None]
    return jnp.where(hit.reshape(hit.shape + (1,) * (new.ndim - 1)),
                     new[:, None].astype(cache.dtype), cache)


def _ring_gather(kv, plen, w):
    """kv:[B,S,...] -> ring cache [B,w,...] for a ragged prefill batch.

    Slot ``j`` of row ``i`` holds position ``p`` = the largest
    ``p < plen[i]`` with ``p % w == j`` (zeros where no such position
    exists) — exactly the layout ``gqa_decode`` derives its ``kpos`` from,
    and bit-identical to the dense scatter it replaces when
    ``plen == S`` for every row (padded positions never enter the ring)."""
    b, s = kv.shape[:2]
    j = jnp.arange(w)
    pm1 = plen[:, None] - 1
    p = pm1 - ((pm1 - j[None]) % w)                       # [B, w]
    valid = (p >= 0).reshape((b, w) + (1,) * (kv.ndim - 2))
    idx = jnp.clip(p, 0, s - 1).reshape((b, w) + (1,) * (kv.ndim - 2))
    out = jnp.take_along_axis(kv, idx, axis=1)
    return jnp.where(valid, out, jnp.zeros((), kv.dtype))


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------

def gqa_init(init: Initializer, cfg):
    hd, h, kv, d = cfg.hd, cfg.n_heads, cfg.n_kv_heads, cfg.d_model
    return {
        "q": linear_init(init, d, h * hd, bias=cfg.qkv_bias),
        "k": linear_init(init, d, kv * hd, bias=cfg.qkv_bias),
        "v": linear_init(init, d, kv * hd, bias=cfg.qkv_bias),
        "o": linear_init(init, h * hd, d),
    }


def _qkv(p, x, cfg, positions, rope=True):
    b, s, _ = x.shape
    hd, h, kv = cfg.hd, cfg.n_heads, cfg.n_kv_heads
    q = linear(x, p["q"]).reshape(b, s, h, hd)
    k = linear(x, p["k"]).reshape(b, s, kv, hd)
    v = linear(x, p["v"]).reshape(b, s, kv, hd)
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    q = constrain(q, "heads")
    return q, k, v


def _repeat_kv(k, v, h):
    """Repeat K/V to the full head count before attention so the GQA
    grouping never reshape-splits a head-sharded dimension (TP-safe)."""
    g = h // k.shape[2]
    if g > 1:
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    return constrain(k, "kv"), constrain(v, "kv")


def gqa_prefill(p, x, cfg, window: int = 0, causal: bool = True,
                cache_len: int = 0, block_q: int = 512, block_k: int = 512,
                plen=None):
    """Full-sequence self-attention. Returns (y, (k_cache, v_cache, kpos))
    where the cache holds the last ``min(window or S, cache_len or S)``
    entries in ring order (ready for gqa_decode).

    ``plen`` ([B] int32, optional) is the per-row valid prefix length of a
    ragged (right-padded) prefill batch: row ``i``'s ring cache holds only
    positions ``< plen[i]`` — causality already keeps padded positions out
    of every real position's attention output, so one padded prefill call
    is bit-identical per row to an unpadded call (DESIGN.md §7)."""
    b, s, _ = x.shape
    positions = jnp.arange(s)[None, :]
    q, k, v = _qkv(p, x, cfg, positions)
    kr, vr = _repeat_kv(k, v, cfg.n_heads)
    y = blockwise_attention(q, kr, vr, causal=causal, window=window,
                            block_q=block_q, block_k=block_k)
    y = linear(y.reshape(b, s, -1), p["o"])
    cache = None
    if cache_len:
        w = min(window, cache_len) if window else cache_len
        rows = (jnp.full((b,), s, jnp.int32) if plen is None
                else jnp.asarray(plen, jnp.int32))
        cache = {"k": _ring_gather(k, rows, w),
                 "v": _ring_gather(v, rows, w)}
    return y, cache


def gqa_decode(p, x, cache, pos, cfg, window: int = 0, active=None):
    """One-step decode. x:[B,1,D]; cache k/v:[B,W,KV,hd]; pos:[B] i32
    per-row next position (a scalar broadcasts); active:[B] bool — only
    active rows write their ring slot (None = all)."""
    b = x.shape[0]
    pos, active = norm_pos_active(pos, active, b)
    q, k, v = _qkv(p, x, cfg, pos[:, None])
    w = cache["k"].shape[1]
    slot = pos % w
    kc = _masked_row_write(cache["k"], k[:, 0], slot, active)
    vc = _masked_row_write(cache["v"], v[:, 0], slot, active)
    # per row, slot j holds position p = pos - ((pos - j) mod W)
    j = jnp.arange(w)
    kpos = pos[:, None] - ((pos[:, None] - j[None]) % w)
    y = _decode_attend(q, kc, vc, kpos, pos, window, 1.0 / (cfg.hd ** 0.5))
    y = linear(y.reshape(b, 1, -1), p["o"])
    return y, {"k": kc, "v": vc}


# ---------------------------------------------------------------------------
# MLA (deepseek-v2)
# ---------------------------------------------------------------------------

def mla_init(init: Initializer, cfg):
    d, h = cfg.d_model, cfg.n_heads
    dn, dr, dv = cfg.nope_head_dim, cfg.rope_head_dim, cfg.v_head_dim
    p = {
        "kv_down": linear_init(init, d, cfg.kv_lora + dr),
        "kv_up": linear_init(init, cfg.kv_lora, h * (dn + dv)),
        "o": linear_init(init, h * dv, d),
    }
    if cfg.q_lora:
        p["q_down"] = linear_init(init, d, cfg.q_lora)
        p["q_up"] = linear_init(init, cfg.q_lora, h * (dn + dr))
    else:
        p["q"] = linear_init(init, d, h * (dn + dr))
    return p


def _mla_q(p, x, cfg, positions):
    b, s, _ = x.shape
    h, dn, dr = cfg.n_heads, cfg.nope_head_dim, cfg.rope_head_dim
    if "q_down" in p:
        q = linear(linear(x, p["q_down"]), p["q_up"])
    else:
        q = linear(x, p["q"])
    q = q.reshape(b, s, h, dn + dr)
    q = constrain(q, "heads")
    q_nope, q_pe = q[..., :dn], q[..., dn:]
    q_pe = apply_rope(q_pe, positions, cfg.rope_theta)
    return q_nope, q_pe


def mla_prefill(p, x, cfg, cache_len: int = 0, block_q: int = 512,
                block_k: int = 512, plen=None):
    """``plen`` ([B] int32, optional): per-row valid prefix length of a
    ragged prefill batch — positions ``>= plen[i]`` are zeroed in row
    ``i``'s compressed cache (matching the zeros an unpadded prefill of
    length ``plen[i]`` leaves there)."""
    b, s, _ = x.shape
    h, dn, dr, dv = cfg.n_heads, cfg.nope_head_dim, cfg.rope_head_dim, cfg.v_head_dim
    positions = jnp.arange(s)[None, :]
    q_nope, q_pe = _mla_q(p, x, cfg, positions)
    ckv = linear(x, p["kv_down"])
    c, k_pe_raw = ckv[..., :cfg.kv_lora], ckv[..., cfg.kv_lora:]
    k_pe = apply_rope(k_pe_raw[:, :, None, :], positions, cfg.rope_theta)
    kv = linear(c, p["kv_up"]).reshape(b, s, h, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    # assemble full q/k with shared rope part broadcast over heads
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (b, s, h, dr))], axis=-1)
    k = constrain(k, "heads")
    v = constrain(v, "heads")
    y = blockwise_attention(q, k, v, causal=True,
                            block_q=block_q, block_k=block_k)
    y = linear(y.reshape(b, s, -1), p["o"])
    cache = None
    if cache_len:
        cc = jnp.zeros((b, cache_len, cfg.kv_lora), c.dtype)
        pc = jnp.zeros((b, cache_len, dr), c.dtype)
        take = min(cache_len, s)
        c_w, pe_w = c, k_pe[:, :, 0]
        if plen is not None:
            keep = (jnp.arange(s) < jnp.asarray(plen, jnp.int32)[:, None]
                    )[..., None]
            c_w = jnp.where(keep, c_w, jnp.zeros((), c.dtype))
            pe_w = jnp.where(keep, pe_w, jnp.zeros((), c.dtype))
        cc = cc.at[:, :take].set(c_w[:, s - take:])
        pc = pc.at[:, :take].set(pe_w[:, s - take:])
        cache = {"c": cc, "k_pe": pc}
    return y, cache


def mla_decode(p, x, cache, pos, cfg, active=None):
    """Absorbed-form decode over the compressed cache. pos:[B] i32 per-row
    next position (a scalar broadcasts); active:[B] bool write mask."""
    b = x.shape[0]
    h, dn, dr, dv = cfg.n_heads, cfg.nope_head_dim, cfg.rope_head_dim, cfg.v_head_dim
    pos, active = norm_pos_active(pos, active, b)
    positions = pos[:, None]
    q_nope, q_pe = _mla_q(p, x, cfg, positions)         # [B,1,H,dn],[B,1,H,dr]
    ckv = linear(x, p["kv_down"])
    c_t, k_pe_raw = ckv[..., :cfg.kv_lora], ckv[..., cfg.kv_lora:]
    k_pe_t = apply_rope(k_pe_raw[:, :, None, :], positions, cfg.rope_theta)[:, :, 0]
    cc = _masked_row_write(cache["c"], c_t[:, 0], pos, active)
    pc = _masked_row_write(cache["k_pe"], k_pe_t[:, 0], pos, active)
    w_up = p["kv_up"]["w"].reshape(cfg.kv_lora, h, dn + dv)
    w_uk, w_uv = w_up[..., :dn], w_up[..., dn:]
    with jax.named_scope("attention"):
        q_c = jnp.einsum("bthn,khn->bthk", q_nope.astype(jnp.float32),
                         w_uk.astype(jnp.float32))
        s_c = jnp.einsum("bthk,bsk->bhs", q_c, cc.astype(jnp.float32))
        s_pe = jnp.einsum("bthr,bsr->bhs", q_pe.astype(jnp.float32),
                          pc.astype(jnp.float32))
        scale = 1.0 / ((dn + dr) ** 0.5)
        s = (s_c + s_pe) * scale
        kpos = jnp.arange(cc.shape[1])[None]
        s = jnp.where((kpos <= pos[:, None])[:, None, :], s, NEG_INF)
        prob = jax.nn.softmax(s, axis=-1)
        ctx = jnp.einsum("bhs,bsk->bhk", prob, cc.astype(jnp.float32))
        y = jnp.einsum("bhk,khv->bhv", ctx, w_uv.astype(jnp.float32))
    y = linear(y.reshape(b, 1, h * dv).astype(x.dtype), p["o"])
    return y, {"c": cc, "k_pe": pc}


# ---------------------------------------------------------------------------
# cross attention (whisper decoder)
# ---------------------------------------------------------------------------

def cross_init(init: Initializer, cfg):
    hd, h, d = cfg.hd, cfg.n_heads, cfg.d_model
    return {
        "q": linear_init(init, d, h * hd, bias=cfg.qkv_bias),
        "k": linear_init(init, d, h * hd),
        "v": linear_init(init, d, h * hd),
        "o": linear_init(init, h * hd, d),
    }


def cross_kv(p, enc, cfg):
    b, t, _ = enc.shape
    k = linear(enc, p["k"]).reshape(b, t, cfg.n_heads, cfg.hd)
    v = linear(enc, p["v"]).reshape(b, t, cfg.n_heads, cfg.hd)
    return {"k": k, "v": v}


def cross_apply(p, x, kv, cfg, block_q: int = 512, block_k: int = 512):
    b, s, _ = x.shape
    q = linear(x, p["q"]).reshape(b, s, cfg.n_heads, cfg.hd)
    y = blockwise_attention(q, kv["k"], kv["v"], causal=False,
                            block_q=block_q, block_k=block_k)
    return linear(y.reshape(b, s, -1), p["o"])


def cross_decode(p, x, kv, cfg):
    b = x.shape[0]
    q = linear(x, p["q"]).reshape(b, 1, cfg.n_heads, cfg.hd)
    t = kv["k"].shape[1]
    kpos = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
    y = _decode_attend(q, kv["k"], kv["v"], kpos, jnp.full((b,), t, jnp.int32),
                       0, 1.0 / (cfg.hd ** 0.5))
    return linear(y.reshape(b, 1, -1), p["o"])
