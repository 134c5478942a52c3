"""Decoder-only LM assembly: embed -> [first dense blocks] -> scan over
superblocks -> final norm -> chunked-vocab loss / logits.

Compile-time discipline for the multi-pod dry-run:

* layers are stacked per superblock *slot* and iterated with ``lax.scan``
  (one traced superblock regardless of depth);
* the LM loss never materializes [B, S, V] logits — cross-entropy is
  computed in sequence chunks inside a scan;
* decode carries the stacked block caches in the scan's carry and
  updates them in place, a layer at a time (DESIGN.md §6).

The decode step names its head for the profiler (``jax.named_scope``,
DESIGN.md §9): ``lm_head`` on the final norm and head projection.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.parallel.policy import constrain

from .common import (
    Initializer, apply_norm, embed_init, norm_init, norm_pos_active,
)
from .blocks import (
    block_init, block_train, block_prefill, block_decode, init_block_cache,
)

__all__ = ["lm_init", "lm_train_loss", "lm_prefill", "lm_decode_step",
           "lm_init_cache", "chunked_ce_loss"]


def _slot_kinds(cfg):
    return list(cfg.pattern)


def lm_init(rng, cfg) -> Dict[str, Any]:
    init = Initializer(rng)
    kinds = _slot_kinds(cfg)
    params: Dict[str, Any] = {
        "embed": embed_init(init, cfg.vocab, cfg.d_model),
        "final_norm": norm_init(init, cfg.d_model, cfg.norm),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {"w": init.normal((cfg.d_model, cfg.vocab),
                                              stddev=0.02)}
    if cfg.frontend == "vision_stub":
        params["patch_proj"] = {"w": init.normal((cfg.d_model, cfg.d_model))}
    for i in range(cfg.first_dense_layers):
        # deepseek-style leading dense block(s), not scanned
        params[f"first{i}"] = block_init(init, cfg, "attn", use_moe=False)

    # stacked superblock params: one init per slot, stacked n_super times
    def one_super(s):
        sinit = Initializer(jax.random.fold_in(rng, 1000 + s))
        return {
            f"slot{j}": block_init(sinit, cfg, kinds[j], cfg.moe_for_slot(j))
            for j in range(len(kinds))
        }

    supers = [one_super(s) for s in range(cfg.n_super)]
    params["blocks"] = jax.tree.map(lambda *xs: jnp.stack(xs), *supers)
    return params


def _lm_head(params, cfg):
    """[D, V] head; tied heads are rescaled by 1/sqrt(D) to undo the
    sqrt(D) input-embedding scaling (Gemma convention)."""
    if cfg.tie_embeddings:
        return params["embed"]["w"].T * (cfg.d_model ** -0.5)
    we = params["lm_head"]["w"]
    if isinstance(we, dict) and "sme_codes" in we:
        from repro.core.integrate import sme_dequant_jnp
        return sme_dequant_jnp(we)
    return we


def _head_logits(params, cfg, xl):
    """Final projection xl[B, D] -> logits[B, V] (f32).

    Packed untied heads dispatch through the SME execution-backend
    registry (the decode hot path's largest matmul); tied/dense heads
    keep the materialized matrix.  Training keeps ``_lm_head`` — its
    chunked CE loss needs the dense matrix."""
    if not cfg.tie_embeddings:
        we = params["lm_head"]["w"]
        if isinstance(we, dict) and "sme_codes" in we:
            from repro.core.backend import sme_apply
            return sme_apply(xl, we, out_dtype=jnp.float32)
    head = _lm_head(params, cfg)
    return (xl @ head.astype(xl.dtype)).astype(jnp.float32)


def _embed_tokens(params, cfg, batch):
    """Returns [B, S_total, D] activations in compute dtype."""
    dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    x = params["embed"]["w"].astype(dt)[batch["tokens"]]
    if cfg.frontend == "vision_stub" and "patches" in batch:
        pp = batch["patches"].astype(dt) @ params["patch_proj"]["w"].astype(dt)
        x = jnp.concatenate([pp, x], axis=1)
    return x * (cfg.d_model ** 0.5)


def _run_first(params, cfg, x, mode, caches=None, pos=None,
               cache_len: int = 0, block_q=512, block_k=512, active=None,
               plen=None):
    new_caches = []
    for i in range(cfg.first_dense_layers):
        p = params[f"first{i}"]
        if mode == "train":
            x = block_train(p, x, cfg, "attn", False, block_q, block_k)
        elif mode == "prefill":
            x, c = block_prefill(p, x, cfg, "attn", False, cache_len,
                                 block_q, block_k, plen=plen)
            new_caches.append(c)
        else:
            x, c = block_decode(p, x, caches[i], pos, cfg, "attn", False,
                                active=active)
            new_caches.append(c)
    return x, new_caches


def _scan_train(params, cfg, x, block_q, block_k, remat: bool = True):
    kinds = _slot_kinds(cfg)

    def body(h, slot_params):
        for j, kind in enumerate(kinds):
            h = block_train(slot_params[f"slot{j}"], h, cfg, kind,
                            cfg.moe_for_slot(j), block_q, block_k)
            h = constrain(h, "act")
        return h, None

    if remat:
        from repro.parallel.policy import current_policy
        pol = current_policy()
        if pol is not None and pol.remat_policy == "dots":
            # save TP matmul outputs: backward recompute skips the forward
            # dots *and their collectives* (§Perf hillclimb B)
            body = jax.checkpoint(
                body, prevent_cse=False,
                policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
        else:
            body = jax.checkpoint(body, prevent_cse=False)
    x, _ = jax.lax.scan(body, x, params["blocks"])
    return x


def chunked_ce_loss(h, head_w, labels, mask, chunk: int = 128):
    """h:[B,S,D] -> mean CE without materializing [B,S,V]."""
    b, s, d = h.shape
    chunk = min(chunk, s)
    pad = (-s) % chunk
    if pad:
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    nc = h.shape[1] // chunk
    hc = h.reshape(b, nc, chunk, d).swapaxes(0, 1)
    lc = labels.reshape(b, nc, chunk).swapaxes(0, 1)
    mc = mask.reshape(b, nc, chunk).swapaxes(0, 1)

    def step(carry, args):
        hx, lx, mx = args
        logits = (hx @ head_w.astype(hx.dtype)).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lx[..., None], axis=-1)[..., 0]
        ce = (lse - gold) * mx
        return (carry[0] + ce.sum(), carry[1] + mx.sum()), None

    (tot, cnt), _ = jax.lax.scan(step, (jnp.float32(0), jnp.float32(0)),
                                 (hc, lc, mc))
    return tot / jnp.maximum(cnt, 1.0)


def lm_train_loss(params, batch, cfg, block_q: int = 512, block_k: int = 512,
                  loss_chunk: int = 128, remat: bool = True):
    from repro.parallel.policy import current_policy
    _pol = current_policy()
    if _pol is not None and _pol.loss_chunk:
        loss_chunk = _pol.loss_chunk
    x = constrain(_embed_tokens(params, cfg, batch), "act")
    x, _ = _run_first(params, cfg, x, "train", block_q=block_q, block_k=block_k)
    x = _scan_train(params, cfg, x, block_q, block_k, remat)
    x = apply_norm(x, params["final_norm"], cfg.norm)
    head = _lm_head(params, cfg)
    labels = batch["labels"]
    mask = batch.get("mask", jnp.ones_like(labels, jnp.float32))
    if x.shape[1] != labels.shape[1]:          # vlm: patches prepended
        x = x[:, x.shape[1] - labels.shape[1]:]
    return chunked_ce_loss(x, head, labels, mask, loss_chunk)


def lm_init_cache(cfg, batch: int, s_max: int, dtype=jnp.bfloat16):
    kinds = _slot_kinds(cfg)
    first = [init_block_cache(cfg, "attn", batch, s_max, dtype)
             for _ in range(cfg.first_dense_layers)]
    one = {f"slot{j}": init_block_cache(cfg, kinds[j], batch, s_max, dtype)
           for j in range(len(kinds))}
    stacked = jax.tree.map(
        lambda l: jnp.broadcast_to(l[None], (cfg.n_super,) + l.shape), one)
    return {"first": first, "blocks": stacked}


def lm_prefill(params, batch, cfg, s_max: int,
               block_q: int = 512, block_k: int = 512, plen=None):
    """Returns (last-token logits [B, V], caches dict).

    ``plen`` ([B] int32, optional) marks each row's valid prefix length in
    a ragged (right-padded) prefill batch — including any frontend tokens.
    Causality keeps the padded suffix out of every valid position, caches
    and recurrent states stop per row at ``plen[i]``, and the returned
    logits are taken at each row's own last valid position, so one padded
    call is bit-identical per row to one unpadded call per request
    (DESIGN.md §7)."""
    kinds = _slot_kinds(cfg)
    x = constrain(_embed_tokens(params, cfg, batch), "act")
    x, first_caches = _run_first(params, cfg, x, "prefill",
                                 cache_len=s_max, block_q=block_q,
                                 block_k=block_k, plen=plen)

    def body(h, slot_params):
        caches = {}
        for j, kind in enumerate(kinds):
            h, c = block_prefill(slot_params[f"slot{j}"], h, cfg, kind,
                                 cfg.moe_for_slot(j), s_max, block_q, block_k,
                                 plen=plen)
            h = constrain(h, "act")
            caches[f"slot{j}"] = c
        return h, caches

    x, block_caches = jax.lax.scan(body, x, params["blocks"])
    x = apply_norm(x, params["final_norm"], cfg.norm)
    if plen is None:
        xl = x[:, -1]
    else:
        last = jnp.clip(jnp.asarray(plen, jnp.int32) - 1, 0, x.shape[1] - 1)
        xl = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
    logits = _head_logits(params, cfg, xl)
    return logits, {"first": first_caches, "blocks": block_caches}


def lm_decode_step(params, token, caches, pos, cfg, active=None):
    """token:[B,1] int32; pos:[B] i32 — each batch row's next position
    index (a scalar broadcasts); active:[B] bool — rows that decode this
    step and may write their cache region (None = all).  The scan body
    carries the full vectors, so one jitted call serves a ragged batch.

    The stacked block caches ride in the scan's carry, not its xs/ys:
    each layer reads its own layer of the one stacked buffer by index and
    puts it back with its new rows written (DESIGN.md §6), so no layer's
    cache is copied out of the stack or into a fresh one."""
    kinds = _slot_kinds(cfg)
    pos, active = norm_pos_active(pos, active, token.shape[0])
    x = _embed_tokens(params, cfg, {"tokens": token})
    x, first_caches = _run_first(params, cfg, x, "decode",
                                 caches=caches["first"], pos=pos,
                                 active=active)

    def body(carry, xs):
        h, stack = carry
        slot_params, layer = xs
        with jax.named_scope("kv_cache"):
            slot_caches = jax.tree.map(lambda l: l[layer], stack)
        new = {}
        for j, kind in enumerate(kinds):
            h, new[f"slot{j}"] = block_decode(
                slot_params[f"slot{j}"], h, slot_caches[f"slot{j}"], pos,
                cfg, kind, cfg.moe_for_slot(j), active=active)
        with jax.named_scope("kv_cache"):
            stack = jax.tree.map(
                lambda l, c: jax.lax.dynamic_update_index_in_dim(
                    l, c, layer, 0), stack, new)
        return (h, stack), None

    (x, block_caches), _ = jax.lax.scan(
        body, (x, caches["blocks"]),
        (params["blocks"], jnp.arange(cfg.n_super)))
    with jax.named_scope("lm_head"):
        x = apply_norm(x, params["final_norm"], cfg.norm)
        logits = _head_logits(params, cfg, x[:, -1])
    return logits, {"first": first_caches, "blocks": block_caches}
