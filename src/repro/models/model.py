"""Unified model API: build_model(cfg) -> ModelAPI.

Every architecture exposes the same five entry points so the launcher,
dry-run, trainer and serving engine are architecture-agnostic:

  * ``init_params(rng)``                    (use jax.eval_shape for dry-run)
  * ``train_loss(params, batch)``           scalar loss
  * ``prefill(params, batch, plen=None)``   -> (last logits, caches);
    ``plen`` is an optional per-row ``[B]`` int32 valid-prefix-length
    vector for ragged right-padded prefill batches (decoder-only
    family; DESIGN.md §7)
  * ``decode_step(params, token, caches, pos, active=None)``
    -> (logits, caches); ``pos`` is a per-row ``[B]`` int32 position
    vector (a scalar broadcasts) and ``active`` a ``[B]`` bool mask —
    inactive rows never write their cache region, so one jitted call
    serves a ragged continuous batch (DESIGN.md §6)
  * ``decode_chunk(params, tokens, caches, pos, nvalid, active=None,
    gated=None)`` -> (logits [K, B, V], live [K, B], caches); scores
    ``k >= 1`` positions per row in one call (chunked prefill, batched
    speculative verify — DESIGN.md §12), built uniformly from
    ``decode_step`` by :func:`make_decode_chunk`
  * ``input_specs(shape_cfg)``              ShapeDtypeStruct stand-ins
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, ShapeConfig
from . import encdec as ed
from . import transformer as tf

__all__ = ["ModelAPI", "build_model", "make_decode_chunk", "param_count",
           "active_param_count"]


def make_decode_chunk(decode_step: Callable) -> Callable:
    """Generalize a single-token ``decode_step`` to score ``k >= 1``
    positions per row in one call (DESIGN.md §12).

    ``tokens`` is ``[B, K]`` int32; row ``i`` consumes its first
    ``nvalid[i]`` tokens as consecutive decode steps starting at
    ``pos[i]`` and is an *inactive* row (no cache writes — the §6
    contract) for every later scan step.  ``gated`` rows additionally
    stop as soon as a step's greedy argmax differs from the next input
    token — the speculative-verify continuation rule: the next draft
    token may only be scored if the full-precision step just confirmed
    it would have been emitted.  Returns per-step logits ``[K, B, V]``,
    the per-step liveness mask ``[K, B]`` (``live[s, i]`` == "step s
    executed for row i"), and the updated caches.

    Each scan iteration is exactly one ``decode_step`` over ``[B, 1]``
    tokens, so every per-row value is bit-identical to the sequential
    loop of single steps it replaces, and independent of the padded
    scan length ``K`` (dead rows are inactive rows).
    """
    def decode_chunk(params, tokens, caches, pos, nvalid, active=None,
                     gated=None):
        b, k = tokens.shape
        pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
        nvalid = jnp.broadcast_to(jnp.asarray(nvalid, jnp.int32), (b,))
        act = jnp.ones((b,), bool) if active is None \
            else jnp.asarray(active, bool)
        gat = jnp.zeros((b,), bool) if gated is None \
            else jnp.asarray(gated, bool)
        toks = tokens.astype(jnp.int32).T                        # [K, B]
        nxt = jnp.roll(toks, -1, axis=0)   # step s's gate token; last unused

        def one(carry, xs):
            i, tok, nxt_tok = xs
            live, c, ps = carry
            # park dead rows at 0 so their (unwritten) positions stay
            # in-bounds by construction, like the engine's freed slots
            logits, c = decode_step(params, tok[:, None], c,
                                    jnp.where(live, ps, 0), live)
            l = logits if logits.ndim == 2 else logits[:, -1]
            with jax.named_scope("lm_head"):
                greedy = jnp.argmax(l, axis=-1).astype(jnp.int32)
            cont = live & (i + 1 < nvalid) & (~gat | (greedy == nxt_tok))
            return (cont, c, jnp.where(live, ps + 1, ps)), (l, live)

        init = (act & (nvalid > 0), caches, pos)
        (_, caches, _), (logits, live) = jax.lax.scan(
            one, init, (jnp.arange(k), toks, nxt))
        return logits, live, caches

    return decode_chunk


@dataclasses.dataclass
class ModelAPI:
    cfg: ModelConfig
    init_params: Callable
    train_loss: Callable
    prefill: Callable
    decode_step: Callable
    init_cache: Callable
    input_specs: Callable
    decode_chunk: Optional[Callable] = None

    def __post_init__(self):
        if self.decode_chunk is None:
            self.decode_chunk = make_decode_chunk(self.decode_step)

    def abstract_params(self):
        return jax.eval_shape(self.init_params, jax.random.key(0))

    def abstract_cache(self, batch: int, s_max: int):
        return jax.eval_shape(
            functools.partial(self.init_cache, batch=batch, s_max=s_max))


def _pick_blocks(cfg: ModelConfig, shape: Optional[ShapeConfig]):
    """Attention block sizes tuned per shape (bigger blocks at long seq)."""
    if shape is None or shape.seq_len <= 8192:
        return dict(block_q=512, block_k=512)
    return dict(block_q=1024, block_k=1024)


def build_model(cfg: ModelConfig, shape: Optional[ShapeConfig] = None) -> ModelAPI:
    bq = _pick_blocks(cfg, shape)
    if cfg.n_enc_layers:
        return _build_encdec(cfg, shape, bq)
    return _build_lm(cfg, shape, bq)


# ---------------------------------------------------------------------------
# decoder-only family (dense / moe / hybrid / ssm / vlm)
# ---------------------------------------------------------------------------

def _build_lm(cfg, shape, bq):
    def init_params(rng):
        return tf.lm_init(rng, cfg)

    def train_loss(params, batch):
        return tf.lm_train_loss(params, batch, cfg, **bq)

    def init_cache(batch: int, s_max: int):
        return tf.lm_init_cache(cfg, batch, s_max)

    def prefill(params, batch, s_max: Optional[int] = None, plen=None):
        s_max = s_max or batch["tokens"].shape[1]
        return tf.lm_prefill(params, batch, cfg, s_max, plen=plen, **bq)

    def decode_step(params, token, caches, pos, active=None):
        return tf.lm_decode_step(params, token, caches, pos, cfg,
                                 active=active)

    def input_specs(sh: ShapeConfig) -> Dict[str, Any]:
        b, s = sh.global_batch, sh.seq_len
        i32 = jnp.int32
        if sh.kind == "train":
            n_txt = s - (cfg.n_frontend_tokens if cfg.frontend else 0)
            specs = {
                "tokens": jax.ShapeDtypeStruct((b, n_txt), i32),
                "labels": jax.ShapeDtypeStruct((b, s), i32),
            }
            if cfg.frontend == "vision_stub":
                specs["patches"] = jax.ShapeDtypeStruct(
                    (b, cfg.n_frontend_tokens, cfg.d_model), jnp.bfloat16)
            return specs
        if sh.kind == "prefill":
            n_txt = s - (cfg.n_frontend_tokens if cfg.frontend else 0)
            specs = {"tokens": jax.ShapeDtypeStruct((b, n_txt), i32)}
            if cfg.frontend == "vision_stub":
                specs["patches"] = jax.ShapeDtypeStruct(
                    (b, cfg.n_frontend_tokens, cfg.d_model), jnp.bfloat16)
            return specs
        # decode: one new token against an s_max cache
        return {"token": jax.ShapeDtypeStruct((b, 1), i32)}

    return ModelAPI(cfg, init_params, train_loss, prefill, decode_step,
                    init_cache, input_specs)


# ---------------------------------------------------------------------------
# enc-dec family (whisper)
# ---------------------------------------------------------------------------

def _build_encdec(cfg, shape, bq):
    def init_params(rng):
        return ed.encdec_init(rng, cfg)

    def train_loss(params, batch):
        return ed.encdec_train_loss(params, batch, cfg, **bq)

    def init_cache(batch: int, s_max: int, src_len: Optional[int] = None):
        return ed.encdec_init_cache(cfg, batch, s_max, src_len or s_max)

    def prefill(params, batch, s_max: Optional[int] = None):
        s_max = s_max or batch["tokens"].shape[1]
        return ed.encdec_prefill(params, batch, cfg, s_max, **bq)

    def decode_step(params, token, caches, pos, active=None):
        return ed.encdec_decode_step(params, token, caches, pos, cfg,
                                     active=active)

    def input_specs(sh: ShapeConfig) -> Dict[str, Any]:
        b, s = sh.global_batch, sh.seq_len
        src = tgt = s // 2
        i32 = jnp.int32
        if sh.kind == "train":
            return {
                "frames": jax.ShapeDtypeStruct((b, src, cfg.d_model), jnp.bfloat16),
                "tokens": jax.ShapeDtypeStruct((b, tgt), i32),
                "labels": jax.ShapeDtypeStruct((b, tgt), i32),
            }
        if sh.kind == "prefill":
            return {
                "frames": jax.ShapeDtypeStruct((b, src, cfg.d_model), jnp.bfloat16),
                "tokens": jax.ShapeDtypeStruct((b, tgt), i32),
            }
        return {"token": jax.ShapeDtypeStruct((b, 1), i32)}

    return ModelAPI(cfg, init_params, train_loss, prefill, decode_step,
                    init_cache, input_specs)


# ---------------------------------------------------------------------------
# parameter accounting (roofline MODEL_FLOPS)
# ---------------------------------------------------------------------------

def param_count(params) -> int:
    return int(sum(np.prod(l.shape) for l in jax.tree.leaves(params)))


def active_param_count(params, cfg: ModelConfig) -> int:
    """MoE-aware active parameters (top_k of n_experts per token)."""
    if not cfg.n_experts:
        return param_count(params)
    total = 0
    flat = jax.tree.leaves_with_path(params)
    for path, leaf in flat:
        names = [getattr(p, "key", getattr(p, "name", "")) for p in path]
        n = int(np.prod(leaf.shape))
        if any(k in ("wi", "wg", "wo") for k in names) and leaf.ndim >= 3 \
                and cfg.n_experts in leaf.shape[:-2]:
            n = n * cfg.top_k // cfg.n_experts
        total += n
    return total
