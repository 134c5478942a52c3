"""Plain float32 forward of the Qwen2 architecture (Qwen1.5 and Qwen2
share it: ``Qwen2ForCausalLM``), for deciding whether served tokens are
right.  It imports nothing of the serving program and is given only the
weights the benchmark drew and the configuration file.

Per layer: ``x += o(attn(rope(q), rope(k), v))`` on ``rmsnorm(x)``, with
bias on q/k/v only, grouped-query heads, causal softmax at scale
``head_dim^-1/2``, rotary embedding over half-split head dims at
``rope_theta``; then ``x += down(silu(gate(h)) * up(h))`` on
``rmsnorm(x)``; RMSNorm eps ``rms_norm_eps``; final norm and a head
tied to the embedding.  Every matmul runs at ``Precision.HIGHEST``.

Departures from the published description, each on purpose:

* projection weights are the effective weights of the format the
  configuration names (``sme_format``), since that is what is served;
* the input embedding is multiplied by ``sqrt(hidden_size)`` and the
  tied head by ``hidden_size^-1/2``: the serving program's convention
  (Gemma's), which Qwen does not have.  Both sides use it so the
  comparison tests the arithmetic; the departure of the program from
  Qwen is recorded in ``PERF.md``.

``round_to`` names a dtype that every intermediate tensor is rounded
through (embedding, residual stream, norm outputs, each projection's
output, rotated queries and keys, attention probabilities and output,
the MLP's gate and product), as a forward computed in that type would
store it; matmuls accumulate in float32 and the logits stay float32.
``None`` for the reference, ``float8_e4m3fn`` for the control.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from .sme_format import effective_weight

HIGHEST = jax.lax.Precision.HIGHEST
PROJ = ("q_w", "k_w", "v_w", "o_w", "gate_w", "up_w", "down_w")


def prepare(w: Dict, cfg: Dict) -> Dict:
    """float32 weights: projections through the configuration's format,
    every other leaf cast."""
    fmt = cfg["format"]
    eff = jax.jit(jax.vmap(functools.partial(
        effective_weight, n_bits=fmt["n_bits"], window=fmt["window"],
        squeeze=fmt["squeeze"], tile=fmt["tile"])))
    return {k: eff(v) if k in PROJ else v.astype(jnp.float32)
            for k, v in w.items()}


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x [B, T, heads, hd], position t at axis 1."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def hidden(p: Dict, cfg: Dict, tokens, round_to: Optional[str] = None):
    """Final-normed hidden states ``[B, T, D]`` for ``tokens [B, T]``."""
    rnd = ((lambda t: t.astype(round_to).astype(jnp.float32))
           if round_to else (lambda t: t))
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    kvh, hd = cfg["num_key_value_heads"], d // h
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    b, t = tokens.shape
    mm = functools.partial(jnp.einsum, precision=HIGHEST)
    causal = jnp.tril(jnp.ones((t, t), bool))

    def proj(x, w, bias=None):
        y = mm("btd,de->bte", x, w)
        return rnd(y if bias is None else y + bias)

    def layer(x, lp):
        hn = rnd(_rms(x, lp["norm1"], eps))
        q = proj(hn, lp["q_w"], lp["q_b"]).reshape(b, t, h, hd)
        k = proj(hn, lp["k_w"], lp["k_b"]).reshape(b, t, kvh, hd)
        v = proj(hn, lp["v_w"], lp["v_b"]).reshape(b, t, kvh, hd)
        q, k = rnd(_rope(q, theta)), rnd(_rope(k, theta))
        k = jnp.repeat(k, h // kvh, axis=2)
        v = jnp.repeat(v, h // kvh, axis=2)
        s = mm("bqhd,bkhd->bhqk", q, k) * hd ** -0.5
        s = jnp.where(causal, s, -jnp.inf)
        a = mm("bhqk,bkhd->bqhd", rnd(jax.nn.softmax(s, axis=-1)), v)
        x = rnd(x + proj(rnd(a.reshape(b, t, h * hd)), lp["o_w"]))
        hn = rnd(_rms(x, lp["norm2"], eps))
        f = rnd(rnd(jax.nn.silu(proj(hn, lp["gate_w"])))
                * proj(hn, lp["up_w"]))
        return rnd(x + proj(f, lp["down_w"])), None

    per_layer = {k: v for k, v in p.items()
                 if k not in ("embed", "final_norm")}
    x = rnd(p["embed"][tokens] * d ** 0.5)
    x, _ = jax.lax.scan(layer, x, per_layer)
    return rnd(_rms(x, p["final_norm"], eps))


def _head(p, cfg, xs):
    return jnp.einsum("bcd,vd->bcv", xs, p["embed"], precision=HIGHEST) \
        * cfg["hidden_size"] ** -0.5


@functools.partial(jax.jit, static_argnames=("cfg_key", "round_to", "chunk"))
def _gaps(p, tokens, targets, *, cfg_key, round_to, chunk):
    cfg = dict(cfg_key)
    with jax.default_matmul_precision("highest"):
        ref = hidden(p, cfg, tokens)
        other = hidden(p, cfg, tokens, round_to) if round_to else None
        b, t, d = ref.shape
        n = t // chunk

        def one(i):
            sl = lambda a: jax.lax.dynamic_slice_in_dim(a, i * chunk, chunk, 1)
            lr = _head(p, cfg, sl(ref))                       # [B, C, V]
            pick = sl(targets)
            if other is not None:
                pick = jnp.argmax(_head(p, cfg, sl(other)), -1)
            got = jnp.take_along_axis(lr, pick[..., None], -1)[..., 0]
            return lr.max(-1) - got

        return jnp.moveaxis(jax.lax.map(one, jnp.arange(n)), 0, 1
                            ).reshape(b, n * chunk)


CFG_KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "rms_norm_eps", "rope_theta")


def logit_gaps(p: Dict, cfg: Dict, tokens, targets,
               round_to: Optional[str] = None, chunk: int = 64):
    """``[B, T]`` gaps ``max_v ref[b, t, v] - ref[b, t, pick]``: ``pick`` is
    ``targets`` for the reference, or the control's own argmax when
    ``round_to`` is given.  ``T`` must be a multiple of ``chunk``."""
    key = tuple((k, cfg[k]) for k in CFG_KEYS)
    return _gaps(p, jnp.asarray(tokens), jnp.asarray(targets),
                 cfg_key=key, round_to=round_to, chunk=chunk)
