"""The SME weight format, as the configuration file states it, in plain
``jax.numpy`` — written from the paper's description, not from the
serving program.

A matrix ``w[K, N]`` keeps one scale, ``max|w| / (1 - 2^-window)``.  Each
magnitude, scaled into ``[0, 1)``, is rounded (half to even) to
``window`` significant binary digits anchored at its leading one and cut
at ``2^-n_bits`` (paper Eq. 2, "modified APT"); a round-up that carries
into the next binade is anchored once more.  Then ``squeeze`` rounds of
squeeze-out run per tile row: within each ``tile``-wide column tile, a
row whose current top plane holds a one anywhere is shifted right one
bit (its last bit dropped) and its input doubled, which leaves the
weight ``(code >> s) * 2^s``.  The effective weight is
``sign * code * 2^-n_bits * scale``.
"""
from __future__ import annotations

import jax.numpy as jnp


def effective_weight(w, *, n_bits: int, window: int, squeeze: int,
                     tile: int):
    """float32 ``[K, N]``: the weight the format represents for ``w``."""
    w = jnp.asarray(w, jnp.float32)
    k_rows, n_cols = w.shape
    a = jnp.abs(w)
    amax = jnp.max(a)
    amax = jnp.where(amax > 0, amax, 1.0)
    code_max = 1.0 - 2.0 ** -window
    v = jnp.minimum(a / amax * code_max, jnp.nextafter(
        jnp.float32(1), jnp.float32(0)))
    _, e = jnp.frexp(v)                       # v in [2^(e-1), 2^e)
    lead = 1 - e                              # 1 = the most significant bit
    k = jnp.clip(lead, 1, n_bits)
    end = jnp.minimum(n_bits, k + window - 1)
    m = jnp.round(jnp.ldexp(v, end))
    k = jnp.where(m >= jnp.ldexp(1.0, end - k + 1), jnp.maximum(k - 1, 1), k)
    end = jnp.minimum(n_bits, k + window - 1)
    m = jnp.round(jnp.ldexp(v, end))
    code = jnp.ldexp(m, n_bits - end).astype(jnp.int32)   # < 2^n_bits

    pad = (-n_cols) % tile
    c = jnp.pad(code, ((0, 0), (0, pad))).reshape(k_rows, -1, tile)
    shift = jnp.zeros(c.shape[:2] + (1,), jnp.int32)
    for t in range(squeeze):
        top = ((c >> (n_bits - (t + 1))) & 1).any(axis=-1, keepdims=True)
        c = jnp.where(top, c >> 1, c)
        shift = shift + top.astype(jnp.int32)
    mag = jnp.ldexp(c.astype(jnp.float32), shift - n_bits)
    mag = mag.reshape(k_rows, -1)[:, :n_cols]
    scale = amax / code_max
    return jnp.where(w < 0, -mag, mag) * scale
