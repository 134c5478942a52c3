"""Weights for a configuration, made by the benchmark from ``--seed``.

``make_weights`` draws every leaf on the device in one jitted call, in
bfloat16 (the type that is packed and served), in the reference's own
layout as the architecture's module gives it (``arch``: ``leaf_specs``;
linear weights ``[..., in, out]``).  ``program_params`` hands the same
arrays to the serving program in its tree layout, and refuses a layout
that differs from what the program would initialise itself.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from arch import for_config

#: kinds of leaf: "proj" N(0, 1/fan_in), so every layer adds to the
#: residual about as much as the last (query and key projections too: at
#: twice that scale attention grows so sharp that bfloat16 rounding alone
#: moves the logits some hundred times more, by an amount that differs
#: from draw to draw, and the check no longer tells it from float8); "embed"
#: N(0, 1/(9*hidden)): with the program's sqrt(hidden) input scale and
#: tied head, a larger embedding dominates the last residual and greedy
#: decoding repeats the input token; "bias" N(0, 0.1^2) and "norm"
#: 1 + N(0, 0.1^2), so that the check sees them
BIAS_STD = 0.1
NORM_STD = 0.1


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any whole-number seed (JAX seeds hold 32 bits)."""
    s = int(seed) % (1 << 64)
    return jax.random.fold_in(jax.random.key(s & 0xFFFFFFFF), s >> 32)


def make_weights(cfg: Dict, seed: int) -> Dict[str, jax.Array]:
    """Every leaf of the architecture's ``leaf_specs``, leaf ``i`` of the
    sorted names from the key folded with ``i``."""
    specs = for_config(cfg).leaf_specs(cfg)

    def draw(key):
        out = {}
        for i, (name, (shape, kind)) in enumerate(sorted(specs.items())):
            z = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            if kind == "proj":
                z = z * shape[-2] ** -0.5
            elif kind == "embed":
                z = z * (9 * shape[-1]) ** -0.5
            elif kind == "bias":
                z = z * BIAS_STD
            elif kind == "norm":
                z = 1.0 + z * NORM_STD
            else:
                raise ValueError(f"{name}: no draw of kind {kind!r}")
            out[name] = z.astype(jnp.bfloat16)
        return out

    return jax.jit(draw)(seed_key(seed))


def program_params(cfg: Dict, w: Dict, api) -> Dict:
    """The architecture's param tree over the arrays of ``w``; the
    structure and shapes must equal those of ``api.init_params``."""
    tree = for_config(cfg).program_params(w, api)
    want = jax.eval_shape(api.init_params, jax.random.key(0))
    got_s = jax.tree.structure(tree)
    want_s = jax.tree.structure(want)
    if got_s != want_s:
        raise ValueError(f"program param layout changed:\n{want_s}\n"
                         f"the benchmark builds\n{got_s}")
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(want)):
        if tuple(a.shape) != tuple(b.shape):
            raise ValueError(f"program leaf shape {b.shape} != {a.shape}")
    return tree
