"""Weights for a configuration, made by the benchmark from ``--seed``.

``make_weights`` draws every leaf on the device in one jitted call, in
bfloat16 (the type that is packed and served), in the reference's own
layout: per-layer leaves stacked on a leading ``[layers]`` axis, linear
weights ``[in, out]``.  ``program_params`` hands the same arrays to the
serving program in its tree layout, and refuses a layout that differs
from what the program would initialise itself.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

#: kinds of leaf: "proj" N(0, 1/fan_in), so every layer adds to the
#: residual about as much as the last; "qk" (query and key projections)
#: QK_GAIN times that, so that attention scores spread by about QK_GAIN^2
#: and attention picks out a few positions, as a trained model's does,
#: rather than averaging the context away; "embed" N(0, 1/(9*hidden)): with
#: the program's sqrt(hidden) input scale and tied head, a larger
#: embedding dominates the last residual and greedy decoding repeats the
#: input token; "bias" N(0, 0.1^2) and "norm" 1 + N(0, 0.1^2), so that
#: the check sees them
BIAS_STD = 0.1
NORM_STD = 0.1
QK_GAIN = 2.0


def dims(cfg: Dict) -> Dict[str, int]:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return dict(L=cfg["num_hidden_layers"], D=d, H=h,
                KV=cfg["num_key_value_heads"], hd=d // h,
                F=cfg["intermediate_size"], V=cfg["vocab_size"])


def leaf_specs(cfg: Dict) -> Dict[str, tuple]:
    n = dims(cfg)
    L, D, F, V = n["L"], n["D"], n["F"], n["V"]
    q, kv = n["H"] * n["hd"], n["KV"] * n["hd"]
    return {
        "embed": ((V, D), "embed"),
        "final_norm": ((D,), "norm"),
        "norm1": ((L, D), "norm"),
        "q_w": ((L, D, q), "qk"), "q_b": ((L, q), "bias"),
        "k_w": ((L, D, kv), "qk"), "k_b": ((L, kv), "bias"),
        "v_w": ((L, D, kv), "proj"), "v_b": ((L, kv), "bias"),
        "o_w": ((L, q, D), "proj"),
        "norm2": ((L, D), "norm"),
        "gate_w": ((L, D, F), "proj"), "up_w": ((L, D, F), "proj"),
        "down_w": ((L, F, D), "proj"),
    }


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any whole-number seed (JAX seeds hold 32 bits)."""
    s = int(seed) % (1 << 64)
    return jax.random.fold_in(jax.random.key(s & 0xFFFFFFFF), s >> 32)


def make_weights(cfg: Dict, seed: int) -> Dict[str, jax.Array]:
    specs = leaf_specs(cfg)

    def draw(key):
        out = {}
        for i, (name, (shape, kind)) in enumerate(sorted(specs.items())):
            z = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            if kind == "proj":
                z = z * shape[-2] ** -0.5
            elif kind == "qk":
                z = z * QK_GAIN * shape[-2] ** -0.5
            elif kind == "embed":
                z = z * (9 * shape[-1]) ** -0.5
            elif kind == "bias":
                z = z * BIAS_STD
            elif kind == "norm":
                z = 1.0 + z * NORM_STD
            out[name] = z.astype(jnp.bfloat16)
        return out

    return jax.jit(draw)(seed_key(seed))


def program_params(w: Dict, api) -> Dict:
    """The program's param tree over the arrays of ``w``; the structure
    and shapes must equal those of ``api.init_params``."""
    def lin(name, bias=True):
        p = {"w": w[name + "_w"]}
        if bias and name + "_b" in w:
            p["b"] = w[name + "_b"]
        return p

    tree = {
        "embed": {"w": w["embed"]},
        "final_norm": {"w": w["final_norm"]},
        "blocks": {"slot0": {
            "norm1": {"w": w["norm1"]},
            "mix": {"q": lin("q"), "k": lin("k"), "v": lin("v"),
                    "o": lin("o")},
            "norm2": {"w": w["norm2"]},
            "mlp": {"wi": lin("up"), "wg": lin("gate"), "wo": lin("down")},
        }},
    }
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
