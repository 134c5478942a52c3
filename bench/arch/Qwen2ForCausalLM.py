"""``Qwen2ForCausalLM`` (Qwen1.5 and Qwen2 share it): dense grouped-query
attention with q/k/v bias, a SwiGLU MLP, RMSNorm, one uniform stack of
layers.  The reference is ``reference/qwen.py``.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple

import work
from reference.qwen import logit_gaps, prepare  # noqa: F401  (the reference)


def program_config(cfg: Dict):
    """The program's ``ModelConfig`` with every size from the file."""
    from repro.configs import ARCHS
    if cfg["hidden_act"] != "silu":
        raise ValueError(f"Qwen2 with hidden_act {cfg['hidden_act']!r}: "
                         f"the program serves silu only")
    return dataclasses.replace(
        ARCHS[cfg["program_arch"]],
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=0,
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        qkv_bias=True, tie_embeddings=cfg["tie_word_embeddings"],
        rope_theta=float(cfg["rope_theta"]))


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
        "q_w": ((L, D, q), "proj"), "q_b": ((L, q), "bias"),
        "k_w": ((L, D, kv), "proj"), "k_b": ((L, kv), "bias"),
        "v_w": ((L, D, kv), "proj"), "v_b": ((L, kv), "bias"),
        "o_w": ((L, q, D), "proj"),
        "norm2": ((L, D), "norm"),
        "gate_w": ((L, D, F), "proj"), "up_w": ((L, D, F), "proj"),
        "down_w": ((L, F, D), "proj"),
    }


def program_params(w: Dict, api) -> Dict:
    """The program's param tree over the arrays of ``w``: one stacked
    ``slot0`` of every layer, the head tied to the embedding."""
    del api

    def lin(name):
        p = {"w": w[name + "_w"]}
        if name + "_b" in w:
            p["b"] = w[name + "_b"]
        return p

    return {
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


def projections(cfg: Dict) -> List[Tuple[int, int]]:
    """(K, N) of the projections of one layer: q, k, v, o, gate, up, down."""
    n = dims(cfg)
    d, q, kv, f = n["D"], n["H"] * n["hd"], n["KV"] * n["hd"], n["F"]
    return [(d, q), (d, kv), (d, kv), (q, d), (d, f), (d, f), (f, d)]


def sme_calls(cfg: Dict, rows: int) -> List[Tuple[float, float]]:
    """Every layer's seven projections on ``rows`` rows."""
    return [work.sme_call(rows, k, n) for k, n in projections(cfg)] \
        * cfg["num_hidden_layers"]


def token_flops(cfg: Dict, pos: int, head: bool) -> float:
    """Operations to process one token at position ``pos`` (0-based):
    every layer's projections, attention over the ``pos + 1`` live
    positions (scores and values), and the tied head when its logits
    are needed."""
    n = dims(cfg)
    proj = sum(2.0 * k * nn for k, nn in projections(cfg))
    attn = 4.0 * n["H"] * n["hd"] * (pos + 1)
    out = n["L"] * (proj + attn)
    if head:
        out += 2.0 * n["D"] * n["V"]
    return out
