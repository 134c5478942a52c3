"""Operations and bytes the algorithm needs, from shapes alone.

The yardstick does not move with the program's format or kernel: an SME
projection call is charged ``2*M*K*N`` operations with ``M`` the rows the
call is given, and bytes for the weights at ``WEIGHT_BITS`` each (the
width of minifloat-6 with its sign, below which no exact encoding of
these weights goes), one float32 scale per output column, the bf16 input
rows and the float32 output rows.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Tuple

WEIGHT_BITS = 6


def dims(cfg: Dict) -> Dict[str, int]:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return dict(L=cfg["num_hidden_layers"], D=d, H=h,
                KV=cfg["num_key_value_heads"], hd=d // h,
                F=cfg["intermediate_size"], V=cfg["vocab_size"])


def projections(cfg: Dict) -> List[Tuple[int, int]]:
    """(K, N) of the projections of one layer: q, k, v, o, gate, up, down."""
    n = dims(cfg)
    d, q, kv, f = n["D"], n["H"] * n["hd"], n["KV"] * n["hd"], n["F"]
    return [(d, q), (d, kv), (d, kv), (q, d), (d, f), (d, f), (f, d)]


def sme_call(m: int, k: int, n: int) -> Tuple[float, float]:
    """(operations, bytes) of one SME projection call on ``m`` rows."""
    flops = 2.0 * m * k * n
    nbytes = k * n * WEIGHT_BITS / 8 + 4.0 * n + 2.0 * m * k + 4.0 * m * n
    return flops, nbytes


def least_time(calls: Iterable[Tuple[float, float]], pk: Dict) -> float:
    """Seconds the chip needs at least for ``calls``: per call the larger
    of operations over peak and bytes over bandwidth."""
    return sum(max(f / pk["bf16_flops"], b / pk["hbm_bytes_per_s"])
               for f, b in calls)


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


def request_flops(cfg: Dict, prompt_len: int, n_tokens: int,
                  first_in: bool, later_in: Iterable[int]) -> float:
    """Operations of one request counted in a window: its prefill when its
    first token came in the window (``first_in``), and for each later
    token ``j`` in ``later_in`` the decode step that fed token ``j - 1``
    at position ``prompt_len + j - 1``."""
    total = 0.0
    if first_in:
        total += sum(token_flops(cfg, p, p == prompt_len - 1)
                     for p in range(prompt_len))
    for j in later_in:
        total += token_flops(cfg, prompt_len + j - 1, True)
    return total
