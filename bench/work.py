"""Operations and bytes the algorithm needs, from shapes alone.

The yardstick does not move with the program's format or kernel: an SME
projection call is charged ``2*M*K*N`` operations with ``M`` the rows the
call is given, and bytes for the weights at ``WEIGHT_BITS`` each (the
width of minifloat-6 with its sign, below which no exact encoding of
these weights goes), one float32 scale per output column, the bf16 input
rows and the float32 output rows.  Which calls one decode step makes,
and the operations of one token, are the architecture's (``arch``:
``sme_calls``, ``token_flops``).
"""
from __future__ import annotations

from typing import Dict, Iterable, Tuple

from arch import for_config

WEIGHT_BITS = 6


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


def request_flops(cfg: Dict, prompt_len: int, n_tokens: int,
                  first_in: bool, later_in: Iterable[int]) -> float:
    """Operations of one request counted in a window: its prefill when its
    first token came in the window (``first_in``), and for each later
    token ``j`` in ``later_in`` the decode step that fed token ``j - 1``
    at position ``prompt_len + j - 1``."""
    token_flops = for_config(cfg).token_flops
    total = 0.0
    if first_in:
        total += sum(token_flops(cfg, p, p == prompt_len - 1)
                     for p in range(prompt_len))
    for j in later_in:
        total += token_flops(cfg, prompt_len + j - 1, True)
    return total
