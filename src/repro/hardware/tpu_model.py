"""TPU v5e roofline model — the three dry-run-derived terms (task §Roofline).

    compute term    = HLO_FLOPs        / (chips * peak_FLOP/s)
    memory term     = HLO_bytes        / (chips * HBM_bw)
    collective term = collective_bytes / (chips * link_bw)

HLO_FLOPs / HLO_bytes come from ``compiled.cost_analysis()`` of the
*partitioned* (per-device) module, so ``chips`` only divides quantities
that are still global (see callers in ``launch/dryrun.py``).
"""
from __future__ import annotations

import dataclasses
from typing import Dict

__all__ = ["TPUSpec", "V5E", "PEAKS", "peak_spec", "roofline_terms",
           "dominant_term", "model_flops"]


@dataclasses.dataclass(frozen=True)
class TPUSpec:
    name: str = "tpu-v5e"
    peak_bf16_flops: float = 197e12        # per chip
    hbm_bw: float = 819e9                  # bytes/s per chip
    ici_link_bw: float = 50e9              # bytes/s per link (task constant)
    hbm_bytes: float = 16e9                # capacity per chip
    vmem_bytes: float = 128e6              # ~128MB VMEM v5e


V5E = TPUSpec()

#: per-chip peaks keyed by ``jax.devices()[0].device_kind``.  Source: Google
#: Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 16 GB HBM at 819 GB/s).
PEAKS: Dict[str, TPUSpec] = {"TPU v5 lite": V5E}


def peak_spec(device_kind: str) -> TPUSpec:
    """Peaks of the chip JAX reports as ``device_kind``; an unknown kind
    is an error, never a silent v5e default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak table entry for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None


def roofline_terms(
    per_device_flops: float,
    per_device_bytes: float,
    per_device_collective_bytes: float,
    spec: TPUSpec = V5E,
) -> Dict[str, float]:
    """All inputs are per-device quantities from the partitioned module."""
    t_compute = per_device_flops / spec.peak_bf16_flops
    t_memory = per_device_bytes / spec.hbm_bw
    t_collective = per_device_collective_bytes / spec.ici_link_bw
    terms = {
        "compute_s": t_compute,
        "memory_s": t_memory,
        "collective_s": t_collective,
    }
    bound = max(terms, key=terms.get)
    terms["bottleneck"] = bound.replace("_s", "")
    # roofline fraction: useful-compute share of the step's critical path
    crit = max(t_compute, t_memory, t_collective)
    terms["roofline_fraction"] = (t_compute / crit) if crit > 0 else 0.0
    return terms


def dominant_term(terms: Dict[str, float]) -> str:
    return str(terms["bottleneck"])


def model_flops(n_params: int, n_tokens: int, kind: str = "train",
                n_active_params: int | None = None) -> float:
    """MODEL_FLOPS = 6*N*D (train) or 2*N*D (inference); MoE uses N_active."""
    n = n_active_params if n_active_params is not None else n_params
    mult = 6.0 if kind == "train" else 2.0
    return mult * float(n) * float(n_tokens)
