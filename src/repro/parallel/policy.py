# smelint: exact-module
"""Activation-sharding policy (Megatron TP / SP selection per arch x phase).

Models call :func:`constrain` at a few key points (embed output, block
boundaries, post-QKV).  Outside a policy context these are no-ops, so smoke
tests and single-device runs never touch mesh state.  The dry-run / trainer
install a policy chosen per architecture:

  * ``heads_tp=True``  — attention heads divide the model axis: classic TP
    (q/k/v constrained to P(dp, None, 'model', None); k/v pre-repeated to
    full head count so GQA grouping never splits a sharded dim);
  * ``heads_tp=False`` — awkward head counts (qwen2 14H, phi4 24H,
    llava 56H): sequence parallelism — activations P(dp, 'model', None),
    attention heads unsharded, GSPMD all-gathers K/V per layer;
  * decode caches are sequence-sharded over 'model' (+ 'data' when
    global_batch == 1) by the cache sharding rules in ``sharding.py``.
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
from typing import Any, Optional, Tuple

import jax
from jax.sharding import PartitionSpec as P

__all__ = ["ShardPolicy", "use_policy", "constrain", "current_policy",
           "policy_for"]

_POLICY: contextvars.ContextVar = contextvars.ContextVar(
    "shard_policy", default=None)


@dataclasses.dataclass(frozen=True)
class ShardPolicy:
    dp: Tuple[str, ...] = ("data",)     # batch axes
    dp_size: int = 1
    model_size: int = 1
    heads_tp: bool = True               # TP attention heads over 'model'
    seq_axis: Optional[str] = None      # SP axis for activations (train/prefill)
    full_dp: bool = False               # small-model mode: batch over model too
    remat_policy: str = "full"          # full | dots (save dot outputs)
    loss_chunk: int = 0                 # 0 = model default (128)
    exact: bool = False                 # serving posture (DESIGN.md §7):
    #   matmul LHS activations are pinned feature-replicated so GSPMD must
    #   all-gather (exact) instead of partial-summing a sharded
    #   contraction (reassociates floats across devices)
    mesh: Optional[Any] = None          # the Mesh the policy was built for:
    #   Pallas kernel calls, which GSPMD cannot partition, run under a
    #   shard_map over it (core.backend._kernel_call)

    def batch_axes(self, b: int):
        if self.dp_size > 1 and b % self.dp_size == 0:
            return self.dp
        if b % max(self.model_size, 1) == 0 and len(self.dp) == 1:
            return self.dp  # single axis case
        # fall back to the largest prefix of dp axes that divides b
        return None


def policy_for(mesh, cfg, kind: str, full_dp: bool = False) -> ShardPolicy:
    import numpy as np
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    if full_dp:
        dp = dp + ("model",)
    dpn = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1
    msz = mesh.shape.get("model", 1)
    heads_tp = (cfg.n_heads % msz == 0) and kind != "decode" and not full_dp
    seq_axis = None
    if kind in ("train", "prefill") and not heads_tp and not full_dp:
        seq_axis = "model"
    return ShardPolicy(dp=dp, dp_size=dpn, model_size=msz,
                       heads_tp=heads_tp, seq_axis=seq_axis, full_dp=full_dp,
                       mesh=mesh)


@contextlib.contextmanager
def use_policy(policy: Optional[ShardPolicy]):
    tok = _POLICY.set(policy)
    try:
        yield
    finally:
        _POLICY.reset(tok)


def current_policy() -> Optional[ShardPolicy]:
    return _POLICY.get()


def _wsc(x, spec):
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except (ValueError, RuntimeError):
        return x  # no mesh in context (plain CPU run)


def _wsc_hint(x, spec):
    """Placement-hint constraint: skipped when the spec carries no axis.

    An all-None constraint places nothing, but the sharding custom-call it
    inserts still perturbs the partitioner's downstream codegen — on the
    CPU backend a no-op constraint inside a scanned block measurably
    changes float rounding between partition counts, which would break the
    serving exactness contract (DESIGN.md §7).  Only 'lhs' (which must
    *force* replication to exclude sharded contractions) keeps its
    constraint when all-None."""
    if all(ax is None for ax in spec):
        return x
    return _wsc(x, spec)


def constrain(x, kind: str):
    """kind: 'act' [B,S,D] | 'heads' [B,S,H,hd] | 'kv' [B,S,KV,hd]
    | 'features' [..., N] (output features of a sharded matmul)
    | 'lhs' (matmul left operand under the exact serving posture)."""
    pol = current_policy()
    if pol is None:
        return x
    if kind == "lhs":
        # exact posture only: replicate the activation entering a matmul
        # so its contraction dim can never be sharded — GSPMD is forced
        # into the all-gather (bit-exact) strategy, never the partial-sum
        # all-reduce whose float reassociation differs across mesh shapes
        if not pol.exact or pol.model_size <= 1:
            return x
        return _wsc(x, P(*([None] * x.ndim)))
    if kind == "features":
        # output-feature sharding for the SME backend dispatch: the packed
        # operand trees shard whole output columns over 'model', so the
        # splice result lands already sharded the same way — this pins the
        # layout so GSPMD never round-trips it through a gather+reshard
        n = x.shape[-1]
        ax = "model" if (pol.model_size > 1
                         and n % pol.model_size == 0) else None
        return _wsc_hint(x, P(*([None] * (x.ndim - 1) + [ax])))
    b = x.shape[0]
    # exact posture: activations never shard on batch either — XLA:CPU
    # evaluates a row-sharded scan body at a different vector width than
    # the full-batch body (1-ULP transcendental drift between mesh
    # shapes); serving batches are slot-sized, so replicated activations
    # cost nothing while weights/caches keep the sharded-memory win
    bax = (pol.dp if (not pol.exact and pol.dp_size > 1
                      and b % pol.dp_size == 0) else None)
    if kind == "act":
        seq = pol.seq_axis if (pol.seq_axis and
                               x.shape[1] % pol.model_size == 0) else None
        return _wsc_hint(x, P(bax, seq, None))
    if kind == "heads":
        if pol.heads_tp and x.shape[2] % pol.model_size == 0:
            return _wsc_hint(x, P(bax, None, "model", None))
        seq = pol.seq_axis if (pol.seq_axis and
                               x.shape[1] % pol.model_size == 0) else None
        return _wsc_hint(x, P(bax, seq, None, None))
    if kind == "kv":
        # pre-repeated K/V follow the same layout as q heads
        return constrain(x, "heads")
    return x
