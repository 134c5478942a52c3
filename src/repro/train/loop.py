"""Training loop: microbatched (gradient-accumulation) train step, logging.

Microbatching is the activation-memory lever at scale: the global batch is
split into ``micro`` chunks scanned sequentially, gradients accumulated in
the (FSDP-sharded) grad tree.  XLA overlaps the per-microbatch gradient
reduce with the next microbatch's compute where possible.
"""
from __future__ import annotations

import math
import time
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.optim.optim import Optimizer

__all__ = ["pick_microbatches", "make_train_step", "train_loop"]


def pick_microbatches(cfg, shape, dp_size: int,
                      budget_bytes: float = 160e6) -> int:
    """Largest power-of-2 split keeping per-microbatch activations under
    ``budget_bytes`` per device (bf16 [tokens, d_model], MoE-inflated)."""
    b_loc = max(shape.global_batch // max(dp_size, 1), 1)
    moe_f = 1.0 + (cfg.top_k / 2.0 if cfg.n_experts else 0.0)
    # recurrent-state families carry O(B * dh^2) chunk states for backward
    if any(k in ("mlstm", "slstm") for k in cfg.pattern):
        moe_f *= 2.0
    footprint = b_loc * shape.seq_len * cfg.d_model * 2.0 * moe_f
    micro = 1
    while footprint / micro > budget_bytes and micro < b_loc:
        micro *= 2
    return micro


def make_train_step(loss_fn: Callable, optimizer: Optimizer,
                    microbatches: int = 1) -> Callable:
    """loss_fn(params, batch) -> scalar. Returns
    train_step(params, opt_state, step, batch) -> (params, opt_state, loss).
    """

    def train_step(params, opt_state, step, batch):
        if microbatches == 1:
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        else:
            # microbatch i takes rows i, i + micro, ...: the batch axis
            # (data-sharded) stays leading through the reshape, and the
            # new scan axis is unsharded — scan slices its xs along
            # axis 0, which must be replicated
            mb = jax.tree.map(
                lambda x: jnp.swapaxes(
                    x.reshape((x.shape[0] // microbatches, microbatches)
                              + x.shape[1:]), 0, 1), batch)
            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)

            def body(carry, b):
                g_acc, l_acc = carry
                l, g = jax.value_and_grad(loss_fn)(params, b)
                g_acc = jax.tree.map(
                    lambda a, x: a + x.astype(jnp.float32), g_acc, g)
                return (g_acc, l_acc + l), None

            (grads, loss), _ = jax.lax.scan(body, (g0, jnp.float32(0)), mb)
            grads = jax.tree.map(lambda g: g / microbatches, grads)
            loss = loss / microbatches
        new_params, new_state = optimizer.update(grads, opt_state, params, step)
        return new_params, new_state, loss

    return train_step


def train_loop(api, params, optimizer: Optimizer, data_iter,
               n_steps: int, *, microbatches: int = 1,
               log_every: int = 10, hooks: Optional[list] = None,
               jit: bool = True) -> Dict[str, Any]:
    """Single-host training driver used by examples/tests (the multi-pod
    launcher wires the same step through pjit shardings)."""
    step_fn = make_train_step(api.train_loss, optimizer, microbatches)
    if jit:
        step_fn = jax.jit(step_fn, donate_argnums=(0, 1))
    opt_state = optimizer.init(params)
    history = []
    t0 = time.time()
    for i in range(n_steps):
        batch = next(data_iter)
        params, opt_state, loss = step_fn(params, opt_state, jnp.int32(i), batch)
        if i % log_every == 0 or i == n_steps - 1:
            l = float(loss)
            history.append((i, l))
            print(f"step {i:5d} loss {l:.4f} ({time.time()-t0:.1f}s)")
        for h in (hooks or []):
            h(i, params, opt_state, loss)
    return {"params": params, "opt_state": opt_state, "history": history}
