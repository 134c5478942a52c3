"""The decode step's in-place cache contract (DESIGN.md §6).

``lm_decode_step`` carries the stacked block caches through its layer
scan and writes each layer's new rows into the stack in place.  The
oracle is the older form of the same loop, kept here: the stacked caches
go through the scan as ``xs`` and come back as ``ys``, each layer
slicing its own cache out and handing a whole new one back.  Both must
give bit-identical logits and caches under a ragged ``pos`` with some
rows inactive, and the carried step may change no byte besides the
active rows' new entries: not an inactive row's, not another layer's.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, scale_down
from repro.models import transformer as tf
from repro.models.blocks import ATTN_KINDS, block_decode
from repro.models.common import apply_norm, norm_pos_active
from repro.models.model import build_model

#: one case per cache family: GQA full, GQA with windowed rings, MLA with
#: a first dense layer, mamba hybrid, xLSTM (two superblocks each, so
#: the layer index picks one of several)
FAMILIES = {
    "gqa": "qwen1.5-0.5b",
    "gqa-window": "gemma3-12b",
    "mla-first-dense": "deepseek-v2-lite-16b",
    "mamba-hybrid": "jamba-v0.1-52b",
    "xlstm": "xlstm-1.3b",
}
B, S_MAX = 4, 16
#: ragged positions, past the 8-entry windowed ring for some rows; row 1
#: is inactive at a position it would otherwise write
POS = np.array([5, 11, 13, 0], np.int32)
ACTIVE = np.array([True, False, True, True])


def _xs_ys_decode_step(params, token, caches, pos, cfg, active):
    """The layer loop with the stacked caches as the scan's xs and ys."""
    kinds = list(cfg.pattern)
    pos, active = norm_pos_active(pos, active, token.shape[0])
    x = tf._embed_tokens(params, cfg, {"tokens": token})
    x, first = tf._run_first(params, cfg, x, "decode",
                             caches=caches["first"], pos=pos, active=active)

    def body(h, xs):
        slot_params, slot_caches = xs
        new = {}
        for j, kind in enumerate(kinds):
            h, new[f"slot{j}"] = block_decode(
                slot_params[f"slot{j}"], h, slot_caches[f"slot{j}"], pos,
                cfg, kind, cfg.moe_for_slot(j), active=active)
        return h, new

    x, blocks = jax.lax.scan(body, x, (params["blocks"], caches["blocks"]))
    x = apply_norm(x, params["final_norm"], cfg.norm)
    return tf._head_logits(params, cfg, x[:, -1]), {"first": first,
                                                     "blocks": blocks}


def _random_like(tree, key):
    leaves, treedef = jax.tree.flatten(tree)
    keys = jax.random.split(key, len(leaves))
    return treedef.unflatten([
        (0.5 * jax.random.normal(k, l.shape)).astype(l.dtype)
        for k, l in zip(keys, leaves)])


@pytest.mark.parametrize("family", list(FAMILIES))
def test_carried_cache_matches_xs_ys_loop(family):
    base = ARCHS[FAMILIES[family]]
    cfg = scale_down(base, n_layers=base.first_dense_layers
                     + 2 * len(base.pattern))
    assert cfg.n_super == 2
    api = build_model(cfg)
    params = api.init_params(jax.random.key(0))
    # random bytes everywhere, so a stray write cannot hide among zeros
    caches = _random_like(api.init_cache(batch=B, s_max=S_MAX),
                          jax.random.key(1))
    token = jnp.asarray(np.arange(B, dtype=np.int32)[:, None] * 7 + 3)
    pos, active = jnp.asarray(POS), jnp.asarray(ACTIVE)

    carried = jax.jit(functools.partial(tf.lm_decode_step, cfg=cfg))
    oracle = jax.jit(functools.partial(_xs_ys_decode_step, cfg=cfg))
    logits, new = carried(params, token, caches, pos, active=active)
    want_logits, want = oracle(params, token, caches, pos, active=active)

    np.testing.assert_array_equal(np.asarray(logits),
                                  np.asarray(want_logits))
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), new, want)

    for j, kind in enumerate(cfg.pattern):
        for old, got in zip(jax.tree.leaves(caches["blocks"][f"slot{j}"]),
                            jax.tree.leaves(new["blocks"][f"slot{j}"])):
            old, got = np.asarray(old), np.asarray(got)
            # an inactive row keeps every byte, in every layer
            np.testing.assert_array_equal(got[:, ~ACTIVE], old[:, ~ACTIVE])
            if kind not in ATTN_KINDS:
                continue
            # position-indexed [L, B, W, ...]: of all the stack, only the
            # active rows' entries at their own slot in each layer move
            slot = POS % old.shape[2]
            mask = np.zeros(old.shape[:3], bool)
            mask[:, ACTIVE, slot[ACTIVE]] = True
            np.testing.assert_array_equal(got[~mask], old[~mask])
            assert not np.array_equal(got[mask], old[mask])
