"""The plain reference agrees with the serving engine at a tiny size, and
the benchmark's threaded packing equals the program's packing of the
whole tree, for a dense tree and for one with a first dense layer, MLA
leaves and stacked experts."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny
import harness  # after tiny, which puts bench/ on the path
import weights
from arch import for_config
from harness import PACK_LAYERS, cuts, pack, run_cell
from reference import qwen


@pytest.mark.parametrize("seed", [11, 13])
def test_served_tokens_match_the_reference(seed):
    out = run_cell(tiny.tiny_cell(), seed, 2.0, False,
                   time.perf_counter(), require_tpu=False, cache=False)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["checks"]["wrong_lengths"]["value"] == 0


def test_reference_logits_match_the_engine():
    """Prefill logits of the program on the packed weights against the
    reference's on the same weights: bf16 activations against float32."""
    from repro.models import build_model
    cfg = tiny.tiny_cell().config
    api = build_model(for_config(cfg).program_config(cfg))
    w = {k: np.asarray(v) for k, v in weights.make_weights(cfg, 4).items()}
    packed = pack(weights.program_params(cfg, w, api), cfg["format"])
    toks = np.random.default_rng(0).integers(
        0, cfg["vocab_size"], (2, 16)).astype(np.int32)
    got, _ = jax.jit(lambda p, t: api.prefill(p, {"tokens": t}, s_max=32))(
        packed, jnp.asarray(toks))
    p_ref = qwen.prepare({k: jnp.asarray(v) for k, v in w.items()}, cfg)
    h = qwen.hidden(p_ref, cfg, jnp.asarray(toks))[:, -1:]
    want = qwen._head(p_ref, cfg, h)[:, 0]
    got = np.asarray(got, np.float32)
    scale = float(jnp.abs(want).max())
    assert float(np.abs(got - np.asarray(want)).max()) < 0.02 * scale
    assert (got.argmax(-1) == np.asarray(want).argmax(-1)).all()


def _qwen_tree():
    """The tiny qwen cell's tree, 6 layers stacked in one slot."""
    from repro.models import build_model
    cfg = dict(tiny.tiny_cell().config,
               num_hidden_layers=PACK_LAYERS + 2)
    api = build_model(for_config(cfg).program_config(cfg))
    w = {k: np.asarray(v) for k, v in weights.make_weights(cfg, 5).items()}
    return weights.program_params(cfg, w, api)


def _deepseek_tree():
    """The program's own DeepSeek-V2-Lite tree at its smoke depth plus one
    layer (a first dense layer, two MoE layers stacked, four experts and
    two shared, an untied head), every width 128 so that each projection
    passes the program's 128-wide floor and is packed."""
    from repro.configs import ARCHS, scale_down
    from repro.models import build_model
    w = 128
    cfg = scale_down(ARCHS["deepseek-v2-lite-16b"], n_layers=3, d_model=w,
                     d_ff=w, expert_dff=w, kv_lora=w, head_dim=w,
                     rope_head_dim=w, nope_head_dim=w, v_head_dim=w)
    api = build_model(cfg)
    return jax.tree.map(np.asarray, api.init_params(jax.random.key(5)))


#: per tree, its builder and a PACK_ELEMENTS that cuts it finer than
#: PACK_LAYERS does: two layers of the widest qwen projection; two
#: experts of one layer, so that each MoE layer's experts are cut
TREES = {"qwen-tiny": (_qwen_tree, 2 * 128 * 256),
         "deepseek-v2-lite": (_deepseek_tree, 2 * 128 * 128)}


@pytest.mark.parametrize("name", sorted(TREES))
def test_threaded_packing_equals_whole_stack(name, monkeypatch):
    from repro.core.integrate import convert_params_to_sme
    make, elements = TREES[name]
    monkeypatch.setattr(harness, "PACK_ELEMENTS", elements)
    fmt = tiny.tiny_cell().config["format"]
    tree = make()
    mine = pack(tree, fmt)
    whole = convert_params_to_sme(
        tree, n_bits=fmt["n_bits"], window=fmt["window"],
        squeeze=fmt["squeeze"], tile=(fmt["tile"],) * 2,
        backend=fmt["pack_backend"])
    a, b = jax.tree.leaves_with_path(mine), jax.tree.leaves_with_path(whole)
    assert [p for p, _ in a] == [p for p, _ in b]
    assert any("sme_codes" in jax.tree_util.keystr(p) for p, _ in a)
    for (path, x), (_, y) in zip(a, b):
        assert x.shape == y.shape and x.dtype == y.dtype, path
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _cut_shapes(shape, c):
    """The shape of each job of the cuts ``c`` of a leaf of ``shape``."""
    return [np.empty(shape, np.int8)[idx].shape for idx in harness._jobs(c)]


def test_cuts_keep_each_job_within_the_cap():
    # the first configuration's projections: 6 jobs of 4 layers each
    for kn in ((1024, 1024), (1024, 2816), (2816, 1024)):
        assert _cut_shapes((24,) + kn, cuts((24,) + kn)) == \
            [(4,) + kn] * 6
    # one chip's share of DeepSeek-V2-Lite's experts: 8 of 64 a layer;
    # a layer of them is past the cap, so each layer is cut by experts
    e = (26, 8, 2048, 1408)
    jobs = _cut_shapes(e, cuts(e))
    assert jobs == [(1, 4, 2048, 1408)] * 52
    assert max(np.prod(j) for j in jobs) <= harness.PACK_ELEMENTS
    # one matrix past the cap is one job; a stack of them, one a layer
    assert cuts((2048, 10944)) is None
    assert _cut_shapes((3, 2048, 10944), cuts((3, 2048, 10944))) == \
        [(1, 2048, 10944)] * 3
