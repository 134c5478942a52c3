"""The plain reference agrees with the serving engine at a tiny size, and
the benchmark's threaded packing equals the program's packing of the
whole stack."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny
import weights
from harness import PACK_LAYERS, pack, program_config, run_cell
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
    api = build_model(program_config(cfg))
    w = {k: np.asarray(v) for k, v in weights.make_weights(cfg, 4).items()}
    packed = pack(weights.program_params(w, api), cfg["format"])
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


def test_threaded_packing_equals_whole_stack():
    from repro.core.integrate import convert_params_to_sme
    from repro.models import build_model
    cfg = dict(tiny.tiny_cell().config,
               num_hidden_layers=PACK_LAYERS + 2)
    api = build_model(program_config(cfg))
    w = {k: np.asarray(v) for k, v in weights.make_weights(cfg, 5).items()}
    fmt = cfg["format"]
    mine = pack(weights.program_params(w, api), fmt)
    whole = convert_params_to_sme(
        weights.program_params(w, api), n_bits=fmt["n_bits"],
        window=fmt["window"], squeeze=fmt["squeeze"],
        tile=(fmt["tile"],) * 2, backend=fmt["pack_backend"])
    a, b = jax.tree.leaves_with_path(mine), jax.tree.leaves_with_path(whole)
    assert [p for p, _ in a] == [p for p, _ in b]
    for (path, x), (_, y) in zip(a, b):
        assert x.shape == y.shape and x.dtype == y.dtype, path
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
