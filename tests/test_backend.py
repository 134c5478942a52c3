"""Execution-backend layer: registry dispatch, kernel-vs-oracle equivalence
across backends, pack vectorization regressions, meta threading."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.sme import (
    sme_compress, sme_matmul_ref_np, pack_csc_reference,
)
from repro.core import backend as B
from repro.core.integrate import convert_params_to_sme, pack_sme_param
from repro.models.common import linear

RNG = np.random.default_rng(11)

BACKENDS = ("xla", "v1", "v2", "v3")


def _param(w, squeeze=1, n_bits=8, emit=None):
    return {k: jnp.asarray(v)
            for k, v in pack_sme_param(w, n_bits=n_bits, squeeze=squeeze,
                                       backend=emit).items()}


def _rel(y, y_ref):
    return np.abs(np.asarray(y, np.float64) - y_ref).max() \
        / max(np.abs(y_ref).max(), 1e-9)


# ----------------------------------------------------------------- registry
def test_registry_contents():
    for name in BACKENDS:
        assert name in B.available_backends()
        assert B.get_backend(name).name == name
    with pytest.raises(KeyError):
        B.get_backend("nope")


def test_use_backend_scoping():
    base = B.default_backend()
    with B.use_backend("v1"):
        assert B.default_backend() == "v1"
        with B.use_backend(None):            # no-op nesting
            assert B.default_backend() == "v1"
    assert B.default_backend() == base


def test_resolve_prefers_packed_operands():
    w = RNG.normal(0, 0.3, (256, 256))
    # on any host, auto picks the backend whose operands are present
    # (v2 over v3 over v1); with none packed, non-TPU hosts resolve to xla
    assert B.resolve_backend(_param(w, emit="v1")).name == "v1"
    assert B.resolve_backend(_param(w, emit="v3")).name == "v3"
    assert B.resolve_backend(_param(w, emit="all")).name == "v2"
    if jax.default_backend() != "tpu":
        assert B.resolve_backend(_param(w)).name == "xla"


# ------------------------------------------------- oracle equivalence sweep
@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("k,n", [(256, 384), (300, 500), (130, 129)])
def test_backend_matches_oracle_odd_shapes(backend, k, n):
    w = RNG.normal(0, 0.3, (k, n))
    smew = sme_compress(w, squeeze=1)
    x = RNG.normal(0, 1, (9, k)).astype(np.float32)
    y = B.sme_apply(jnp.asarray(x), _param(w), backend)
    assert y.shape == (9, n)
    assert _rel(y, sme_matmul_ref_np(x, smew)) < 5e-5, (backend, k, n)


@pytest.mark.parametrize("backend", BACKENDS)
def test_backend_empty_tile_heavy(backend):
    """Mostly-empty weight: CSC skipping must not change numerics."""
    w = RNG.normal(0, 0.3, (512, 384))
    w[128:512] = 0.0                     # 3 of 4 row-tiles empty
    w[:, :128] = 0.0                     # first col-tile fully empty (nnz=0)
    smew = sme_compress(w, squeeze=1)
    assert int(smew.occupancy.sum()) < smew.grid[0] * smew.grid[1]
    x = RNG.normal(0, 1, (5, 512)).astype(np.float32)
    y = B.sme_apply(jnp.asarray(x), _param(w), backend)
    assert _rel(y, sme_matmul_ref_np(x, smew)) < 5e-5


@pytest.mark.parametrize("backend", BACKENDS)
def test_backend_batched_leading_dims(backend):
    w = RNG.normal(0, 0.3, (256, 200))
    smew = sme_compress(w, squeeze=1)
    x = RNG.normal(0, 1, (2, 3, 256)).astype(np.float32)
    y = B.sme_apply(jnp.asarray(x), _param(w), backend)
    assert y.shape == (2, 3, 200)
    y_ref = sme_matmul_ref_np(x.reshape(-1, 256), smew).reshape(2, 3, 200)
    assert _rel(y, y_ref) < 5e-5


@pytest.mark.parametrize("backend", BACKENDS)
def test_backend_stacked_experts(backend):
    """[E, D, F] MoE-style weights: per-expert kernel dispatch."""
    E, D, F = 3, 256, 128
    wi = RNG.normal(0, 0.3, (E, D, F))
    p = convert_params_to_sme({"wi": wi}, squeeze=1)["wi"]
    x = RNG.normal(0, 1, (E, 4, D)).astype(np.float32)
    y = B.sme_apply(jnp.asarray(x), p, backend)
    assert y.shape == (E, 4, F)
    y_ref = np.stack([
        sme_matmul_ref_np(x[e], sme_compress(wi[e], squeeze=1))
        for e in range(E)])
    assert _rel(y, y_ref) < 5e-5


def test_backends_agree_under_jit_with_operands():
    """Pre-packed operands run the Pallas kernels inside jitted programs."""
    w = RNG.normal(0, 0.3, (256, 256))
    smew = sme_compress(w, squeeze=1)
    x = RNG.normal(0, 1, (4, 256)).astype(np.float32)
    p = _param(w, emit="all")
    y_ref = sme_matmul_ref_np(x, smew)
    for backend in BACKENDS:
        f = jax.jit(lambda a, q: B.sme_apply(a, q, backend))
        assert _rel(f(jnp.asarray(x), p), y_ref) < 5e-5, backend


def test_traced_without_operands_falls_back_to_xla():
    w = RNG.normal(0, 0.3, (256, 256))
    smew = sme_compress(w, squeeze=1)
    x = RNG.normal(0, 1, (4, 256)).astype(np.float32)
    p = _param(w)                                      # no kernel operands
    y = jax.jit(lambda a, q: B.sme_apply(a, q, "v1"))(jnp.asarray(x), p)
    assert _rel(y, sme_matmul_ref_np(x, smew)) < 1e-4


# ------------------------------------------------------------ pack once
def test_operand_cache_packs_once():
    w = RNG.normal(0, 0.3, (256, 256))
    p = _param(w)
    B.clear_operand_cache()
    x = jnp.asarray(RNG.normal(0, 1, (3, 256)), jnp.float32)
    B.sme_apply(x, p, "v1")
    be = B.get_backend("v1")
    ops1 = B._cached_operands(p, be)
    B.sme_apply(x, p, "v1")
    assert B._cached_operands(p, be) is ops1           # identity: no repack
    B.clear_operand_cache()


# ------------------------------------------- pack vectorization regressions
@pytest.mark.parametrize("k,n,squeeze", [(300, 500, 1), (256, 384, 0),
                                         (130, 129, 2), (512, 384, 1)])
def test_pack_csc_vectorized_bit_identical(k, n, squeeze):
    w = RNG.normal(0, 0.3, (k, n))
    w[: k // 2] = 0.0                     # force empty tiles + ragged nnz
    smew = sme_compress(w, squeeze=squeeze)
    fast, ref = smew.pack_csc(), pack_csc_reference(smew)
    assert set(fast) == set(ref)
    for key in ref:
        assert fast[key].dtype == ref[key].dtype, key
        assert (fast[key] == ref[key]).all(), key


def test_pack_csc_pad_to_bit_identical():
    w = RNG.normal(0, 0.3, (384, 384))
    w[128:256] = 0.0
    smew = sme_compress(w, squeeze=1)
    L = int(smew.occupancy.sum(axis=0).max()) + 2
    fast, ref = smew.pack_csc(pad_to=L), pack_csc_reference(smew, pad_to=L)
    for key in ref:
        assert (fast[key] == ref[key]).all(), key


def test_pack_operands6_vectorized_matches_loop():
    """v2 CSC gather vs the seed per-tile loop (minifloat encode path)."""
    from repro.core.minifloat import encode6, pack6
    w = RNG.normal(0, 0.3, (384, 256))
    w[:128] = 0.0
    smew = sme_compress(w, squeeze=1)
    fast = B.get_backend("v2").pack_weight(smew)
    csc = pack_csc_reference(smew)
    nt, L = csc["rowid"].shape
    tr, tc = smew.tile
    signs_t = smew.sign_tiled()
    packed = np.zeros((nt, L, 3 * tr // 4, tc), np.uint8)
    occ = smew.occupancy
    for j in range(nt):
        rows = np.nonzero(occ[:, j])[0]
        for l, i in enumerate(rows):
            c6 = encode6(smew.tiled_codes[i, j], signs_t[i, j],
                         smew.n_bits, smew.squeezed)
            packed[j, l] = pack6(c6)
    assert (fast["packed"] == packed).all()
    for key in ("rowscale", "rowid", "nnz"):
        assert (fast[key] == csc[key]).all(), key


# ----------------------------------------------------------- meta threading
@pytest.mark.parametrize("n_bits", [6, 8])
def test_nbits_threads_through_linear(n_bits):
    """Non-8-bit conversions must dequantize with their own n_bits."""
    w = RNG.normal(0, 0.3, (256, 256))
    smew = sme_compress(w, n_bits=n_bits, squeeze=1)
    p = _param(w, n_bits=n_bits)
    assert int(np.asarray(p["sme_nbits"])) == n_bits
    x = RNG.normal(0, 1, (4, 256)).astype(np.float32)
    y = linear(jnp.asarray(x), {"w": p}, backend="xla")
    assert _rel(y, sme_matmul_ref_np(x, smew)) < 5e-5


def test_nbits_threads_through_kernel_backend():
    w = RNG.normal(0, 0.3, (256, 256))
    smew = sme_compress(w, n_bits=6, squeeze=1)
    x = RNG.normal(0, 1, (4, 256)).astype(np.float32)
    y = B.sme_apply(jnp.asarray(x), _param(w, n_bits=6), "v1")
    assert _rel(y, sme_matmul_ref_np(x, smew)) < 5e-5


def test_v2_rejects_unsqueezed():
    w = RNG.normal(0, 0.3, (256, 256))
    smew = sme_compress(w, squeeze=0)
    with pytest.raises(ValueError):
        B.get_backend("v2").pack_weight(smew)


# ------------------------------------------------------------- model routes
def test_moe_routes_through_kernel_backend():
    """moe_apply numerics are backend-invariant for packed expert weights."""
    from repro.models.moe import moe_init, moe_apply
    from repro.models.common import Initializer

    class Cfg:
        d_model, n_experts, expert_dff = 128, 2, 128
        top_k, capacity_factor, n_shared_experts = 1, 1.25, 0

    cfg = Cfg()
    init = Initializer(jax.random.key(0))
    p = jax.tree.map(np.asarray, moe_init(init, cfg))
    x = jnp.asarray(RNG.normal(0, 1, (1, 8, 128)), jnp.float32)
    y_dense = moe_apply(p, x, cfg)
    ps = convert_params_to_sme(p, squeeze=1, backend="v1")
    outs = {}
    for backend in BACKENDS:
        with B.use_backend(backend):
            outs[backend] = np.asarray(moe_apply(ps, x, cfg))
    y_sme = outs["xla"]
    assert np.corrcoef(np.asarray(y_dense).ravel(),
                       y_sme.ravel())[0, 1] > 0.99
    for backend in ("v1", "v2"):
        assert np.abs(outs[backend] - y_sme).max() \
            / max(np.abs(y_sme).max(), 1e-9) < 2e-2, backend


def test_serve_engine_with_kernel_backend():
    """End-to-end: packed weights + v1 backend through prefill/decode.

    The model must be >= 128-dim so its weights are actually SME-eligible
    and the engine's jitted programs run the Pallas kernel (interpret
    mode on CPU)."""
    from repro.configs import ARCHS, scale_down
    from repro.models import build_model
    from repro.serve import ServeEngine, Request

    cfg = scale_down(ARCHS["qwen1.5-0.5b"], d_model=128, d_ff=256,
                     head_dim=32, n_heads=4, n_kv_heads=4, vocab=256,
                     n_layers=1)
    api = build_model(cfg)
    params = api.init_params(jax.random.key(0))
    ps = convert_params_to_sme(jax.tree.map(np.asarray, params), squeeze=1,
                               backend="v1")
    assert any("sme_v1_codes" in str(p)
               for p, _ in jax.tree_util.tree_leaves_with_path(ps)), \
        "no weight was SME-converted; test config ineligible"
    eng = ServeEngine(api, ps, slots=2, s_max=32, backend="v1")
    reqs = [Request(rid=i, prompt=np.arange(3 + i, dtype=np.int32),
                    max_new_tokens=2) for i in range(2)]
    stats = eng.run(reqs, max_steps=20)
    assert stats["completed"] == 2
    assert all(len(r.out_tokens) >= 2 for r in reqs)


# ------------------------------------------------------- no fallback on TPU
@pytest.fixture
def on_tpu(monkeypatch):
    """Make the backend layer see a TPU default backend (no chip needed:
    both guards below refuse before any kernel is built)."""
    monkeypatch.setattr(B.jax, "default_backend", lambda: "tpu")


def test_interpret_mode_refused_on_tpu(on_tpu):
    assert B._resolve_interpret(None) is False
    assert B._resolve_interpret(False) is False
    with pytest.raises(ValueError, match="interpret mode"):
        B._resolve_interpret(True)


def test_interpret_mode_default_off_tpu():
    assert B._resolve_interpret(None) is True
    assert B._resolve_interpret(False) is False


def test_traced_codes_without_operands_refused_on_tpu(on_tpu):
    """Under jit the raw codes are traced and cannot be packed: off-TPU
    sme_apply falls back to xla, on a TPU it must refuse instead."""
    p = _param(RNG.normal(0, 0.3, (256, 256)))
    x = jnp.ones((4, 256), jnp.float32)
    with pytest.raises(ValueError, match="no packed operands"):
        jax.jit(lambda x, p: B.sme_apply(x, p, "v2")).lower(x, p)


def test_auto_resolves_to_a_kernel_on_tpu(on_tpu):
    assert B.resolve_backend(None, "auto").name == "v2"
    p = _param(RNG.normal(0, 0.3, (256, 256)))
    assert B.resolve_backend(p, "auto").name != "xla"
