"""Mesh-native serving: bit-identity across mesh shapes (DESIGN.md §7).

The contract (ISSUE 4): ``ServeEngine`` on any ``(data, model)`` mesh must
emit **bit-identical** tokens to the degenerate 1x1 mesh — the exact-mode
sharding rules only ever split output-feature / head / batch dims, so no
float reduction crosses a device boundary.  Verified for the ragged-batch
suite across dense, SME v1, v2 and v3 (plane-CSC) backends (kernel
backends in interpret mode on CPU), plus the ``.smez`` sharded-load path.

Multi-device cases need forced host devices::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m pytest tests/test_serve_mesh.py

(the CI mesh job runs exactly that); without the flag every >1-device
case skips and only the 1x1 invariants run.
"""
import functools

import numpy as np
import jax
import pytest

from repro.configs import ARCHS, get_smoke, scale_down
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.serve import Request, ServeEngine

RNG = jax.random.key(0)
MESHES = [(1, 1), (2, 2), (4, 1)]
BACKENDS = [None, "v1", "v2", "v3"]


def _need(data, model):
    return pytest.mark.skipif(
        jax.device_count() < data * model,
        reason=f"needs {data * model} devices "
               f"(XLA_FLAGS=--xla_force_host_platform_device_count=8)")


@functools.lru_cache(maxsize=None)
def _build(backend):
    """Smoke model + params shared across mesh cases (one pack per
    backend). SME needs >= 128-dim weights to be eligible."""
    if backend is None:
        cfg = get_smoke("qwen1.5-0.5b")
    else:
        cfg = scale_down(ARCHS["qwen1.5-0.5b"], d_model=128, d_ff=256,
                         vocab=256)
    api = build_model(cfg)
    params = api.init_params(RNG)
    if backend is not None:
        from repro.core.integrate import convert_params_to_sme
        params = convert_params_to_sme(jax.tree.map(np.asarray, params),
                                       squeeze=1, backend=backend)
    return cfg, api, params


def _requests(cfg, seed=0):
    rng = np.random.default_rng(seed)
    lens = (5, 7, 6)
    max_new = (4, 6, 3)
    return [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab, size=lens[i],
                                        dtype=np.int32),
                    max_new_tokens=max_new[i], temperature=0.7 * (i % 2))
            for i in range(3)]


def _serve(cfg, api, params, backend, mesh, seed=0):
    eng = ServeEngine(api, params, slots=2, s_max=32, backend=backend,
                      mesh=mesh, seed=seed)
    reqs = _requests(cfg, seed=seed)
    eng.run(reqs, max_steps=100)
    assert all(r.done for r in reqs)
    return eng, [r.out_tokens for r in reqs]


@pytest.mark.parametrize("data,model",
                         [pytest.param(d, m, marks=_need(d, m))
                          for d, m in MESHES if (d, m) != (1, 1)])
@pytest.mark.parametrize("backend", BACKENDS,
                         ids=[b or "dense" for b in BACKENDS])
def test_mesh_tokens_bit_identical(backend, data, model):
    """Ragged batch on a (data, model) mesh == 1x1 mesh, token for token,
    including per-request temperature sampling."""
    cfg, api, params = _build(backend)
    _, ref = _serve(cfg, api, params, backend, None)
    _, got = _serve(cfg, api, params, backend,
                    make_mesh((data, model)))
    assert got == ref, (
        f"mesh ({data},{model}) diverged from 1x1 for backend "
        f"{backend or 'dense'}: {got} != {ref}")


@pytest.mark.parametrize("arch", ["mixtral-8x7b", "deepseek-v2-lite-16b",
                                  "jamba-v0.1-52b"])
def test_mesh_tokens_bit_identical_arch_families(arch):
    """The ragged-batch suite's architecture families (GQA ring + MoE,
    MLA + MoE, SSM hybrid) are mesh-invariant too — these exercise the
    exact-posture rules the qwen matrix cannot (expert-parallel combine,
    MLA compressed caches and small rope dims under the shard floor,
    recurrent state freezing)."""
    if jax.device_count() < 4:
        pytest.skip("needs 4 devices "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    cfg = get_smoke(arch)
    api = build_model(cfg)
    params = api.init_params(RNG)
    _, ref = _serve(cfg, api, params, None, None)
    _, got = _serve(cfg, api, params, None, make_mesh((2, 2)))
    assert got == ref, f"{arch} diverged on 2x2: {got} != {ref}"


@pytest.mark.parametrize("data,model",
                         [pytest.param(2, 2, marks=_need(2, 2))])
def test_one_decode_per_step_under_sharding(data, model):
    """PR 3's one-jitted-decode-per-step contract must hold on a mesh."""
    cfg, api, params = _build("v1")
    eng = ServeEngine(api, params, slots=2, s_max=32, backend="v1",
                      mesh=make_mesh((data, model)))
    pending = _requests(cfg)
    steps = 0
    while pending or any(r is not None for r in eng.active):
        window = []
        while pending and len(window) < len(eng._free_slots()):
            window.append(pending.pop(0))
        if window:
            eng._admit(window)
        eng.step()
        steps += 1
        assert steps < 200
    assert eng._stats["decode_steps"] == steps


def test_default_engine_is_1x1_mesh():
    """No-mesh construction is the degenerate 1x1 mesh through the same
    code path (no unsharded branch left): same tokens, sharded leaves."""
    cfg, api, params = _build(None)
    _, ref = _serve(cfg, api, params, None, None)
    _, got = _serve(cfg, api, params, None, make_mesh((1, 1)))
    assert got == ref
    eng = ServeEngine(api, params, slots=2, s_max=32)
    assert dict(eng.mesh.shape) == {"data": 1, "model": 1}
    for leaf in jax.tree.leaves(eng.params):
        assert isinstance(leaf, jax.Array) and leaf.committed


def test_param_leaves_actually_shard():
    """On a model-axis mesh the big leaves (embed/lm_head/SME payloads)
    must be split, not replicated."""
    if jax.device_count() < 4:
        pytest.skip("needs 4 devices")
    cfg, api, params = _build("v1")
    eng = ServeEngine(api, params, slots=2, s_max=32, backend="v1",
                      mesh=make_mesh((2, 2)))
    sharded = 0
    for path, leaf in jax.tree_util.tree_leaves_with_path(eng.params):
        names = [str(getattr(p, "key", getattr(p, "idx", p))) for p in path]
        if any(n in ("sme_codes", "sme_sign") or "embed" in n
               for n in names):
            if not leaf.sharding.is_fully_replicated:
                sharded += 1
    assert sharded > 0, "no SME payload/embed leaf was sharded on the mesh"


@pytest.mark.parametrize("data,model",
                         [pytest.param(2, 2, marks=_need(2, 2)),
                          pytest.param(1, 1)])
def test_smez_sharded_load_identity(tmp_path, data, model):
    """from_artifact on a mesh device_puts each .smez leaf straight into
    its computed shard (no host-replicated tree) and serves bit-identical
    tokens to the meshless boot."""
    from repro.compiler.artifact import compile_model
    cfg, api, params = _build("v1")
    art = str(tmp_path / "m.smez")
    compile_model(jax.tree.map(np.asarray, api.init_params(RNG)),
                  out=art, backend="v1",
                  extra={"serve_backend": "v1"})
    ref = ServeEngine.from_artifact(api, art, slots=2, s_max=32)
    reqs_ref = _requests(cfg)
    ref.run(reqs_ref, max_steps=100)

    mesh = make_mesh((data, model))
    eng = ServeEngine.from_artifact(api, art, mesh=mesh, slots=2, s_max=32)
    assert eng.backend == "v1"
    # leaves were placed at load: committed jax arrays under the mesh
    n_sharded = 0
    for leaf in jax.tree.leaves(eng.params):
        assert isinstance(leaf, jax.Array) and leaf.committed
        n_sharded += int(not leaf.sharding.is_fully_replicated)
    if model > 1:
        assert n_sharded > 0, "sharded-load left every leaf replicated"
    reqs = _requests(cfg)
    eng.run(reqs, max_steps=100)
    assert [r.out_tokens for r in reqs] == \
        [r.out_tokens for r in reqs_ref]


def test_hypothesis_ragged_mesh_identity():
    """Property form: random ragged prompt sets are mesh-invariant."""
    hyp = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st
    if jax.device_count() < 4:
        pytest.skip("needs 4 devices")
    cfg, api, params = _build(None)
    mesh = make_mesh((2, 2))

    @settings(max_examples=5, deadline=None)
    @given(seed=st.integers(0, 2 ** 16),
           lens=st.lists(st.integers(1, 12), min_size=1, max_size=4))
    def prop(seed, lens):
        rng = np.random.default_rng(seed)
        def mk():
            return [Request(rid=i, prompt=rng0.integers(
                        0, cfg.vocab, size=n, dtype=np.int32),
                        max_new_tokens=3 + i % 3)
                    for i, n in enumerate(lens)]
        rng0 = np.random.default_rng(seed)
        a = mk()
        rng0 = np.random.default_rng(seed)
        b = mk()
        e1 = ServeEngine(api, params, slots=2, s_max=32, seed=seed)
        e1.run(a, max_steps=100)
        e2 = ServeEngine(api, params, slots=2, s_max=32, seed=seed,
                         mesh=mesh)
        e2.run(b, max_steps=100)
        assert [r.out_tokens for r in a] == [r.out_tokens for r in b]

    prop()
