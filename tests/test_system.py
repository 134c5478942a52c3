"""End-to-end behaviour tests: training reduces loss; SME-compressed serving
matches dense; the serving engine completes batched requests; the multi-device
sharding path compiles and runs (subprocess with 8 virtual devices)."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_smoke, ARCHS, scale_down
from repro.models import build_model
from repro.data import lm_batches
from repro.optim import adamw, cosine_schedule
from repro.train import train_loop


def test_lm_training_reduces_loss():
    cfg = get_smoke("qwen2-0.5b")
    api = build_model(cfg)
    params = api.init_params(jax.random.key(0))
    it = (jax.tree.map(jnp.asarray, b)
          for b in lm_batches(cfg.vocab, batch=8, seq=32, seed=0))
    out = train_loop(api, params, adamw(cosine_schedule(3e-3, 10, 60)), it,
                     n_steps=60, log_every=30)
    first, last = out["history"][0][1], out["history"][-1][1]
    assert last < first - 0.5, (first, last)


def test_cnn_training_reduces_loss():
    from repro.models.cnn import resnet_init, resnet_apply, cnn_loss
    from repro.data import image_task
    x, y = image_task(256, size=8)
    params = resnet_init(jax.random.key(0), widths=(8, 16, 24, 32))
    opt = adamw(3e-3)
    state = opt.init(params)
    apply_fn = lambda p, im: resnet_apply(p, im, widths=(8, 16, 24, 32))

    @jax.jit
    def step(params, state, i):
        l, g = jax.value_and_grad(
            lambda p: cnn_loss(apply_fn, p, jnp.asarray(x), jnp.asarray(y)))(params)
        params, state = opt.update(g, state, params, i)
        return params, state, l

    l0 = None
    for i in range(40):
        params, state, l = step(params, state, jnp.int32(i))
        l0 = l0 if l0 is not None else float(l)
    assert float(l) < 0.6 * l0


def test_sme_serving_matches_dense():
    cfg = scale_down(ARCHS["phi4-mini-3.8b"], d_model=256, d_ff=512,
                     head_dim=64, n_heads=4, n_kv_heads=2, vocab=512)
    api = build_model(cfg)
    params = api.init_params(jax.random.key(0))
    batch = {"tokens": jax.random.randint(jax.random.key(1), (2, 16), 0,
                                          cfg.vocab)}
    dense, _ = jax.jit(lambda p, b: api.prefill(p, b, s_max=16))(params, batch)
    from repro.core.integrate import convert_params_to_sme
    smep = convert_params_to_sme(jax.tree.map(np.asarray, params), squeeze=1)
    sme, _ = jax.jit(lambda p, b: api.prefill(p, b, s_max=16))(smep, batch)
    corr = np.corrcoef(np.asarray(dense).ravel(), np.asarray(sme).ravel())[0, 1]
    assert corr > 0.99, corr
    assert (np.asarray(dense).argmax(-1) == np.asarray(sme).argmax(-1)).mean() >= 0.75


def test_serve_engine_completes_requests():
    from repro.serve import ServeEngine, Request
    cfg = get_smoke("qwen1.5-0.5b")
    api = build_model(cfg)
    params = api.init_params(jax.random.key(0))
    eng = ServeEngine(api, params, slots=2, s_max=48)
    reqs = [Request(rid=i, prompt=np.arange(4 + i, dtype=np.int32),
                    max_new_tokens=5) for i in range(4)]
    stats = eng.run(reqs, max_steps=60)
    assert stats["completed"] == 4
    assert all(len(r.out_tokens) >= 5 for r in reqs)


def test_default_mesh_engine_serves_under_jax_defaults():
    """JAX >= 0.9 gives ``jax.make_mesh`` Explicit axes, under which the
    embedding gather had no resolvable out-sharding: the engine's default
    mesh must come from ``launch.mesh.make_mesh`` with Auto axes."""
    from jax.sharding import AxisType
    from repro.serve import ServeEngine, Request
    cfg = get_smoke("qwen1.5-0.5b")
    api = build_model(cfg)
    eng = ServeEngine(api, api.init_params(jax.random.key(0)), slots=1,
                      s_max=16)
    assert all(t == AxisType.Auto for t in eng.mesh.axis_types)
    req = Request(rid=0, prompt=np.arange(3, dtype=np.int32),
                  max_new_tokens=2)
    assert eng.run([req], max_steps=10)["completed"] == 1
    assert len(req.out_tokens) == 2


def test_engine_lower_programs_compile():
    """``lower_programs`` lowers the served prefill and step programs at
    their serving shardings (what the chip smoke inspects for kernels)."""
    from repro.serve import ServeEngine
    cfg = scale_down(ARCHS["qwen1.5-0.5b"], d_model=128, d_ff=256)
    api = build_model(cfg)
    from repro.core.integrate import convert_params_to_sme
    params = convert_params_to_sme(
        jax.tree.map(np.asarray, api.init_params(jax.random.key(0))),
        backend="v1")
    eng = ServeEngine(api, params, slots=2, s_max=32, backend="v1")
    lowered = eng.lower_programs(2, 8)
    assert set(lowered) == {"prefill", "step"}
    for low in lowered.values():
        # off-TPU the kernels run in interpret mode: no Mosaic call
        assert "tpu_custom_call" not in low.compile().as_text()


MULTIDEV_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_smoke
    from repro.models import build_model
    from repro.launch.mesh import make_mesh
    from repro.parallel.sharding import param_sharding, batch_sharding
    from repro.parallel.policy import policy_for, use_policy
    from repro.optim import adamw
    from repro.train import make_train_step

    cfg = get_smoke("qwen1.5-0.5b")
    api = build_model(cfg)
    data, model = (int(a) for a in os.environ["MULTIDEV_MESH"].split(","))
    mesh = make_mesh((data, model))
    params = api.init_params(jax.random.key(0))
    opt = adamw(1e-3)
    opt_state = opt.init(params)
    ps = param_sharding(mesh, params)
    os_ = param_sharding(mesh, opt_state)
    batch = {"tokens": jnp.zeros((4, 32), jnp.int32),
             "labels": jnp.zeros((4, 32), jnp.int32)}
    bs = batch_sharding(mesh, batch)
    rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    step = make_train_step(api.train_loss, opt, microbatches=2)
    pol = policy_for(mesh, cfg, "train")
    with mesh, use_policy(pol):
        fn = jax.jit(step, in_shardings=(ps, os_, rep, bs),
                     out_shardings=(ps, os_, rep))
        p2, s2, loss = fn(jax.device_put(params, ps),
                          jax.device_put(opt_state, os_),
                          jnp.int32(0), jax.device_put(batch, bs))
    assert np.isfinite(float(loss)), loss
    print("MULTIDEV_OK", float(loss))
""")


@pytest.mark.parametrize("mesh", ["2,4", "2,1"])
def test_multidevice_sharded_train_step(mesh):
    """Microbatched train step on a data-sharded batch: the scan over
    microbatches must see an unsharded leading axis."""
    env = {**os.environ, "PYTHONPATH": "src", "MULTIDEV_MESH": mesh}
    r = subprocess.run([sys.executable, "-c", MULTIDEV_SCRIPT],
                       capture_output=True, text=True, env=env,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert "MULTIDEV_OK" in r.stdout, r.stdout + r.stderr


def test_sme_storage_beats_bf16_at_scale():
    from repro.core.sme import sme_compress
    rng = np.random.default_rng(0)
    w = rng.normal(0, 0.05, (1024, 1024))
    smew = sme_compress(w, squeeze=1)
    assert smew.storage_bits_per_weight("bytecode") < 11  # vs 16 bf16
