"""Launch plumbing: the one mesh helper, published-width configs, the
compilation-cache rule, the device-kind peak table."""
import argparse

import jax
import pytest
from jax.sharding import AxisType

from repro.configs import ARCHS
from repro.hardware.tpu_model import V5E, peak_spec
from repro.launch import cache
from repro.launch.compile import add_scale_args, scaled_config
from repro.launch.mesh import make_mesh, parse_mesh


def test_make_mesh_has_auto_axes():
    mesh = make_mesh((1, 1))
    assert mesh.axis_names == ("data", "model")
    assert all(t == AxisType.Auto for t in mesh.axis_types)


def test_make_mesh_refuses_missing_devices():
    with pytest.raises(ValueError, match="needs"):
        make_mesh((jax.device_count() + 1, 1))


def test_parse_mesh():
    assert parse_mesh("2,2") == (2, 2)
    with pytest.raises(ValueError):
        parse_mesh("4")


def _cfg(*argv):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    add_scale_args(ap)
    return scaled_config(ap.parse_args(list(argv)))


@pytest.mark.parametrize("argv,published", [
    ((), True), (("--smoke",), False), (("--d-model", "128"), False)])
def test_published_widths_unless_scaled(argv, published):
    cfg = _cfg(*argv)
    full = ARCHS["qwen1.5-0.5b"]
    assert (cfg == full) == published
    if not published:
        assert cfg.n_layers == 1 and cfg.vocab == 256


def test_compile_cache_honours_env(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    before = jax.config.jax_compilation_cache_dir
    assert cache.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_fixed_path_in_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = cache.enable_compile_cache()
        assert path == str(cache._CHECKOUT / ".jax_cache")
        assert (cache._CHECKOUT / "src" / "repro").is_dir()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_peaks_keyed_by_device_kind():
    assert peak_spec("TPU v5 lite") is V5E
    with pytest.raises(KeyError, match="no peak table entry"):
        peak_spec("cpu")
