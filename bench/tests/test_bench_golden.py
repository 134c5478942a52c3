"""The drawn weights, the packed tree and the reference's logit gaps of
the tiny cell, against pinned digests: a change to how a configuration's
weights are drawn, laid out, packed or scored shows here."""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny
import weights
from arch import for_config
from harness import pack

#: per seed, sha256 of the weights' bfloat16 bytes, of the packed tree and
#: of the reference's and the float8 control's gaps on a fixed batch
GOLDEN = {
    1: {"weights": "193a2595af08f67b3ec5fb3676d85cb1"
                   "95ed6d052804b854a6fc263f48708c4e",
        "packed": "7f77c06d09c3ed10f85903cf2c72df6a"
                  "81d77cb54d0416e4b7fa3f03b85a7a4a",
        "gaps": "fe9f713fa5d242e71f92fe3cff064abe"
                "dc6c51ef29828f93c80651f25d56766d"},
    2: {"weights": "a152409398215a1aa0fc1b06f6018a0c"
                   "7481468eeb8762dd9a110d8d860d98ca",
        "packed": "2056e13a83f3e4f51eae659f6a9bfd0d"
                  "8a839c196080242124b1ae2ea8c5ef19",
        "gaps": "3685634308ae2a9c5e1977822c49a185"
                "243f9551b7e8bbf204d53a75bc1659e6"},
}
SEEDS = sorted(GOLDEN)


def _weights(seed):
    cfg = tiny.tiny_cell().config
    return cfg, {k: np.asarray(v)
                 for k, v in weights.make_weights(cfg, seed).items()}


@pytest.mark.parametrize("seed", SEEDS)
def test_drawn_weights(seed):
    _, w = _weights(seed)
    h = hashlib.sha256()
    for name in sorted(w):
        a = w[name]
        h.update(name.encode())
        h.update(str(a.shape).encode())
        h.update(a.view(np.uint16).tobytes())
    assert h.hexdigest() == GOLDEN[seed]["weights"]


@pytest.mark.parametrize("seed", SEEDS)
def test_packed_tree(seed):
    from repro.models import build_model
    cfg, w = _weights(seed)
    api = build_model(for_config(cfg).program_config(cfg))
    packed = pack(weights.program_params(cfg, w, api), cfg["format"])
    h = hashlib.sha256()
    for path, x in jax.tree.leaves_with_path(packed):
        a = np.asarray(x)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    assert h.hexdigest() == GOLDEN[seed]["packed"]


@pytest.mark.parametrize("seed", SEEDS)
def test_logit_gaps(seed):
    cfg, w = _weights(seed)
    rng = np.random.default_rng(0)
    tokens, targets = (rng.integers(0, cfg["vocab_size"], (2, 64)
                                    ).astype(np.int32) for _ in range(2))
    ref = for_config(cfg)
    p = ref.prepare({k: jnp.asarray(v) for k, v in w.items()}, cfg)
    h = hashlib.sha256()
    for round_to in (None, "float8_e4m3fn"):
        g = ref.logit_gaps(p, cfg, tokens, targets, round_to=round_to)
        h.update(np.asarray(g, np.float32).tobytes())
    assert h.hexdigest() == GOLDEN[seed]["gaps"]
