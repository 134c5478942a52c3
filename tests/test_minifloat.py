"""Minifloat-6 re-encoding (kernel v2): lossless property + kernel sweep."""
import numpy as np
import jax.numpy as jnp
import pytest

pytest.importorskip("hypothesis", reason="hypothesis not installed")
from hypothesis import given, settings, strategies as st

from repro.core.sme import sme_compress
from repro.core.minifloat import (
    encode6, decode6_value, pack6, unpack6, minifloat_from_sme, minifloat_dequant, bits_per_weight6,
)
from repro.kernels.sme_spmm import sme_linear6_from_weight

RNG = np.random.default_rng(0)


def test_pack_unpack_roundtrip():
    c = RNG.integers(0, 64, size=(16, 128)).astype(np.uint8)
    assert (unpack6(pack6(c)) == c).all()


def test_pack_rows_roundtrip_and_size():
    """The v2 kernel's row-blocked tile layout: lossless, 0.75 B/code,
    and the byte triple of column 0 packs its four row quarters."""
    c = RNG.integers(0, 64, size=(3, 128, 128)).astype(np.uint8)
    packed = pack6(c)
    assert packed.shape == (3, 96, 128)
    assert (unpack6(packed) == c).all()
    q = c[0, ::32, 0].astype(np.uint16)           # rows 0, 32, 64, 96
    assert packed[0, 0, 0] == (q[0] | q[1] << 6) & 0xFF
    assert packed[0, 64, 0] == (q[2] >> 4 | q[3] << 2) & 0xFF
    with pytest.raises(ValueError, match="divisible by 4"):
        pack6(c[:, :6])


@given(seed=st.integers(0, 200), sq=st.integers(1, 3))
@settings(max_examples=20, deadline=None)
def test_encode_decode_lossless_codes(seed, sq):
    """Code-level re-encoding is exact for squeeze>=1, S<=3."""
    rng = np.random.default_rng(seed)
    w = rng.normal(0, 0.3, (64, 64))
    smew = sme_compress(w, n_bits=8, window=3, squeeze=sq, tile=(32, 32))
    # value-domain comparison, float64 scales on both sides
    c6 = encode6(smew.tiled_codes, np.zeros_like(smew.tiled_codes), 8, sq)
    v6 = np.abs(decode6_value(c6, 8, sq))
    v_ref = smew.tiled_codes.astype(np.float64) * 2.0 ** -8
    assert np.abs(v6 - v_ref).max() == 0.0


@pytest.mark.parametrize("sq", [1, 2, 3])
def test_minifloat_dequant_matches_sme(sq):
    w = RNG.normal(0, 0.05, (512, 384))
    smew = sme_compress(w, squeeze=sq)
    mf = minifloat_from_sme(smew)
    rel = np.abs(minifloat_dequant(mf) - smew.dequant()).max() \
        / np.abs(smew.dequant()).max()
    assert rel < 1e-6          # f32 scale rounding only
    assert bits_per_weight6(mf) < 6.5


def test_minifloat_requires_squeeze():
    w = RNG.normal(0, 0.05, (128, 128))
    smew = sme_compress(w, squeeze=0)
    with pytest.raises(ValueError):
        minifloat_from_sme(smew)


@pytest.mark.parametrize("k,n,m", [(128, 128, 4), (300, 500, 9), (256, 384, 1)])
def test_kernel_v2_matches_oracle(k, n, m):
    w = RNG.normal(0, 0.2, (k, n))
    x = RNG.normal(0, 1, (m, k)).astype(np.float32)
    smew = sme_compress(w, squeeze=1)
    y = np.asarray(sme_linear6_from_weight(jnp.asarray(x), smew))
    y_ref = x.astype(np.float64) @ smew.dequant()
    rel = np.abs(y - y_ref).max() / max(np.abs(y_ref).max(), 1e-9)
    assert rel < 5e-5, rel


def test_kernel_v2_block_sparse():
    w = RNG.normal(0, 0.2, (512, 256))
    w[128:384] = 0.0
    x = RNG.normal(0, 1, (5, 512)).astype(np.float32)
    smew = sme_compress(w, squeeze=1)
    assert int(smew.occupancy.sum()) < smew.grid[0] * smew.grid[1]
    y = np.asarray(sme_linear6_from_weight(jnp.asarray(x), smew))
    y_ref = x.astype(np.float64) @ smew.dequant()
    assert np.abs(y - y_ref).max() / np.abs(y_ref).max() < 5e-5
