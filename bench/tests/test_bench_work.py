"""Operations and bytes from shapes, against counts made by hand: the
generic ones of ``work`` and the Qwen2 architecture module's."""
import pytest

import tiny  # noqa: F401  (puts bench/ on the path)
import work
from arch import for_config
from peaks import PEAKS, peaks
from spec import BENCH, read_json

QWEN15 = read_json(BENCH / "configs" / "qwen1.5-0.5b.sme-v2.json")
QWEN2 = for_config(QWEN15)
V5E = PEAKS["TPU v5 lite"]


def test_one_projection_by_hand():
    # qwen1.5's gate projection on the 16 decode rows: 1024 -> 2816
    flops, nbytes = work.sme_call(16, 1024, 2816)
    assert flops == 2 * 16 * 1024 * 2816 == 92_274_688
    # 6-bit weights + f32 column scales + bf16 rows in + f32 rows out
    assert nbytes == 2_162_688 + 11_264 + 32_768 + 180_224 == 2_386_944
    # memory-bound at M = 16: bytes over bandwidth, not flops over peak
    t = work.least_time([(flops, nbytes)], V5E)
    assert t == pytest.approx(2_386_944 / 819e9)
    assert flops / V5E["bf16_flops"] < t


def test_projections_of_a_layer():
    assert QWEN2.__name__ == "arch.Qwen2ForCausalLM"
    assert QWEN2.projections(QWEN15) == [
        (1024, 1024), (1024, 1024), (1024, 1024), (1024, 1024),
        (1024, 2816), (1024, 2816), (2816, 1024)]
    # qwen2-0.5b's widths, GQA: 2 KV heads of 64 -> k and v project 896 -> 128
    q2 = dict(QWEN15, hidden_size=896, num_attention_heads=14,
              num_key_value_heads=2, intermediate_size=4864)
    assert QWEN2.projections(q2)[1:3] == [(896, 128), (896, 128)]


def test_sme_calls_of_a_decode_step():
    # every layer's seven projections on the 16 decode rows, layer by layer
    calls = QWEN2.sme_calls(QWEN15, 16)
    assert len(calls) == 24 * 7
    assert calls[4] == calls[7 * 23 + 4] == work.sme_call(16, 1024, 2816)
    assert calls[:7] == [work.sme_call(16, k, n)
                         for k, n in QWEN2.projections(QWEN15)]


def test_token_flops_by_hand():
    per_layer = 2 * (4 * 1024 * 1024 + 3 * 1024 * 2816)
    attn = 4 * 16 * 64 * 10             # scores and values over 10 positions
    head = 2 * 1024 * 151936
    assert QWEN2.token_flops(QWEN15, 9, False) == 24 * (per_layer + attn)
    assert QWEN2.token_flops(QWEN15, 9, True) == \
        24 * (per_layer + attn) + head


def test_request_flops_counts_each_token_once():
    full = work.request_flops(QWEN15, 5, 3, True, [1, 2])
    prefill = sum(QWEN2.token_flops(QWEN15, p, p == 4) for p in range(5))
    assert full == prefill + QWEN2.token_flops(QWEN15, 5, True) + \
        QWEN2.token_flops(QWEN15, 6, True)
    assert work.request_flops(QWEN15, 5, 3, False, []) == 0


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks("cpu")
