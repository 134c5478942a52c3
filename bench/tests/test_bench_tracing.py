"""The reduction from a profiler trace to per-layer metrics, on a trace
made by hand and on a slice of a trace recorded on a TPU v5e."""
import json
import pathlib

import pytest

import tiny
import tracing
import work
from arch import for_config
from harness import Ctx
from peaks import PEAKS
from spec import load_cell, metric_reader

FIXTURE = pathlib.Path(__file__).parent / "fixtures" / "trace_decode.json"
V5E = PEAKS["TPU v5 lite"]

# one device; times in ns.  Programs run in [0, 150) and [300, 350).
HAND = {
    "devices": [{"plane": "/device:TPU:0", "modules": [
        ["jit_chunk_fn", 0, 150], ["jit_write_fn", 300, 50],
    ], "ops": [
        ["while.2", 0, 150, ""],
        ["fusion.1", 0, 100, ""],
        ["_v2_call.3", 50, 100, "tpu_custom_call"],
        ["copy-start.4", 60, 200, ""],
        ["copy.2", 300, 50, ""],
    ]}],
    "host": [["bench.window", 0, 1000], ["bench.step", 0, 200],
             ["bench.pump", 200, 50], ["bench.step", 250, 150]],
}


def _ctx(data, traffic="closed-decode"):
    return Ctx(tiny.tiny_cell(traffic), V5E, None, tracing.Trace(data))


def test_hand_trace_numbers():
    t = tracing.Trace(HAND)
    assert t.window_s() == 1000e-9
    assert t.busy_s() == pytest.approx(200e-9)
    assert t.idle_pct() == pytest.approx(80.0)
    steps = t.spans("bench.step")
    assert t.busy_s(steps) == pytest.approx(200e-9)
    kernel = metric_reader("sme_roofline.decode").is_kernel
    assert [o[0] for o in t.ops_in(steps, kernel)] == ["_v2_call.3"]
    assert t.idle_gaps()[:2] == [["total:other", pytest.approx(650e-9)],
                                 ["total:bench.pump", pytest.approx(150e-9)]]
    assert t.top_ops() == [["jit_chunk_fn/fusion", 100e-9],
                           ["jit_chunk_fn/_v2_call", 100e-9],
                           ["jit_write_fn/copy", 50e-9]]


def test_hand_trace_readers():
    ctx = _ctx(HAND)
    assert metric_reader("device_idle.decode").read(ctx) == \
        pytest.approx(80.0)
    assert metric_reader("step_device_ms.decode").read(ctx) == \
        pytest.approx(100e-9 * 1e3)
    cfg, slots = ctx.cell.config, ctx.cell.traffic["slots"]
    least = work.least_time(
        [work.sme_call(slots, k, n)
         for k, n in for_config(cfg).projections(cfg)]
        * cfg["num_hidden_layers"], V5E)
    assert metric_reader("sme_roofline.decode").read(ctx) == \
        pytest.approx(100.0 * 2 * least / 100e-9)


def test_nothing_to_read_gives_nothing():
    empty = {"devices": [], "host": [["bench.window", 0, 1000]]}
    for name in ("device_idle.decode", "step_device_ms.decode",
                 "sme_roofline.decode", "mfu.decode"):
        assert metric_reader(name).read(_ctx(empty)) is None


def _brute_busy(data, lo, hi):
    """Busy ns in [lo, hi) of device 0, by marking every nanosecond
    covered by a program (a reduction independent of ``tracing.union``)."""
    covered = bytearray(hi - lo)
    for _, s, d in data["devices"][0]["modules"]:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            covered[a - lo:b - lo] = b"\x01" * (b - a)
    return sum(covered)


def test_recorded_trace():
    data = json.loads(FIXTURE.read_text())
    t = tracing.Trace(data)
    lo, hi = t.window()
    busy_ns = _brute_busy(data, lo, hi)
    assert t.busy_s() == pytest.approx(busy_ns / 1e9, abs=1e-12)
    assert 0.0 < t.idle_pct() < 100.0
    steps = t.spans("bench.step")
    assert steps
    in_steps = sum(_brute_busy(data, a, b) for a, b in steps)
    assert t.busy_s(steps) == pytest.approx(in_steps / 1e9, abs=1e-12)
    kernel = metric_reader("sme_roofline.decode").is_kernel
    # one decode step: 24 layers of q, k, v, o, gate, up, down
    assert len(t.ops_in(steps, kernel)) == 24 * 7 * len(steps)
    share = metric_reader("sme_roofline.decode").read(
        Ctx(load_cell("qwen15-decode"), V5E, None, t))
    assert 0.0 < share < 100.0
