"""The program's spans and scopes read from a profiler trace: the six
readers on a trace made by hand, on a program without spans or scopes,
and on a slice of a trace recorded on a TPU v5e; the readings the
benchmark had before them left as they were."""
import copy
import json
import pathlib

import jax
import numpy as np
import pytest

import tiny
import tracing
import program_trace
from harness import Ctx
from peaks import PEAKS
from spec import load_cell, metric_reader

FIXTURES = pathlib.Path(__file__).parent / "fixtures"
V5E = PEAKS["TPU v5 lite"]
OLD_READERS = ("device_idle.decode", "step_device_ms.decode",
               "sme_roofline.decode")

# one device; times in ns.  Two steps: the step program runs in
# [45, 150) and [275, 355), each launched by its serve.step.dispatch; the
# host spans tile each serve.step.
HAND = {
    "devices": [{"plane": "/device:TPU:0", "modules": [
        ["jit_chunk_fn", 45, 105], ["jit_chunk_fn", 275, 80],
    ], "ops": [
        ["while.2", 45, 105, ""],
        ["fusion.1", 45, 100, ""],
        ["_v2_call.3", 50, 100, "tpu_custom_call"],
        ["copy-start.4", 60, 200, ""],
        ["copy.2", 275, 50, ""],
        ["fusion.9", 310, 40, ""],
    ], "scopes": [None, "attention", None, None, "kv_cache", "lm_head"]}],
    "host": [["bench.window", 0, 1000], ["bench.step", 0, 200],
             ["bench.pump", 200, 50], ["bench.step", 250, 150]],
    "program": [
        ["serve.step", 10, 180, {"active": 3, "tokens": 3}],
        ["serve.step.plan", 10, 30, {}], ["serve.step.dispatch", 40, 20, {}],
        ["serve.step.wait", 60, 100, {}], ["serve.step.emit", 160, 30, {}],
        ["serve.pump", 200, 48, {}],
        ["serve.admit", 205, 40, {"n_reqs": 2, "pad_to": 8}],
        ["serve.step", 260, 130, {"active": 3, "tokens": 3}],
        ["serve.step.plan", 260, 10, {}], ["serve.step.dispatch", 270, 10, {}],
        ["serve.step.wait", 280, 90, {}], ["serve.step.emit", 370, 20, {}],
    ],
}
#: per reader, its reading of ``HAND`` in ms
HAND_MS = {"step_host_ms.decode": (80 + 40) / 2 / 1e6,
           "dispatch_ms.decode": (20 + 10) / 2 / 1e6,
           "admit_ms_per_req.decode": 40 / 2 / 1e6,
           "attn_ms.decode": 100 / 2 / 1e6,
           "kv_cache_ms.decode": 50 / 2 / 1e6,
           "lm_head_ms.decode": 40 / 2 / 1e6}


def _ctx(data, cell=None):
    return Ctx(cell or tiny.tiny_cell(), V5E, None, tracing.Trace(data))


@pytest.mark.parametrize("name", sorted(HAND_MS))
def test_hand_trace_reader(name):
    assert metric_reader(name).read(_ctx(HAND)) == pytest.approx(
        HAND_MS[name])


def test_scopes_and_unscoped_add_up_to_the_ops():
    prog = program_trace.program(_ctx(HAND))
    by = prog.scoped_ns(prog.trace.spans("bench.step"))
    # the SME kernel is the one op no scope names; the while and the
    # async copy are left out as in top_ops
    assert by == {"attention": 100, "kv_cache": 50, "lm_head": 40,
                  "unscoped": 100}


def test_device_stamps_ahead_of_the_host_are_moved_back():
    """Device stamps 50 ns early put the first step's attention op before
    its bench.step opens; the skew bound (the step program cannot start
    before its dispatch span) brings it back inside."""
    early = copy.deepcopy(HAND)
    dev = early["devices"][0]
    dev["modules"] = [[n, s - 50, d] for n, s, d in dev["modules"]]
    dev["ops"] = [[n, s - 50, d, t] for n, s, d, t in dev["ops"]]
    prog = program_trace.program(_ctx(early))
    assert prog.skew_ns(dev) == 45
    assert prog.skew_ns(HAND["devices"][0]) == 0
    for name in ("attn_ms.decode", "kv_cache_ms.decode",
                 "lm_head_ms.decode"):
        assert metric_reader(name).read(_ctx(copy.deepcopy(early))) == \
            pytest.approx(HAND_MS[name])


def _bare_capture(trace_dir) -> None:
    """A CPU profiler capture of a program with no ``serve.*`` spans and
    no scopes, as the parent of the spans leaves one."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        np.asarray(jax.jit(lambda x: x + 1)(np.arange(4.0)))
    jax.profiler.stop_trace()


def test_a_program_without_spans_or_scopes_reads_nothing(tmp_path,
                                                         monkeypatch):
    """The parent of the spans: its trace holds neither, and a benchmark
    run of it reads its xplane from the trace directory."""
    _bare_capture(tmp_path)
    monkeypatch.setattr(program_trace, "trace_dir", lambda name: tmp_path)
    bare = copy.deepcopy(HAND)
    del bare["program"], bare["devices"][0]["scopes"]
    for data in ({**bare, "program": []}, bare):
        for name in sorted(HAND_MS):
            assert metric_reader(name).read(_ctx(copy.deepcopy(data))) \
                is None, name


def test_a_traced_run_without_its_xplane_raises(tmp_path, monkeypatch):
    """A trace directory that moved must not read as a program without
    spans: the readers raise instead of returning None."""
    monkeypatch.setattr(program_trace, "trace_dir", lambda name: tmp_path)
    bare = copy.deepcopy(HAND)
    del bare["program"], bare["devices"][0]["scopes"]
    for name in sorted(HAND_MS):
        with pytest.raises(FileNotFoundError):
            metric_reader(name).read(_ctx(copy.deepcopy(bare)))


def test_scope_of_reads_the_innermost_scope():
    path = "jit(chunk_fn)/while/body/closed_call/{}/dot_general:"
    assert program_trace.scope_of(path.format("attention")) == "attention"
    assert program_trace.scope_of("a/lm_head/b/kv_cache/c") == "kv_cache"
    assert program_trace.scope_of("jit(chunk_fn)/attention_mask/mul") is None
    assert program_trace.scope_of("") is None


def _pb(*fields) -> bytes:
    """A protobuf message of ``(field, value)`` pairs: ints as varints,
    text, bytes and nested messages length-delimited."""
    def varint(v):
        out = b""
        while True:
            out += bytes([(v & 0x7F) | (0x80 if v > 0x7F else 0)])
            v >>= 7
            if not v:
                return out
    out = b""
    for f, v in fields:
        if isinstance(v, int):
            out += varint(f << 3) + varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += varint(f << 3 | 2) + varint(len(v)) + v
    return out


def _xspace() -> bytes:
    """One TPU plane: two programs (ids 7 and 8) whose ops share the name
    ``fusion.1``, scoped differently; a third op with no scope."""
    events = {1: ("jit_chunk_fn(7)", []), 2: ("jit_chunk_fn(8)", []),
              3: ("%fusion.1 = f32[2] fusion(f32[2] %x)",
                  [(1, "jit(chunk_fn)/while/body/attention/dot_general:"),
                   (2, 7)]),
              4: ("%fusion.1 = f32[2] fusion(f32[2] %x)",
                  [(1, "jit(chunk_fn)/while/body/kv_cache/copy:"), (2, 8)]),
              5: ("%_v2_call.3 = f32[2] custom-call(f32[2] %x), "
                  'custom_call_target="tpu_custom_call"',
                  [(1, "jit(chunk_fn)/jit(_v2_call)/pallas_call:"),
                   (2, 7)])}

    def stat(sid, v):
        return _pb((1, sid), (5 if isinstance(v, str) else 3, v))
    meta = [(4, _pb((1, i), (2, _pb((1, i), (2, n), *[
        (5, stat(sid, v)) for sid, v in st]))))
        for i, (n, st) in events.items()]
    stat_meta = [(5, _pb((1, i), (2, _pb((1, i), (2, n)))))
                 for i, n in ((1, "tf_op"), (2, "program_id"))]

    def line(name, evs):
        return (3, _pb((2, name), (3, 1_000), *[
            (4, _pb((1, m), (2, t * 1000), (3, d * 1000)))
            for m, t, d in evs]))
    plane = _pb((1, 1), (2, "/device:TPU:0"),
                line("XLA Modules", [(1, 0, 100), (2, 200, 100)]),
                line("XLA Ops", [(3, 10, 50), (5, 60, 30), (4, 210, 50)]),
                *meta, *stat_meta)
    return _pb((1, plane))


def test_extract_reads_scopes_from_the_op_metadata():
    data = program_trace.extract(_xspace())
    [dev] = data["devices"]
    assert [(o[0], o[1]) for o in dev["ops"]] == [
        ("fusion.1", 1_010), ("_v2_call.3", 1_060), ("fusion.1", 1_210)]
    assert dev["scopes"] == ["attention", None, "kv_cache"]
    assert [m[0] for m in dev["modules"]] == ["jit_chunk_fn"] * 2


def test_added_keys_leave_the_recorded_readings_as_they_were():
    data = json.loads((FIXTURES / "trace_decode.json").read_text())
    before = tracing.Trace(copy.deepcopy(data))
    more = copy.deepcopy(data)
    more["program"] = []
    for dev in more["devices"]:
        dev["scopes"] = {}
    after = tracing.Trace(more)
    assert after.busy_s() == before.busy_s()
    assert after.idle_pct() == before.idle_pct()
    assert after.top_ops() == before.top_ops()
    assert after.idle_gaps() == before.idle_gaps()
    cell = load_cell("qwen15-decode")
    for name in OLD_READERS[:3]:
        assert metric_reader(name).read(Ctx(cell, V5E, None, after)) == \
            metric_reader(name).read(Ctx(cell, V5E, None, before)), name


def test_extract_adds_to_what_tracing_extract_keeps(tmp_path):
    """On a CPU capture with the engine's kind of spans: ``extract`` holds
    exactly ``tracing.extract``'s reduction, plus the ``serve.*`` spans
    with their arguments."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("serve.admit") as span:
            np.asarray(jax.jit(lambda x: x * 2)(np.arange(4.0)))
            span.set_metadata(n_reqs=3, pad_to=8)
    jax.profiler.stop_trace()
    [path] = tmp_path.glob("**/*.xplane.pb")
    data = program_trace.read_dir(str(tmp_path))
    for dev in data["devices"]:
        del dev["scopes"]
    assert {k: data[k] for k in ("devices", "host")} == tracing.extract(
        jax.profiler.ProfileData.from_file(str(path)))
    [(name, _, dur, args)] = data["program"]
    assert name == "serve.admit" and dur > 0
    assert args["n_reqs"] == 3 and args["pad_to"] == 8


def test_recorded_scoped_trace():
    """A slice of a traced qwen15-decode run, measured on one TPU v5e chip:
    one admission and the decode step after it.  Every reader finds its
    spans or scopes; the step's four phases tile it; the scoped and
    unscoped op time adds up to the step's device time; the SME kernels
    and the KV-slab copies of the layer scan are unscoped."""
    data = json.loads((FIXTURES / "trace_decode_scoped.json").read_text())
    ctx = Ctx(load_cell("qwen15-decode"), V5E, None, tracing.Trace(data))
    values = {n: metric_reader(n).read(ctx) for n in HAND_MS}
    assert all(v is not None and v > 0 for v in values.values()), values
    prog = program_trace.program(ctx)
    [(_, step, args)] = prog.spans("serve.step")
    phases = [prog.children("serve.step", p)[0] for p in (
        "serve.step.plan", "serve.step.dispatch", "serve.step.wait",
        "serve.step.emit")]
    assert abs(step - sum(k[0][1] for k in phases)) < 0.02 * step
    assert args["active"] == 16 and args["tokens"] == 16
    [(_, _, admit)] = prog.spans("serve.admit")
    assert admit["n_reqs"] == 1
    steps = ctx.trace.spans("bench.step")
    by = prog.scoped_ns(steps)
    busy = ctx.trace.busy_s(steps) * 1e9
    assert abs(sum(by.values()) - busy) < 0.01 * busy
    dev = data["devices"][0]
    kernel = metric_reader("sme_roofline.decode").is_kernel
    kinds = {(tracing.op_class(o[0]), sc) for o, sc in
             zip(dev["ops"], dev["scopes"]) if kernel(o) or
             tracing.op_class(o[0]) == "copy" and o[2] > 50_000}
    assert kinds == {("_v2_call", None), ("copy", None)}
