"""Telemetry layer (DESIGN.md §9): metrics registry semantics, the
engine's profiler spans and the step program's named scopes, the two
invariance properties (telemetry and the profiler cannot change the
lowered HLO or the served tokens), the instrumentation hooks in
core/backend + hardware/autotune + ServeEngine, the snapshot CI gate, and
the Prometheus HTTP endpoint."""
import glob
import json
import logging
import os
import subprocess
import sys
import urllib.error
import urllib.request

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.core import backend as B
from repro.core.integrate import convert_params_to_sme, pack_sme_param
from repro.hardware.autotune import AutotuneCache, TuneKey, set_cache
from repro.obs.gate import check_snapshot, main as gate_main
from repro.obs.httpd import start_metrics_server
from repro.obs.metrics import MetricsRegistry, flatten_snapshot, \
    write_snapshot

RNG = np.random.default_rng(57)


@pytest.fixture(autouse=True)
def _telemetry_on():
    # every test starts (and leaves the process) with telemetry enabled —
    # the default; individual tests flip it via obs.set_enabled
    obs.set_enabled(True)
    set_cache(None)
    yield
    obs.set_enabled(True)
    set_cache(None)


def _param(w, emit=None, **kw):
    return {k: jnp.asarray(v)
            for k, v in pack_sme_param(w, backend=emit, **kw).items()}


def _pruned(rows, cols, q=0.5):
    w = RNG.normal(0, 0.3, (rows, cols))
    w[np.abs(w) < np.quantile(np.abs(w), q)] = 0.0
    return w


# ------------------------------------------------------- metrics registry
def test_registry_counter_gauge_labels_and_validation():
    R = MetricsRegistry()
    c = R.counter("c_total", "things", ("k",))
    c.labels(k="a").inc()
    c.labels(k="a").inc(2)
    assert R.value("c_total", k="a") == 3
    assert R.value("c_total", k="never") == 0.0     # absent child reads 0
    assert R.value("nope") == 0.0                   # absent family reads 0
    with pytest.raises(ValueError):
        c.labels(wrong="x")                         # label-name mismatch
    with pytest.raises(ValueError):
        R.gauge("c_total")                          # kind conflict
    with pytest.raises(ValueError):
        c.labels(k="a").inc(-1)                     # counters only go up
    g = R.gauge("g")
    g.set(5.0)
    g.dec(2.0)
    assert R.value("g") == 3.0
    assert R.sum_values("c_total") == 3.0


def test_histogram_buckets_and_snapshot_flatten_roundtrip():
    R = MetricsRegistry()
    h = R.histogram("h_seconds", "latency", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 0.1, 100.0):               # 0.1 lands in le=0.1
        h.observe(v)
    snap = R.snapshot()
    hv = snap["metrics"]["h_seconds"]["values"][0]
    assert hv["count"] == 4
    assert hv["sum"] == pytest.approx(100.65)
    assert hv["buckets"] == {"0.1": 2, "1.0": 1, "+Inf": 1}
    # flatten survives a JSON round trip (what --metrics-out produces)
    flat = flatten_snapshot(json.loads(json.dumps(snap)))
    assert flat["h_seconds_count"] == 4
    assert flat["h_seconds_sum"] == pytest.approx(100.65)
    with pytest.raises(ValueError):
        R.histogram("bad", buckets=(1.0, 1.0))      # must strictly increase


def test_render_text_prometheus_exposition():
    R = MetricsRegistry()
    R.counter("a_total", "things", ("q",)).labels(q='x"y').inc()
    h = R.histogram("lat_seconds", "latency", buckets=(0.5,))
    h.observe(0.2)
    h.observe(7.0)
    text = R.render_text()
    assert "# TYPE a_total counter" in text
    assert 'a_total{q="x\\"y"} 1' in text           # label value escaping
    assert "# TYPE lat_seconds histogram" in text
    assert 'lat_seconds_bucket{le="0.5"} 1' in text
    assert 'lat_seconds_bucket{le="+Inf"} 2' in text   # cumulative
    assert "lat_seconds_sum 7.2" in text
    assert "lat_seconds_count 2" in text


# ------------------------------------------------------- profiler spans
STEP_PHASES = ("serve.step.plan", "serve.step.dispatch", "serve.step.wait",
               "serve.step.emit")


def _serve_events(profile_dir):
    """``(name, start_ns, end_ns, args)`` of every ``serve.*`` host event
    in the newest xplane under ``profile_dir``, by start."""
    files = sorted(glob.glob(os.path.join(str(profile_dir), "**",
                                          "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    assert files, f"no xplane under {profile_dir}"
    pd = jax.profiler.ProfileData.from_file(files[-1])
    out = [(ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
            {k: v for k, v in ev.stats})
           for plane in pd.planes if plane.name.startswith("/host:")
           for line in plane.lines for ev in line.events
           if ev.name.startswith("serve.")]
    return sorted(out, key=lambda e: e[1])


def _captured(profile_dir, fn):
    """``fn()`` run under the JAX profiler (python tracer off), and the
    ``serve.*`` host events it recorded."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(profile_dir), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    return out, _serve_events(profile_dir)


def _inside(events, name, lo, hi):
    return [e for e in events if e[0] == name and lo <= e[1] and e[2] <= hi]


# --------------------------------------------------- invariance properties
def test_hlo_invariant_under_telemetry(monkeypatch):
    # the tentpole contract: emitting metrics at trace time must not
    # appear in the lowered program — compare HLO text with telemetry on
    # vs off, on both v3 kernel paths (matmul grid and decode GEMV)
    p = _param(_pruned(200, 150), emit="v3", squeeze=1)
    x = jnp.zeros((1, 200), jnp.float32)
    for mode in ("off", "on"):
        monkeypatch.setenv("SME_DECODE_KERNEL", mode)
        texts = []
        for en in (True, False):
            obs.set_enabled(en)
            fn = jax.jit(lambda xx: B.sme_apply(xx, p, "v3"))
            texts.append(fn.lower(x).as_text())
        assert texts[0], f"empty lowering (mode={mode})"
        assert texts[0] == texts[1], \
            f"telemetry changed the lowered HLO (SME_DECODE_KERNEL={mode})"


@pytest.mark.parametrize("backend", ["v1", "v2", "v3"])
def test_serve_tokens_bit_identical_with_tracing(smoke_engine_parts,
                                                 backend, tmp_path):
    # greedy tokens must be bit-identical with the profiler recording and
    # metrics enabled vs no profiler and metrics fully disabled, through
    # the real slot engine on each kernel backend
    from repro.serve import Request, ServeEngine
    cfg, api, params = smoke_engine_parts
    ps = convert_params_to_sme(params, squeeze=1, backend=backend)

    def serve(en):
        obs.set_enabled(en)
        eng = ServeEngine(api, ps, slots=2, s_max=32, backend=backend)
        reqs = [Request(rid=i,
                        prompt=(np.arange(1, 4 + i) % cfg.vocab
                                ).astype(np.int32),
                        max_new_tokens=4)
                for i in range(3)]
        stats = eng.run(reqs, max_steps=30)
        return [list(r.out_tokens) for r in reqs], stats, eng

    (toks_on, stats_on, eng_on), events = _captured(
        tmp_path, lambda: serve(True))
    toks_off, stats_off, eng_off = serve(False)
    assert toks_on == toks_off
    assert stats_on["completed"] == stats_off["completed"] == 3
    for k in ("prefills", "prefill_reqs", "decode_steps", "tokens"):
        assert stats_on[k] == stats_off[k], k
    # the profiler holds one serve.step span per decode step
    steps = [e for e in events if e[0] == "serve.step"]
    assert len(steps) == stats_on["decode_steps"]
    assert eng_on._m["ttft"].count == 3
    assert eng_off._m["ttft"].count == 0


# ---------------------------------------------------- engine instrumentation
@pytest.fixture(scope="module")
def smoke_engine_parts():
    from repro.configs import ARCHS, scale_down
    from repro.models import build_model
    cfg = scale_down(ARCHS["qwen1.5-0.5b"], d_model=128, d_ff=256,
                     head_dim=32, n_heads=4, n_kv_heads=4, vocab=256,
                     n_layers=1)
    api = build_model(cfg)
    params = jax.tree.map(np.asarray, api.init_params(jax.random.key(0)))
    return cfg, api, params


def test_engine_stats_derive_from_registry(smoke_engine_parts, tmp_path):
    from repro.serve import Request, ServeEngine
    cfg, api, params = smoke_engine_parts
    eng = ServeEngine(api, params, slots=2, s_max=32)
    reqs = [Request(rid=i,
                    prompt=(np.arange(2, 7 + i) % cfg.vocab
                            ).astype(np.int32),
                    max_new_tokens=3)
            for i in range(4)]
    # one oversized prompt: rejected in run(), the rest keep serving
    reqs.append(Request(rid=99, prompt=np.zeros(40, np.int32),
                        max_new_tokens=3))
    stats, events = _captured(tmp_path, lambda: eng.run(reqs, max_steps=40))

    assert set(stats) == {"completed", "evicted", "rejected", "unserved",
                          "wall_s", "prefills", "prefill_reqs",
                          "decode_steps", "tokens"}
    assert stats["completed"] == 4
    assert stats["rejected"] == 1
    assert stats["prefill_reqs"] == 4
    assert stats["tokens"] >= 4

    # one source of truth: the returned dict, the _stats property and the
    # registry all read the same counters
    R = obs.get_registry()
    assert stats["decode_steps"] == eng._stats["decode_steps"] == \
        R.value("serve_decode_steps_total", engine=eng._eid)
    assert stats["tokens"] == \
        R.value("serve_tokens_total", engine=eng._eid)
    assert R.value("serve_requests_total", engine=eng._eid,
                   outcome="completed") == 4
    assert R.value("serve_requests_total", engine=eng._eid,
                   outcome="rejected") == 1

    # latency/occupancy instruments observed the run
    assert eng._m["ttft"].count == 4
    assert eng._m["qwait"].count == 4
    assert eng._m["occupancy"].count == stats["decode_steps"]
    assert eng._m["pad_frac"].count == stats["prefills"]
    assert eng._m["itl"].count == stats["tokens"]

    # the profiler holds every host phase of the engine
    names = {e[0] for e in events}
    assert {"serve.pump", "serve.admit", "serve.step", *STEP_PHASES} <= names

    # a second run() reports per-run outcome deltas, not lifetime totals,
    # while the stats counters keep accumulating
    reqs2 = [Request(rid=10 + i,
                     prompt=(np.arange(3, 8) % cfg.vocab).astype(np.int32),
                     max_new_tokens=2)
             for i in range(2)]
    stats2 = eng.run(reqs2, max_steps=40)
    assert stats2["completed"] == 2
    assert stats2["rejected"] == 0
    assert stats2["decode_steps"] > stats["decode_steps"]


def _spans_run(smoke_engine_parts, tmp_path):
    from repro.serve import Request, ServeEngine
    cfg, api, params = smoke_engine_parts
    eng = ServeEngine(api, params, slots=3, s_max=32)
    reqs = [Request(rid=i, prompt=(np.arange(1, 4 + 2 * i) % cfg.vocab
                                   ).astype(np.int32),
                    max_new_tokens=3 + i)
            for i in range(5)]
    stats, events = _captured(tmp_path, lambda: eng.run(reqs, max_steps=40))
    return reqs, stats, events


def test_step_phases_tile_the_step_on_one_clock(smoke_engine_parts,
                                                 tmp_path):
    # plan / dispatch / wait / emit lie inside their serve.step, in that
    # order, without overlap, and leave no more than a sliver of it out
    reqs, stats, events = _spans_run(smoke_engine_parts, tmp_path)
    steps = [e for e in events if e[0] == "serve.step"]
    assert len(steps) == stats["decode_steps"] > 0
    emitted = 0
    for _, lo, hi, args in steps:
        kids = [_inside(events, n, lo, hi) for n in STEP_PHASES]
        assert all(len(k) == 1 for k in kids), kids
        kids = [k[0] for k in kids]
        assert all(a[2] <= b[1] for a, b in zip(kids, kids[1:]))
        covered = sum(k[2] - k[1] for k in kids)
        assert covered <= hi - lo
        assert hi - lo - covered < max(0.02 * (hi - lo), 200_000)
        assert args["slots"] == 3 and args["chunk"] >= 1
        assert 1 <= args["active"] <= 3
        emitted += args["tokens"]
    # every emitted token but each request's prefill-sampled first one
    assert emitted == sum(len(r.out_tokens) - 1 for r in reqs)


def test_admit_span_carries_its_window(smoke_engine_parts, tmp_path):
    reqs, stats, events = _spans_run(smoke_engine_parts, tmp_path)
    admits = [e for e in events if e[0] == "serve.admit"]
    assert len(admits) == stats["prefills"]
    assert sum(a[3]["n_reqs"] for a in admits) == stats["prefill_reqs"] == 5
    for _, lo, hi, args in admits:
        assert args["pad_to"] >= 8 and args["qwait_ms_max"] >= 0.0
        # each admission window runs inside a pump, never inside a step
        assert [e for e in events if e[0] == "serve.pump"
                and e[1] <= lo and hi <= e[2]]


def test_step_program_holds_the_scopes():
    # attention, the KV cache's row writes into the carried layer stack
    # and its layer reads, and the head each carry their named scope in
    # the compiled step program's metadata
    from repro.configs import ARCHS, scale_down
    from repro.models import build_model
    from repro.serve import ServeEngine
    cfg = scale_down(ARCHS["qwen1.5-0.5b"], d_model=128, d_ff=256,
                     head_dim=32, n_heads=4, n_kv_heads=4, vocab=256,
                     n_layers=2)
    api = build_model(cfg)
    eng = ServeEngine(api, api.init_params(jax.random.key(0)), slots=2,
                      s_max=32)
    text = eng.lower_programs(2, 8)["step"].compile().as_text()
    ops = {}
    for line in text.splitlines():
        if 'op_name="' not in line:
            continue
        path = line.split('op_name="', 1)[1].split('"', 1)[0].split("/")
        for scope in ("attention", "kv_cache", "lm_head"):
            if scope in path:
                ops.setdefault(scope, []).append(path[-1])
    assert set(ops) == {"attention", "kv_cache", "lm_head"}
    assert "dot_general" in ops["attention"]
    assert {"select_n", "dynamic_slice", "dynamic_update_slice"} \
        <= set(ops["kv_cache"])
    assert {"dot_general", "rsqrt"} <= set(ops["lm_head"])


def test_launch_profile_dir_writes_serve_spans(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               PYTHONPATH="src" + os.pathsep + os.environ.get("PYTHONPATH",
                                                             ""))
    prof = tmp_path / "prof"
    p = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--smoke",
         "--requests", "2", "--max-new", "3", "--slots", "2", "--s-max",
         "32", "--profile-dir", str(prof)],
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    names = {e[0] for e in _serve_events(prof)}
    assert {"serve.pump", "serve.admit", "serve.step"} <= names


# --------------------------------------------------- backend/kernel hooks
def test_dispatch_and_prepacked_counters():
    p = _param(_pruned(128, 96), emit="v1", squeeze=1)
    x = jnp.ones((2, 128), jnp.float32)
    R = obs.get_registry()
    base_d = R.value("sme_dispatch_total", backend="v1")
    base_p = R.value("sme_operand_cache_total", event="prepacked")
    base_b = R.value("sme_modeled_bytes_total", backend="v1")
    B.sme_apply(x, p, "v1")
    assert R.value("sme_dispatch_total", backend="v1") == base_d + 1
    assert R.value("sme_operand_cache_total",
                   event="prepacked") == base_p + 1
    assert R.value("sme_modeled_bytes_total", backend="v1") > base_b


def test_decode_kernel_path_counters(monkeypatch):
    p = _param(_pruned(200, 150), emit="v3", squeeze=1)
    x1 = jnp.ones((1, 200), jnp.float32)
    R = obs.get_registry()
    monkeypatch.setenv("SME_DECODE_KERNEL", "on")
    base_dec = R.value("sme_decode_kernel_total", mode="on", path="decode")
    B.sme_apply(x1, p, "v3")
    assert R.value("sme_decode_kernel_total", mode="on",
                   path="decode") == base_dec + 1
    monkeypatch.setenv("SME_DECODE_KERNEL", "off")
    base_mm = R.value("sme_decode_kernel_total", mode="off", path="matmul")
    B.sme_apply(x1, p, "v3")
    assert R.value("sme_decode_kernel_total", mode="off",
                   path="matmul") == base_mm + 1


def test_operand_cache_counters_and_thrash_warning(caplog):
    class BlockPackBackend(B.SpmmV1Backend):
        # packed layout depends on bm, so every bm change is a repack
        def pack_block_key(self, bm):
            return bm

    p = _param(_pruned(64, 48), squeeze=1)
    be = BlockPackBackend()
    R = obs.get_registry()

    def val(ev):
        return R.value("sme_operand_cache_total", event=ev)

    base = {e: val(e) for e in ("hit", "miss", "repack")}
    B._cached_operands(p, be, bm=64)                # first sight: miss
    B._cached_operands(p, be, bm=64)                # same key: hit
    B._cached_operands(p, be, bm=128)               # new block key: repack
    assert val("miss") - base["miss"] == 1
    assert val("hit") - base["hit"] == 1
    assert val("repack") - base["repack"] == 1
    with caplog.at_level(logging.WARNING, logger="repro.obs"):
        B._cached_operands(p, be, bm=256)           # 2nd repack: thrash
    assert val("repack") - base["repack"] == 2
    assert any("thrash" in r.getMessage() for r in caplog.records)


def test_autotune_cache_counters(tmp_path):
    R = obs.get_registry()

    def val(ev):
        return R.value("autotune_cache_total", event=ev)

    base = {e: val(e) for e in ("hit", "miss", "stale")}
    cache = AutotuneCache()
    assert cache.best("v3", 1, 8, 8, "testdev") is None
    assert val("miss") - base["miss"] == 1
    cache.record(TuneKey("v3", 1, 8, 8, 64, "testdev"), 10.0)
    bm, _ = cache.best("v3", 1, 8, 8, "testdev")
    assert bm == 64
    assert val("hit") - base["hit"] == 1
    stale = tmp_path / "stale.json"
    stale.write_text(json.dumps({"version": 999, "entries": {}}))
    with pytest.raises(ValueError):
        AutotuneCache.load(str(stale))
    assert val("stale") - base["stale"] == 1


def test_disabled_telemetry_records_nothing():
    # with the gate off, every hook is a single branch: the process
    # registry must be byte-for-byte unchanged across kernel dispatch,
    # operand packing and autotune lookups
    obs.set_enabled(False)
    p = _param(_pruned(64, 48), emit="v1", squeeze=1)
    x = jnp.ones((1, 64), jnp.float32)
    R = obs.get_registry()
    flat0 = R.flat_values()
    B.sme_apply(x, p, "v1")
    B._cached_operands(_param(_pruned(64, 48), squeeze=1),
                       B.get_backend("v1"))
    AutotuneCache().best("v1", 1, 1, 1, "dev")
    assert R.flat_values() == flat0


# ------------------------------------------------------------ CI gate
def _serve_like_registry():
    R = MetricsRegistry()
    eid = dict(engine="0")
    R.counter("serve_requests_total", "", ("engine", "outcome")).labels(
        engine="0", outcome="completed").inc(3)
    R.counter("serve_prefills_total", "", ("engine",)).labels(**eid).inc(2)
    R.counter("serve_decode_steps_total", "",
              ("engine",)).labels(**eid).inc(7)
    R.counter("serve_tokens_total", "", ("engine",)).labels(**eid).inc(12)
    R.histogram("serve_ttft_seconds", "",
                ("engine",)).labels(**eid).observe(0.1)
    R.histogram("serve_inter_token_seconds", "",
                ("engine",)).labels(**eid).observe(0.01)
    R.counter("sme_dispatch_total", "", ("backend",)).labels(
        backend="v1").inc(4)
    R.counter("sme_operand_cache_total", "", ("event",)).labels(
        event="prepacked").inc(4)
    return R


def test_gate_passes_on_live_snapshot(tmp_path):
    R = _serve_like_registry()
    snap = json.loads(json.dumps(R.snapshot()))
    assert check_snapshot(snap) == []
    path = write_snapshot(str(tmp_path / "m.json"), registry=R)
    assert gate_main([path]) == 0


def test_gate_fails_on_missing_family_or_dead_run(tmp_path):
    snap = json.loads(json.dumps(_serve_like_registry().snapshot()))

    missing = json.loads(json.dumps(snap))
    del missing["metrics"]["serve_ttft_seconds"]
    assert any("serve_ttft_seconds" in f for f in check_snapshot(missing))

    zero = json.loads(json.dumps(snap))
    zero["metrics"]["serve_decode_steps_total"]["values"][0]["value"] = 0
    assert any("decode steps" in f for f in check_snapshot(zero))

    nocache = json.loads(json.dumps(snap))
    nocache["metrics"]["sme_operand_cache_total"]["values"][0][
        "labels"]["event"] = "miss"
    assert any("operand" in f for f in check_snapshot(nocache))

    assert check_snapshot({"version": 99, "metrics": {}})

    extra = json.loads(json.dumps(snap))
    assert any("my_custom_total" in f
               for f in check_snapshot(extra, require=["my_custom_total"]))

    bad_path = tmp_path / "bad.json"
    bad_path.write_text(json.dumps(missing))
    assert gate_main([str(bad_path)]) == 1


# ------------------------------------------------------- HTTP exposition
def test_metrics_http_endpoint():
    R = MetricsRegistry()
    R.counter("up_total", "liveness").inc()
    server, _thread = start_metrics_server(0, registry=R)
    try:
        port = server.server_port
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10).read().decode()
        assert "up_total 1" in body
        assert "# TYPE up_total counter" in body
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/nope", timeout=10)
    finally:
        server.shutdown()
