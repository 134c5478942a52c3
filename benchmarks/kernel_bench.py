"""Kernel + serving benchmarks: sme_spmm vs dense matmul, per-arch weight
storage, decode-bandwidth model.

On this CPU container wall-times are interpret-mode artifacts; the decisive
numbers are bytes-per-weight (HBM traffic at decode) and the bandwidth-model
speedup = dense_bytes / packed_bytes for memory-bound decode.
"""
from __future__ import annotations

import time
from typing import List, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.sme import sme_compress
from repro.hardware.tpu_model import V5E

Row = Tuple[str, float, str]


def bench_sme_spmm_numerics() -> List[Row]:
    rows: List[Row] = []
    rng = np.random.default_rng(0)
    # kernel v2 (minifloat-6) numerics + storage
    from repro.kernels.sme_spmm import sme_linear6_from_weight
    from repro.core.minifloat import minifloat_from_sme, bits_per_weight6
    w = rng.normal(0, 0.05, (1024, 1024))
    x = rng.normal(0, 1, (8, 1024)).astype(np.float32)
    smew = sme_compress(w, squeeze=1)
    y = np.asarray(sme_linear6_from_weight(jnp.asarray(x), smew))
    y_ref = x.astype(np.float64) @ smew.dequant()
    rel = float(np.abs(y - y_ref).max() / np.abs(y_ref).max())
    rows.append(("kernel_v2/1024x1024/sq1/bits_per_weight",
                 round(bits_per_weight6(minifloat_from_sme(smew)), 3),
                 f"rel_err={rel:.2e} (vs 9.06 v1, 16 bf16)"))
    for k, n in [(512, 512), (1024, 1024)]:
        w = rng.normal(0, 0.05, (k, n))
        x = rng.normal(0, 1, (8, k)).astype(np.float32)
        for sq in (0, 1, 2):
            smew = sme_compress(w, squeeze=sq)
            from repro.kernels.sme_spmm import sme_linear_from_weight
            t0 = time.perf_counter()
            y = sme_linear_from_weight(jnp.asarray(x), smew)
            jax.block_until_ready(y)
            dt = (time.perf_counter() - t0) * 1e6
            y_ref = x.astype(np.float64) @ smew.dequant()
            rel = float(np.abs(np.asarray(y) - y_ref).max()
                        / max(np.abs(y_ref).max(), 1e-9))
            bits = smew.storage_bits_per_weight("bytecode")
            rows.append((f"kernel/{k}x{n}/sq{sq}/bits_per_weight",
                         round(bits, 3), f"rel_err={rel:.2e}"))
            rows.append((f"kernel/{k}x{n}/sq{sq}/interpret_us",
                         round(dt, 1), "CPU interpret mode"))
    return rows


def bench_plane_occupancy() -> List[Row]:
    """Plane-CSC (v3) vs tile-CSC (v1/v2) storage per layer: bytes/weight
    and occupied-unit counts (codeword tiles vs (plane, tile) pairs).

    Layers cover the sparsity regimes that matter: a dense gaussian MLP
    weight (plane-dense — v3 honestly loses to v2 there), magnitude-pruned
    layers (the paper's target: survivors' leading bits concentrate in the
    top planes, emptying the bottom ones), and a banded per-row-magnitude
    layer after the compiler's plane-level reordering.  The acceptance bar
    is v3 < v2's 0.75 B/weight at equal (n_bits, window) on the pruned /
    structured rows.
    """
    from repro.core.sparsity import plane_occupancy_stats
    from repro.compiler.reorder import plan_row_permutation

    rng = np.random.default_rng(5)

    def pruned(k, n, frac):
        w = rng.normal(0, 0.05, (k, n))
        w[np.abs(w) < np.quantile(np.abs(w), frac)] = 0.0
        return w

    def banded(k, n):
        # rows drawn from interleaved magnitude bands: scattered as laid
        # out, plane-separable once rows are clustered
        w = rng.normal(0, 0.05, (k, n))
        w *= np.where(np.arange(k) % 2 == 0, 1.0, 1 / 64.0)[:, None]
        return w

    layers = [
        ("mlp_dense_1024x1024", rng.normal(0, 0.05, (1024, 1024)), 3, False),
        ("attn_pruned90_2048x2048", pruned(2048, 2048, 0.90), 3, False),
        ("mlp_pruned80_1024x2048", pruned(1024, 2048, 0.80), 2, False),
        ("banded_reordered_1024x1024", banded(1024, 1024), 3, True),
    ]
    rows: List[Row] = []
    for name, w, win, reorder in layers:
        perm = plan_row_permutation(w, window=win, level="plane") \
            if reorder else None
        smew = sme_compress(w, window=win, squeeze=1, squeeze_max=7,
                            row_perm=perm)
        st = plane_occupancy_stats(smew)
        bw = st["bytes_per_weight"]
        setting = f"Nq=8 S={win} x=1..{st['tile_squeeze_max']}"
        rows.append((f"plane_occ/{name}/v1_bytes_per_weight",
                     round(bw["v1"], 3), setting))
        rows.append((f"plane_occ/{name}/v2_bytes_per_weight",
                     round(bw["v2"], 3), "minifloat-6 tile-CSC"))
        rows.append((f"plane_occ/{name}/v3_bytes_per_weight",
                     round(bw["v3"], 3),
                     f"plane-CSC; {'wins' if bw['v3'] < bw['v2'] else 'loses'}"
                     f" vs v2 at equal (Nq, S)"))
        rows.append((f"plane_occ/{name}/occupied_tiles",
                     st["occupied_tiles"],
                     f"of {st['tiles']} (v1/v2 DMA units)"))
        rows.append((f"plane_occ/{name}/occupied_plane_tiles",
                     st["occupied_plane_tiles"],
                     f"of {st['plane_tiles']} (v3 DMA units); per-plane "
                     + "/".join(str(int(c)) for c in st["per_plane_tiles"])))
    wins = sum(1 for r in rows if r[0].endswith("v3_bytes_per_weight")
               and r[1] < 0.75)
    rows.append(("plane_occ/layers_beating_v2_minifloat", wins,
                 "v3 < 0.75 B/weight at equal (n_bits, window)"))
    if wins < 2:
        raise RuntimeError(
            f"plane-CSC beat v2 on only {wins} layer(s); expected >= 2")
    return rows


def bench_decode_bandwidth_model() -> List[Row]:
    """Memory-bound decode: tokens/s/chip = HBM_bw / bytes_per_token.

    bytes_per_token ~ weight bytes touched per token (batch amortizes the
    KV cache differently; weights dominate for the assigned shapes).

    The plane-CSC (v3) row on the pruned layer is gated against the
    committed baseline ``benchmarks/baselines/decode_bandwidth.json`` —
    a format or packing change that regresses v3 bytes/token fails the
    suite (and CI) instead of silently shipping a fatter decode payload.
    """
    import json
    import pathlib

    rows: List[Row] = []
    rng = np.random.default_rng(1)
    w = rng.normal(0, 0.04, (2048, 2048))
    smew1 = sme_compress(w, squeeze=1)
    from repro.core.minifloat import minifloat_from_sme, bits_per_weight6
    mf = minifloat_from_sme(smew1)
    bw = V5E.hbm_bw
    n_w = w.size
    for label, bytes_per_w in [
        ("sme_minifloat6_v2", bits_per_weight6(mf) / 8),
        ("f32", 4.0), ("bf16", 2.0),
        ("sme_bytecode", smew1.storage_bits_per_weight("bytecode") / 8),
        ("sme_planes", smew1.storage_bits_per_weight("planes") / 8),
    ]:
        toks = bw / (n_w * bytes_per_w)
        rows.append((f"decode_bw/{label}/tokens_per_s_per_layerweight",
                     round(toks, 1),
                     f"{bytes_per_w:.3f} B/weight; speedup vs bf16 = "
                     f"{2.0 / bytes_per_w:.2f}x"))
    # plane-CSC on the decode-relevant regime: a magnitude-pruned layer
    # (deterministic rng, so the number is reproducible and gateable)
    wp = rng.normal(0, 0.04, (1024, 1024))
    wp[np.abs(wp) < np.quantile(np.abs(wp), 0.90)] = 0.0
    smew3 = sme_compress(wp, squeeze=1, squeeze_max=7)
    v3_bpw = smew3.storage_bits_per_weight("plane_csc") / 8
    rows.append(("decode_bw/sme_plane_csc_pruned90/tokens_per_s_per_layerweight",
                 round(bw / (wp.size * v3_bpw), 1),
                 f"{v3_bpw:.4f} B/weight on pruned90 1024x1024; speedup vs "
                 f"bf16 = {2.0 / v3_bpw:.2f}x"))
    base_path = pathlib.Path(__file__).parent / "baselines" \
        / "decode_bandwidth.json"
    if base_path.exists():
        ref = json.loads(base_path.read_text())["v3_bytes_per_weight_pruned90"]
        if v3_bpw > ref * 1.02 + 1e-9:
            raise RuntimeError(
                f"v3 plane-CSC decode payload regressed: "
                f"{v3_bpw:.4f} B/weight vs committed baseline {ref:.4f} "
                f"(tolerance 2%) — see benchmarks/baselines/")
        rows.append(("decode_bw/v3_baseline_check", 1,
                     f"{v3_bpw:.4f} <= {ref:.4f} * 1.02"))
    return rows


def _time_us(f, *args, reps: int = 2) -> float:
    y = f(*args)
    jax.block_until_ready(y)
    t0 = time.perf_counter()
    for _ in range(reps):
        y = f(*args)
    jax.block_until_ready(y)
    return (time.perf_counter() - t0) / reps * 1e6


def _pruned(rng, k, n, frac):
    w = rng.normal(0, 0.05, (k, n))
    w[np.abs(w) < np.quantile(np.abs(w), frac)] = 0.0
    return w


def _banded(rng, k, n):
    w = rng.normal(0, 0.05, (k, n))
    w *= np.where(np.arange(k) % 2 == 0, 1.0, 1 / 64.0)[:, None]
    return w


def bench_decode_gemv() -> List[Row]:
    """Decode-shaped (M in {1, 8, 32}) execution across every backend plus
    the v3 decode kernel (``SME_DECODE_KERNEL=on``) on the layers where
    plane-CSC pays: pruned and banded weights.

    Two classes of numbers: interpret-mode walltimes (CPU smoke — the
    grid/DMA structure is exercised, the absolute time is not meaningful)
    and the modeled HBM bytes per decoded token, which IS the decode
    currency on real hardware.  The suite fails unless v3 moves strictly
    fewer modeled bytes/token than v2 on every layer here.
    """
    import os

    from repro.compiler.reorder import plan_row_permutation
    from repro.core import backend as B
    from repro.core.integrate import pack_sme_param

    rng = np.random.default_rng(7)
    wb = _banded(rng, 512, 512)
    layers = [("pruned90_512x512", _pruned(rng, 512, 512, 0.90), None),
              # banded wins for v3 only after the compiler's plane-level
              # row clustering — serve the layout serving would see
              ("banded_reordered_512x512", wb,
               plan_row_permutation(wb, window=3, level="plane"))]
    rows: List[Row] = []
    saved = os.environ.get("SME_DECODE_KERNEL")
    try:
        for lname, w, perm in layers:
            k, n = w.shape
            smew = sme_compress(w, squeeze=1, squeeze_max=7, row_perm=perm)
            bpw = {
                "xla": 9.06 / 8,
                "v1": smew.storage_bits_per_weight("bytecode") / 8,
                "v2": smew.storage_bits_per_weight("minifloat6") / 8,
                "v3": smew.storage_bits_per_weight("plane_csc") / 8,
            }
            bpw["v3-decode"] = bpw["v3"]      # same operands, reshaped grid
            for label, b in bpw.items():
                rows.append((f"decode_gemv/{lname}/{label}/bytes_per_token",
                             round(b * w.size, 1),
                             f"{b:.4f} B/weight modeled HBM payload"))
            if not (bpw["v3"] < bpw["v2"]):
                raise RuntimeError(
                    f"decode-shaped v3 must move strictly fewer modeled "
                    f"bytes/token than v2 on {lname}: "
                    f"{bpw['v3']:.4f} vs {bpw['v2']:.4f} B/weight")
            params = {
                name: {key: jnp.asarray(v) for key, v in pack_sme_param(
                    w, squeeze=1, squeeze_max=7, row_perm=perm,
                    backend=None if name == "xla" else name).items()}
                for name in ("xla", "v1", "v2", "v3")
            }
            for m in (1, 8, 32):
                x = jnp.asarray(rng.normal(0, 1, (m, k)), jnp.float32)
                for label in ("xla", "v1", "v2", "v3", "v3-decode"):
                    name = "v3" if label == "v3-decode" else label
                    os.environ["SME_DECODE_KERNEL"] = \
                        "on" if label == "v3-decode" else "off"
                    dt = _time_us(
                        lambda a, nm=name: B.sme_apply(a, params[nm], nm), x)
                    rows.append(
                        (f"decode_gemv/{lname}/{label}/m{m}/interpret_us",
                         round(dt, 1), "CPU interpret-mode walltime"))
    finally:
        if saved is None:
            os.environ.pop("SME_DECODE_KERNEL", None)
        else:
            os.environ["SME_DECODE_KERNEL"] = saved
    return rows


def bench_autotune_sweep() -> List[Row]:
    """Populate the measured-timing autotune cache (DESIGN.md §8): sweep
    kernel backends x block sizes on a decode-shaped call, record observed
    us/call into an ``AutotuneCache`` JSON, and report what the planner
    does with it — the chosen (backend, bm) with the cache vs without.

    The cache path comes from ``SME_AUTOTUNE_CACHE`` (else
    ``BENCH_autotune_cache.json`` in the CWD); CI publishes it as an
    artifact.  Off-TPU the device key carries ``-interpret``, so these
    CPU smoke timings can never steer a real TPU serve.
    """
    import os

    from repro.compiler.plan import plan_model
    from repro.core import backend as B
    from repro.core.integrate import pack_sme_param
    from repro.hardware.autotune import AutotuneCache, TuneKey, device_kind

    rng = np.random.default_rng(9)
    k = n = 256
    w = _pruned(rng, k, n, 0.85)
    x = jnp.asarray(rng.normal(0, 1, (1, k)), jnp.float32)
    path = os.environ.get("SME_AUTOTUNE_CACHE", "BENCH_autotune_cache.json")
    cache = AutotuneCache(path)
    dev = device_kind()
    rows: List[Row] = []
    for name in ("v1", "v2", "v3"):
        p = {key: jnp.asarray(v) for key, v in
             pack_sme_param(w, squeeze=1, backend=name).items()}
        for bm in (64, 128, 256):
            dt = _time_us(
                lambda a, nm=name, b=bm: B.sme_apply(a, p, nm, bm=b), x)
            cache.record(TuneKey(name, 1, k, n, bm, dev), dt)
            rows.append((f"autotune/{name}/bm{bm}/us_per_call",
                         round(dt, 1), f"m=1 decode shape, {dev}"))
        best = cache.best(name, 1, k, n)
        rows.append((f"autotune/{name}/best_bm", best[0],
                     f"{best[1]['tokens_per_s']:.0f} tokens/s measured"))
    cache.save()
    rows.append(("autotune/cache_entries", len(cache.entries), path))
    tree = {"layer": {"w": w}}
    lp0 = plan_model(tree, autotune=AutotuneCache()).layers["layer/w"]
    lp1 = plan_model(tree, autotune=cache).layers["layer/w"]
    rows.append(("autotune/plan_no_cache",
                 0, f"backend={lp0.backend} bm={lp0.bm} (analytic prices)"))
    rows.append(("autotune/plan_with_cache",
                 1, f"backend={lp1.backend} bm={lp1.bm} (measured prices)"))
    return rows


def bench_dense_vs_sme_xla() -> List[Row]:
    """XLA path: dense bf16 matmul vs on-the-fly dequant matmul (CPU walltime
    is indicative only; the HLO byte footprint is the durable metric)."""
    rows: List[Row] = []
    rng = np.random.default_rng(2)
    k = n = 1024
    w = rng.normal(0, 0.05, (k, n))
    x = jnp.asarray(rng.normal(0, 1, (16, k)), jnp.float32)
    wd = jnp.asarray(w, jnp.bfloat16)
    f_dense = jax.jit(lambda a, b: (a.astype(jnp.bfloat16) @ b).astype(jnp.float32))
    y = f_dense(x, wd)
    t0 = time.perf_counter()
    for _ in range(20):
        y = f_dense(x, wd)
    jax.block_until_ready(y)
    rows.append(("xla/dense_us", round((time.perf_counter() - t0) / 20 * 1e6, 1), ""))

    from repro.core.integrate import pack_sme_param, sme_dequant_jnp
    packed = {key: jnp.asarray(v) for key, v in pack_sme_param(w).items()}
    f_sme = jax.jit(lambda a, p: (a.astype(jnp.bfloat16)
                                  @ sme_dequant_jnp(p)).astype(jnp.float32))
    y2 = f_sme(x, packed)
    t0 = time.perf_counter()
    for _ in range(20):
        y2 = f_sme(x, packed)
    jax.block_until_ready(y2)
    rows.append(("xla/sme_dequant_us",
                 round((time.perf_counter() - t0) / 20 * 1e6, 1),
                 "dequant not fused on CPU; Pallas kernel is the TPU path"))
    rel = float(jnp.abs(y - y2).max() / jnp.abs(y).max())
    rows.append(("xla/dense_vs_sme_rel_err", round(rel, 5), ""))
    return rows


def bench_backend_matrix() -> List[Row]:
    """All registered execution backends side by side on one weight:
    offline pack time, per-call exec time, numerics vs the float64 oracle,
    and the HBM payload each backend moves per weight."""
    from repro.core import backend as B
    from repro.core.integrate import pack_sme_param
    from repro.core.sme import sme_matmul_ref_np

    rows: List[Row] = []
    rng = np.random.default_rng(3)
    k = n = 1024
    w = rng.normal(0, 0.05, (k, n))
    smew = sme_compress(w, squeeze=1)
    x = jnp.asarray(rng.normal(0, 1, (16, k)), jnp.float32)
    y_ref = sme_matmul_ref_np(np.asarray(x), smew)
    bytes_per_w = {
        "xla": 9.06 / 8,      # raw codes + sign bitmap travel as-is
        "v1": smew.storage_bits_per_weight("bytecode") / 8,
        "v2": 0.75,
    }
    for name in B.available_backends():
        be = B.get_backend(name)
        t0 = time.perf_counter()
        param = {key: jnp.asarray(v)
                 for key, v in pack_sme_param(w, squeeze=1,
                                              backend=None if not be.OPERANDS
                                              else name).items()}
        jax.block_until_ready(list(param.values()))
        pack_ms = (time.perf_counter() - t0) * 1e3
        f = jax.jit(lambda a, p, nm=name: B.sme_apply(a, p, nm))
        y = f(x, param)
        jax.block_until_ready(y)
        t0 = time.perf_counter()
        reps = 5
        for _ in range(reps):
            y = f(x, param)
        jax.block_until_ready(y)
        dt_us = (time.perf_counter() - t0) / reps * 1e6
        rel = float(np.abs(np.asarray(y, np.float64) - y_ref).max()
                    / np.abs(y_ref).max())
        rows.append((f"backend/{name}/pack_ms", round(pack_ms, 2),
                     "offline, includes sme_compress"))
        rows.append((f"backend/{name}/exec_us", round(dt_us, 1),
                     f"rel_err={rel:.2e}; interpret-mode walltime off-TPU"))
        rows.append((f"backend/{name}/bytes_per_weight",
                     round(bytes_per_w.get(name, float("nan")), 3),
                     "HBM payload per weight at decode"))
    return rows


def bench_artifact_io() -> List[Row]:
    """Offline compiler artifact path: plan / pack+save / load timings.

    The number that matters for serving is load-vs-inline: booting from a
    ``.smez`` artifact replaces the whole quantize+squeeze+CSC-pack
    pipeline with an mmap of kernel-ready operands."""
    import shutil
    import tempfile

    from repro.compiler import compile_model, load_artifact, plan_model
    from repro.core.integrate import convert_params_to_sme

    rows: List[Row] = []
    rng = np.random.default_rng(4)
    tree = {"layer": {"w": rng.normal(0, 0.05, (1024, 1024))}}

    t0 = time.perf_counter()
    plan = plan_model(tree, error_budget=0.06)
    rows.append(("artifact/plan_ms",
                 round((time.perf_counter() - t0) * 1e3, 1),
                 f"{len(plan.layers)} layers, trial-measured grid"))

    tmp = tempfile.mkdtemp()
    try:
        out = tmp + "/bench.smez"
        t0 = time.perf_counter()
        compile_model(tree, plan=plan, out=out)
        rows.append(("artifact/pack_save_ms",
                     round((time.perf_counter() - t0) * 1e3, 1),
                     "convert_params_to_sme + payload write"))

        t0 = time.perf_counter()
        params, _, _ = load_artifact(out)
        rows.append(("artifact/load_mmap_ms",
                     round((time.perf_counter() - t0) * 1e3, 1),
                     "manifest parse + lazy mmap views"))
        t0 = time.perf_counter()
        touched = sum(int(np.asarray(v).sum(dtype=np.int64))
                      for v in params["layer"]["w"].values()
                      if np.issubdtype(np.asarray(v).dtype, np.integer))
        rows.append(("artifact/load_touch_ms",
                     round((time.perf_counter() - t0) * 1e3, 1),
                     f"page in every payload byte (checksum {touched % 997})"))

        t0 = time.perf_counter()
        convert_params_to_sme(tree, plan=plan)
        inline_ms = (time.perf_counter() - t0) * 1e3
        rows.append(("artifact/inline_convert_ms", round(inline_ms, 1),
                     "what every boot pays without the artifact"))
        disk = sum(f.stat().st_size for f in
                   __import__("pathlib").Path(out).rglob("*") if f.is_file())
        rows.append(("artifact/disk_mb", round(disk / 1e6, 2),
                     "1024x1024 layer, plan-chosen backend operands"))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return rows


def bench_shard_matrix() -> List[Row]:
    """Mesh-serving throughput matrix: tokens/s per (data, model) mesh
    shape through ``ServeEngine`` (DESIGN.md §7).

    Every mesh shape runs in this process, over the devices it already
    has: a child process could not reach an accelerator this process
    holds.  Needs 4 devices — on a CPU host run it under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=4``.  Off-TPU the
    kernels run in interpret mode and the tok/s is an emulation artifact;
    the decisive check is that every mesh shape emits the same tokens
    from the same request batch (asserted here and in
    tests/test_serve_mesh.py)."""
    import jax

    from repro.configs import ARCHS, scale_down
    from repro.core.integrate import convert_params_to_sme
    from repro.launch.mesh import make_mesh
    from repro.models import build_model
    from repro.serve import Request, ServeEngine

    if jax.device_count() < 4:
        raise RuntimeError(
            f"shard matrix needs 4 devices, found {jax.device_count()}; on "
            "CPU set XLA_FLAGS=--xla_force_host_platform_device_count=4 "
            "before starting the process")
    cfg = scale_down(ARCHS["qwen1.5-0.5b"], d_model=128, d_ff=256, vocab=256)
    api = build_model(cfg)
    params = convert_params_to_sme(
        jax.tree.map(np.asarray, api.init_params(jax.random.key(0))),
        squeeze=1, backend="v1")
    dev = jax.devices()[0]
    rows: List[Row] = []
    ref = None
    for data, model in ((1, 1), (2, 2), (4, 1), (1, 4)):
        rng = np.random.default_rng(0)
        reqs = [Request(rid=i, prompt=rng.integers(
                    0, cfg.vocab, size=5 + i % 4, dtype=np.int32),
                        max_new_tokens=6) for i in range(4)]
        t0 = time.perf_counter()
        eng = ServeEngine(api, params, slots=2, s_max=64, backend="v1",
                          mesh=make_mesh((data, model)))
        stats = eng.run(reqs, max_steps=500)
        wall = time.perf_counter() - t0
        toks = [r.out_tokens for r in reqs]
        if ref is None:
            ref = toks
        elif toks != ref:
            raise RuntimeError(
                f"mesh {data}x{model} emitted different tokens than 1x1")
        name = f"shard_matrix/mesh_{data}x{model}"
        rows.append((name + "/tok_s", round(stats["tokens"] / wall, 2),
                     f"{data * model} {dev.platform} devices, sme v1, "
                     f"{stats['tokens']} tokens, incl. compile"))
        rows.append((name + "/wall_s", round(wall, 1),
                     "engine build + compile + serve"))
    return rows


ALL = [bench_sme_spmm_numerics, bench_plane_occupancy,
       bench_decode_bandwidth_model, bench_decode_gemv,
       bench_autotune_sweep, bench_dense_vs_sme_xla,
       bench_backend_matrix, bench_artifact_io, bench_shard_matrix]
