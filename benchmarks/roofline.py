"""Roofline table from the dry-run JSONs (task §ROOFLINE).

Reads experiments/dryrun/*.json (single-pod mesh), emits a markdown table
with the three terms, the bottleneck, MODEL_FLOPS ratio and a one-line
lever per cell; writes experiments/roofline.md (embedded in EXPERIMENTS.md).
"""
from __future__ import annotations

import json
import pathlib
from typing import List, Tuple

Row = Tuple[str, float, str]

DRY = pathlib.Path("experiments/dryrun")
OUT = pathlib.Path("experiments/roofline.md")

LEVERS = {
    "compute": "raise MXU utilization: larger microbatch / fuse dequant "
               "(sme_spmm) / drop remat recompute on cheap layers",
    "memory": "cut HBM traffic: SME-packed weights (1B/w), bf16 cache, "
              "fuse attention intermediates",
    "collective": "reshard: DP instead of TP for small models, overlap "
                  "grad all-reduce with microbatches, int8 gradient "
                  "compression cross-pod",
}


def load_cells(mesh: str = "single"):
    cells = []
    for p in sorted(DRY.glob(f"*__{mesh}.json")):
        d = json.load(open(p))
        cells.append(d)
    return cells


def render_table(mesh: str = "single") -> str:
    lines = [
        f"### Roofline — {mesh}-pod mesh "
        f"({'256' if mesh == 'single' else '512'} chips, v5e terms)",
        "",
        "| arch | shape | kind | compute_s | memory_s | collective_s | "
        "bottleneck | roofline frac | useful/HLO flops | lever |",
        "|---|---|---|---|---|---|---|---|---|---|",
    ]
    for d in load_cells(mesh):
        if d["status"] == "skipped":
            lines.append(
                f"| {d['arch']} | {d['shape']} | — | — | — | — | skipped | — "
                f"| — | {d['reason'][:60]} |")
            continue
        if d["status"] != "ok":
            lines.append(f"| {d['arch']} | {d['shape']} | ? | ERROR |")
            continue
        r = d["roofline"]
        ur = d.get("useful_compute_ratio")
        lines.append(
            f"| {d['arch']} | {d['shape']} | {d['kind']} "
            f"| {r['compute_s']:.4f} | {r['memory_s']:.4f} "
            f"| {r['collective_s']:.4f} | **{r['bottleneck']}** "
            f"| {r['roofline_fraction']:.3f} | {ur:.2f} "
            f"| {LEVERS[r['bottleneck']][:58]} |")
    return "\n".join(lines)


def bench_roofline() -> List[Row]:
    rows: List[Row] = []
    ok = skip = err = 0
    worst = None
    most_coll = None
    for mesh in ("single", "multi"):
        for d in load_cells(mesh):
            if d["status"] == "ok":
                ok += 1
                if mesh == "single":
                    r = d["roofline"]
                    frac = r["roofline_fraction"]
                    key = f"{d['arch']}/{d['shape']}"
                    if worst is None or frac < worst[1]:
                        worst = (key, frac)
                    cshare = r["collective_s"] / max(
                        r["compute_s"] + r["memory_s"] + r["collective_s"], 1e-9)
                    if most_coll is None or cshare > most_coll[1]:
                        most_coll = (key, cshare)
            elif d["status"] == "skipped":
                skip += 1
            else:
                err += 1
    rows.append(("roofline/cells_ok", ok, ""))
    rows.append(("roofline/cells_skipped", skip, "documented skips"))
    rows.append(("roofline/cells_error", err, ""))
    if worst:
        rows.append(("roofline/worst_fraction_cell", worst[1], worst[0]))
    if most_coll:
        rows.append(("roofline/most_collective_bound", round(most_coll[1], 3),
                     most_coll[0]))
    md = render_table("single") + "\n\n" + render_table("multi")
    OUT.parent.mkdir(parents=True, exist_ok=True)
    OUT.write_text(md)
    rows.append(("roofline/table_written", 1, str(OUT)))
    return rows


def bench_backend_roofline() -> List[Row]:
    """Achieved-vs-peak HBM bytes/s per execution backend on a decode call.

    For each backend the modeled weight payload (the bytes a real TPU
    would stream per token, from ``storage_bits_per_weight``) is divided
    by the measured wall time of one decode-shaped ``sme_apply``.  On a
    TPU the achieved rate is compared with that chip's HBM peak, looked
    up by ``device_kind`` (``tpu_model.peak_spec``; an unknown kind is an
    error).  Off-TPU the kernels run in interpret mode: the rates are
    reported as CPU wall-time artifacts with no peak fraction.
    """
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import backend as B
    from repro.core.integrate import pack_sme_param
    from repro.core.sme import sme_compress
    from repro.hardware.tpu_model import peak_spec

    rng = np.random.default_rng(11)
    k = n = 512
    w = rng.normal(0, 0.05, (k, n))
    w[np.abs(w) < np.quantile(np.abs(w), 0.90)] = 0.0
    smew = sme_compress(w, squeeze=1, squeeze_max=7)
    payload_bytes = {
        "xla": 9.06 / 8 * w.size,
        "v1": smew.storage_bits_per_weight("bytecode") / 8 * w.size,
        "v2": smew.storage_bits_per_weight("minifloat6") / 8 * w.size,
        "v3": smew.storage_bits_per_weight("plane_csc") / 8 * w.size,
    }
    x = jnp.asarray(rng.normal(0, 1, (8, k)), jnp.float32)
    rows: List[Row] = []
    dev = jax.devices()[0]
    peak = peak_spec(dev.device_kind) if dev.platform == "tpu" else None
    for name, payload in payload_bytes.items():
        p = {key: jnp.asarray(v) for key, v in pack_sme_param(
            w, squeeze=1, squeeze_max=7,
            backend=None if name == "xla" else name).items()}
        y = B.sme_apply(x, p, name)
        jax.block_until_ready(y)
        t0 = time.perf_counter()
        for _ in range(2):
            y = B.sme_apply(x, p, name)
        jax.block_until_ready(y)
        dt = (time.perf_counter() - t0) / 2
        achieved = payload / dt
        ctx = (f"{achieved / peak.hbm_bw:.2e} of {dev.device_kind} HBM peak"
               if peak else f"{dev.platform} interpret-mode wall time, "
               "not a device rate")
        rows.append((f"backend_roofline/{name}/achieved_bytes_per_s",
                     round(achieved, 1), f"{ctx} ({payload:.0f} B payload)"))
    if peak:
        rows.append(("backend_roofline/peak_bytes_per_s", peak.hbm_bw,
                     f"{dev.device_kind} HBM roofline reference"))
    return rows


ALL = [bench_roofline, bench_backend_roofline]
