"""Serving driver: batched requests through the slot engine, optionally with
SME-compressed weights — converted inline, or booted from a compiled
``.smez`` artifact with zero per-boot packing (DESIGN.md §4).

Prompts are deliberately ragged (lengths ``5 + i % 4``): the engine decodes
all slots with one vectorized call per step — per-slot ``pos`` and an
``active`` mask — so mixed sequence lengths cost no extra decode calls and
cannot cross-corrupt slot caches (DESIGN.md §6).  CI runs this as a smoke
step with ``--sme --backend v1``.

Serving is mesh-native (DESIGN.md §7): ``--mesh data,model`` places params
and slot caches across a device mesh (bit-identical tokens to the default
1x1 mesh); on a CPU host add ``--host-devices N`` to fabricate N devices
(translated into ``--xla_force_host_platform_device_count`` before the
first jax import).

The engine is an open-stream continuous scheduler (DESIGN.md §12):
prompts prefill in ``--chunk-len`` token chunks interleaved with running
decode rows, ``--prefix-cache`` reuses page-aligned token-id-exact
prompt prefixes, and ``--stream`` drives the ``submit``/``poll``
streaming API instead of the closed ``run()`` loop — all with tokens
bit-identical to solo decoding.

``--spec-depth K|auto`` turns on self-speculative decoding (DESIGN.md
§11): greedy draft tokens from only the K most-significant occupied
bit-planes per tile group, verified at full precision — accepted tokens
are bit-identical to the non-speculative run.  ``auto`` reads the
per-layer depths the compiler plan stamped into the converted params.

Telemetry (DESIGN.md §9): ``--metrics-out m.json`` writes the process
metrics snapshot on exit (TTFT/inter-token histograms, decode-step and
dispatch counters — ``python -m repro.obs.gate m.json`` is the CI gate),
``--profile-dir DIR`` records serving under the JAX profiler (one
TensorBoard/Perfetto trace holding the engine's ``serve.*`` host spans
and the device ops, on one clock), and ``--metrics-port N`` serves the
live Prometheus text exposition at ``/metrics``.  Tokens are
bit-identical with telemetry on, off, or disabled via
``SME_TELEMETRY=0``, and with the profiler recording or not.

Widths: with no dim flag the published config is served at full width
and depth (on a TPU; see ``chip_smoke.py``).  ``--smoke`` or any dim
override (``--d-model`` ...) scales it down to one layer for the CPU.
The compiled programs persist in JAX's compilation cache
(``launch/cache.py``).

    PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b \
        --sme --s-max 512
    PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b --smoke \
        --requests 6 --max-new 12 [--sme] [--squeeze 1] \
        [--metrics-out m.json --profile-dir prof --metrics-port 9090]
    PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b \
        --d-model 256 --d-ff 512 --artifact qwen.smez
    PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b \
        --d-model 128 --host-devices 8 --mesh 2,2 --sme --backend v1
"""
from __future__ import annotations

import os
import sys

# --host-devices must take effect before the first jax import (jax locks
# the device count on first init), so it is sniffed from argv here —
# both "--host-devices 8" and "--host-devices=8" forms — and only echoed
# into argparse below for --help/validation (argparse reports malformed
# values; the sniff just skips them).
for _i, _a in enumerate(sys.argv):
    if _a == "--host-devices" or _a.startswith("--host-devices="):
        _v = (_a.split("=", 1)[1] if "=" in _a
              else sys.argv[_i + 1] if _i + 1 < len(sys.argv) else "")
        if _v.isdigit():
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={_v}").strip()
        break

import argparse
import time

import jax
import numpy as np

from repro.configs import ARCHS
from repro.models import build_model
from repro.serve import Request, ServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b", choices=sorted(ARCHS))
    from repro.launch.compile import add_scale_args, scaled_config
    add_scale_args(ap)
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--slots", type=int, default=3)
    ap.add_argument("--s-max", type=int, default=96)
    ap.add_argument("--sme", action="store_true",
                    help="serve inline SME-compressed weights")
    ap.add_argument("--squeeze", type=int, default=1)
    ap.add_argument("--artifact", default=None,
                    help="boot from a compiled .smez artifact (no per-boot "
                         "packing; see repro.launch.compile)")
    ap.add_argument("--backend",
                    default=os.environ.get("SME_BACKEND", "auto"),
                    choices=["auto", "xla", "v1", "v2", "v3"],
                    help="SME execution backend; v1/v2/v3 pre-pack kernel "
                         "operands offline and serve through the Pallas "
                         "block-sparse kernels (interpret mode off-TPU); "
                         "v3 is the plane-CSC format (DESIGN.md §2)")
    ap.add_argument("--spec-depth",
                    default=os.environ.get("SME_SPEC_DEPTH") or None,
                    metavar="K|auto",
                    help="enable self-speculative decode (DESIGN.md §11): "
                         "draft greedy tokens over only the K most-"
                         "significant occupied bit-planes per tile group, "
                         "then verify at full precision; 'auto' uses the "
                         "per-layer depths the compiler plan stamped into "
                         "the params.  Accepted tokens are bit-identical "
                         "to non-speculative greedy decode.  Default from "
                         "SME_SPEC_DEPTH; unset = off")
    ap.add_argument("--spec-len", type=int,
                    default=int(os.environ.get("SME_SPEC_LEN") or 0),
                    help="tokens drafted per speculative round (default 4 "
                         "when --spec-depth is set; SME_SPEC_LEN env)")
    ap.add_argument("--chunk-len", type=int, default=None,
                    help="chunked-prefill quota: prompt tokens scored per "
                         "engine step per slot, interleaved with running "
                         "decode rows (DESIGN.md §12; default SME_CHUNK_LEN "
                         "env or 32)")
    ap.add_argument("--page-tokens", type=int, default=None,
                    help="KV page size in tokens for occupancy accounting "
                         "and the prefix-cache pool (default SME_PAGE_TOKENS "
                         "env or 16)")
    ap.add_argument("--prefix-cache", action="store_true", default=None,
                    help="snapshot chunk-aligned prompt prefixes and "
                         "restore them for token-id-exact matches "
                         "(DESIGN.md §12; default SME_PREFIX_CACHE env)")
    ap.add_argument("--stream", action="store_true",
                    help="drive the open-stream API instead of run(): "
                         "submit() requests over time, pump()+step() the "
                         "scheduler, and poll() streamed token events")
    ap.add_argument("--bm", type=int, default=None,
                    help="kernel M block size override (threads through "
                         "core.backend.use_block; default resolves via the "
                         "autotune cache / SME_BM env / 128; DESIGN.md §8)")
    ap.add_argument("--mesh", default="1,1",
                    help="serving mesh as 'data,model' (e.g. 2,2); params "
                         "and slot caches shard across it with bit-"
                         "identical tokens to 1,1 (DESIGN.md §7)")
    ap.add_argument("--host-devices", type=int, default=None,
                    help="force N CPU host devices (must be first-init; "
                         "handled before the jax import above)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the process metrics snapshot (registry "
                         "JSON; DESIGN.md §9) here on exit — CI gates on "
                         "it via `python -m repro.obs.gate`")
    ap.add_argument("--profile-dir", default=None, metavar="DIR",
                    help="record serving under the JAX profiler into DIR "
                         "(an .xplane.pb under plugins/profile/: serve.* "
                         "host spans and device ops on one clock; open "
                         "with TensorBoard or Perfetto; DESIGN.md §9)")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve the Prometheus text exposition on this "
                         "port at /metrics for the process lifetime "
                         "(0 picks an ephemeral port)")
    args = ap.parse_args()

    spec_depth = args.spec_depth
    if spec_depth is not None and spec_depth != "auto":
        if not str(spec_depth).isdigit() or int(spec_depth) < 1:
            ap.error(f"--spec-depth must be a positive int or 'auto', "
                     f"got {spec_depth!r}")
        spec_depth = int(spec_depth)
    spec_kw = {}
    if spec_depth is not None:
        spec_kw = dict(spec_depth=spec_depth, spec_len=args.spec_len)

    if args.metrics_port is not None:
        from repro.obs.httpd import start_metrics_server
        server, _ = start_metrics_server(args.metrics_port)
        print(f"metrics: http://127.0.0.1:{server.server_port}/metrics")

    from repro.launch.cache import enable_compile_cache
    from repro.launch.mesh import make_mesh, parse_mesh
    print(f"compile cache: {enable_compile_cache()}")
    mesh = make_mesh(parse_mesh(args.mesh))
    dev = jax.devices()[0]
    print(f"mesh: {dict(mesh.shape)} over {jax.device_count()} "
          f"{dev.platform} devices ({dev.device_kind})")

    cfg = scaled_config(args)
    api = build_model(cfg)

    serve_kw = {}
    if args.chunk_len is not None:
        serve_kw["chunk_len"] = args.chunk_len
    if args.page_tokens is not None:
        serve_kw["page_tokens"] = args.page_tokens
    if args.prefix_cache:
        serve_kw["prefix_cache"] = True

    if args.artifact:
        from repro.compiler import read_manifest
        man = read_manifest(args.artifact)
        art_arch = man.get("extra", {}).get("arch")
        if art_arch and art_arch != args.arch:
            raise SystemExit(f"artifact {args.artifact} was compiled for "
                             f"--arch {art_arch}, not {args.arch}")
        dims = man.get("extra", {}).get("dims") or {}
        mine = {"d_model": cfg.d_model, "d_ff": cfg.d_ff,
                "vocab": cfg.vocab, "n_layers": cfg.n_layers,
                "head_dim": cfg.hd}
        bad = {k: (v, mine[k]) for k, v in dims.items()
               if k in mine and v != mine[k]}
        if bad:
            raise SystemExit(
                f"artifact {args.artifact} dims do not match this model "
                f"(artifact vs flags): {bad}; pass the same --d-model/"
                f"--d-ff/... the artifact was compiled with")
        kw = {} if args.backend == "auto" else {"backend": args.backend}
        if args.bm is not None:
            kw["bm"] = args.bm
        t0 = time.time()
        eng = ServeEngine.from_artifact(api, args.artifact, mesh=mesh,
                                        slots=args.slots, s_max=args.s_max,
                                        **spec_kw, **serve_kw, **kw)
        print(f"booted from {args.artifact} in {time.time() - t0:.2f}s "
              f"(plan: {len(eng.plan.layers) if eng.plan else 0} layers, "
              f"backend={eng.backend})")
    else:
        params = api.init_params(jax.random.key(0))
        if args.sme:
            from repro.core.integrate import (convert_params_to_sme,
                                              sme_storage_summary)
            params_np = jax.tree.map(np.asarray, params)
            emit = args.backend if args.backend in ("v1", "v2", "v3") \
                else None
            if emit is None and args.backend == "auto" \
                    and jax.default_backend() == "tpu":
                # auto on TPU serves through the Pallas kernels, which need
                # operands emitted offline (jitted programs cannot pack)
                emit = "v2" if args.squeeze >= 1 else "v1"
            plan = None
            if spec_depth == "auto" and emit == "v3":
                # --spec-depth auto needs the per-layer draft depths the
                # compiler stamps into the params (sme_draft_planes meta)
                from repro.compiler.plan import plan_model
                plan = plan_model(params_np, backend=emit)
            params = convert_params_to_sme(params_np, squeeze=args.squeeze,
                                           backend=emit, plan=plan)
            print("SME storage:", sme_storage_summary(params))
            print(f"SME backend: {args.backend}")
        eng = ServeEngine(api, params, slots=args.slots, s_max=args.s_max,
                          backend=args.backend if args.sme else None,
                          mesh=mesh, bm=args.bm, **spec_kw, **serve_kw)

    rng = np.random.default_rng(0)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab, size=5 + i % 4,
                                        dtype=np.int32),
                    max_new_tokens=args.max_new)
            for i in range(args.requests)]
    if args.profile_dir:
        jax.profiler.start_trace(args.profile_dir)
    t0 = time.time()
    if args.stream:
        # open-stream demo: requests arrive two at a time between engine
        # steps; poll() drains token/finish/reject events as they happen
        pending = list(reqs)
        n_events = 0
        for steps in range(500):
            for r in pending[:2]:
                eng.submit(r)
            pending = pending[2:]
            eng.pump()
            eng.step()
            for ev in eng.poll():
                n_events += 1
                if ev["kind"] != "token":
                    print(f"  [{steps:3d}] req {ev['rid']}: {ev['kind']}")
            if not pending and all(r.done or r.outcome for r in reqs):
                break
        done = sum(1 for r in reqs if r.outcome == "completed")
        toks = sum(len(r.out_tokens) for r in reqs)
        print(f"stream: {done}/{len(reqs)} completed, {toks} tokens, "
              f"{n_events} events in {steps + 1} steps")
        stats = {"tokens": toks}
    else:
        stats = eng.run(reqs, max_steps=500)
        print(f"stats: {stats}")
    if args.profile_dir:
        jax.profiler.stop_trace()
        print(f"profile: {args.profile_dir}")
    for r in reqs[:4]:
        print(f"req {r.rid}: prompt={list(r.prompt)} -> {r.out_tokens}")
    print(f"throughput: {stats['tokens'] / (time.time() - t0):.1f} tok/s "
          f"({dev.platform} smoke incl. compile, not a benchmark)")

    if args.metrics_out:
        from repro.obs import write_snapshot
        write_snapshot(args.metrics_out)
        print(f"metrics snapshot: {args.metrics_out}")


if __name__ == "__main__":
    main()
