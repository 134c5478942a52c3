"""Offline model compiler driver: plan -> reorder -> pack -> ``.smez``.

    PYTHONPATH=src python -m repro.launch.compile --arch qwen1.5-0.5b \
        --d-model 256 --d-ff 512 --out qwen.smez [--budget 0.06] \
        [--backend auto|v1|v2|none] [--no-reorder] [--ckpt DIR]

The artifact then boots serving with zero per-boot packing:

    PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b \
        --d-model 256 --d-ff 512 --artifact qwen.smez
"""
from __future__ import annotations

import argparse
import pathlib
import time

import jax
import numpy as np

from repro.configs import ARCHS, scale_down
from repro.models import build_model


def add_scale_args(ap: argparse.ArgumentParser) -> None:
    """Dim overrides shared by compile/serve so artifacts match the model.
    Without any of them (and without ``--smoke``) the published config
    is used at its full widths and depth."""
    ap.add_argument("--d-model", type=int, default=None)
    ap.add_argument("--d-ff", type=int, default=None)
    ap.add_argument("--head-dim", type=int, default=None)
    ap.add_argument("--vocab", type=int, default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="scale the config down to its one-layer CPU smoke "
                         "size (implied by any dim override)")


def scaled_config(args):
    """The published config, or its ``scale_down`` when ``--smoke`` or a
    dim override asks for it."""
    over = {k: getattr(args, a) for k, a in
            [("d_model", "d_model"), ("d_ff", "d_ff"),
             ("head_dim", "head_dim"), ("vocab", "vocab")]
            if getattr(args, a) is not None}
    if not over and not args.smoke:
        return ARCHS[args.arch]
    return scale_down(ARCHS[args.arch], **over)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b", choices=sorted(ARCHS))
    add_scale_args(ap)
    ap.add_argument("--out", default=None,
                    help="artifact directory (default <arch>.smez)")
    ap.add_argument("--ckpt", default=None,
                    help="checkpoint dir to compile (default: fresh init)")
    ap.add_argument("--budget", type=float, default=0.06,
                    help="global weighted relative-error budget")
    ap.add_argument("--backend", default="auto",
                    choices=["auto", "v1", "v2", "v3", "none"],
                    help="kernel operand set to emit per layer (auto "
                         "prices v3 plane-CSC vs v2/v1 per layer by "
                         "measured bytes)")
    ap.add_argument("--measure", default="trial",
                    choices=["trial", "analytic"])
    ap.add_argument("--objective", default="bytes",
                    choices=["bytes", "energy"])
    ap.add_argument("--no-reorder", action="store_true",
                    help="skip the tile-densifying row reordering")
    ap.add_argument("--verify", action="store_true",
                    help="re-hash the written artifact payloads")
    args = ap.parse_args()

    cfg = scaled_config(args)
    api = build_model(cfg)
    params = api.init_params(jax.random.key(0))
    if args.ckpt:
        from repro.train.checkpoint import restore
        params = restore(args.ckpt, None, params)
    params = jax.tree.map(np.asarray, params)

    from repro.compiler import compile_model, verify_artifact
    from repro.core.integrate import sme_storage_summary

    out = args.out or f"{args.arch}.smez"
    backend = None if args.backend == "none" else args.backend
    t0 = time.perf_counter()
    packed, plan = compile_model(
        params, out=out, error_budget=args.budget, backend=backend,
        reorder=not args.no_reorder, measure=args.measure,
        objective=args.objective,
        extra={"arch": args.arch, "config": cfg.name,
               "dims": {"d_model": cfg.d_model, "d_ff": cfg.d_ff,
                        "vocab": cfg.vocab, "n_layers": cfg.n_layers,
                        "head_dim": cfg.hd},
               "serve_backend": None if backend is None else "auto"})
    dt = time.perf_counter() - t0

    print(f"{'layer':42s} {'shape':14s} {'Nq':>3s} {'S':>2s} {'x':>2s} "
          f"{'be':>4s} {'perm':>4s} {'B/w':>6s} {'xbar red':>9s}")
    for key, lp in sorted(plan.layers.items()):
        print(f"{key:42s} {str(lp.shape):14s} {lp.n_bits:3d} {lp.window:2d} "
              f"{lp.squeeze:2d} {str(lp.backend):>4s} "
              f"{'yes' if lp.reorder else '-':>4s} "
              f"{lp.bytes_per_weight:6.3f} {lp.crossbar_reduction:8.2f}x")
    s = plan.summary()
    print(f"plan: {s['layers']} layers, weighted_err={s['weighted_error']:.4f} "
          f"(budget {args.budget}), crossbar_reduction="
          f"{s['crossbar_reduction']:.2f}x, reordered={s['reordered_layers']}")
    print("storage:", sme_storage_summary(packed))
    n_payload = sum(1 for _ in pathlib.Path(out, "payload").iterdir())
    disk = sum(f.stat().st_size
               for f in pathlib.Path(out).rglob("*") if f.is_file())
    print(f"wrote {out}: {n_payload} payloads, {disk / 1e6:.2f} MB, "
          f"compiled in {dt:.1f}s")
    if args.verify:
        print(f"verified {verify_artifact(out)} payload hashes")


if __name__ == "__main__":
    main()
