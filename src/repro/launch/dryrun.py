import os
os.environ["XLA_FLAGS"] = os.environ.get("DRYRUN_XLA_FLAGS",
    "--xla_force_host_platform_device_count=512")
# the dry-run models a pod on forced host devices: pin the CPU platform so
# it never claims an attached accelerator (the per-arch children of --all
# inherit this environment)
os.environ["JAX_PLATFORMS"] = "cpu"
# ^ both MUST precede any jax import: jax locks platform and device count
# on first init.

# Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell and
# extract the roofline inputs (task §MULTI-POD DRY-RUN / §ROOFLINE).
#
# Usage:
#   python -m repro.launch.dryrun --arch qwen2-0.5b --shape train_4k --mesh single
#   python -m repro.launch.dryrun --all [--mesh both] [--force]
#
# Each cell writes experiments/dryrun/<arch>__<shape>__<mesh>.json with:
#   per-device HLO flops / bytes (cost_analysis of the partitioned module),
#   per-device collective bytes by kind (parsed from compiled HLO),
#   memory analysis, roofline terms vs TPU v5e, MODEL_FLOPS and the
#   useful-compute ratio.  ``--all`` runs cells in subprocesses (isolation +
#   caching), honoring each architecture's documented shape skips.

import argparse
import dataclasses
import functools
import json
import pathlib
import subprocess
import sys
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, SHAPES, get_config
from repro.hardware.hlo_analysis import collective_bytes, cost_summary
from repro.hardware.hlo_costs import analyze_hlo
from repro.hardware.tpu_model import V5E, model_flops, roofline_terms
from repro.launch.mesh import make_mesh
from repro.models import build_model, param_count, active_param_count
from repro.optim.optim import adamw, cosine_schedule
from repro.train.loop import make_train_step, pick_microbatches
from repro.parallel.policy import policy_for, use_policy
from repro.parallel.sharding import (
    batch_sharding, cache_sharding, param_sharding, replicated,
)

OUT_DIR = pathlib.Path(os.environ.get("DRYRUN_OUT", "experiments/dryrun"))


def _tree_bytes(tree, shardings=None, mesh=None) -> int:
    """Per-device bytes of a (sharded) abstract tree."""
    total = 0
    leaves = jax.tree.leaves(tree)
    shs = jax.tree.leaves(shardings) if shardings is not None else [None] * len(leaves)
    for leaf, sh in zip(leaves, shs):
        n = int(np.prod(leaf.shape)) * jnp.dtype(leaf.dtype).itemsize
        if sh is not None:
            spec = sh.spec
            denom = 1
            for dim, ax in zip(leaf.shape, spec):
                if ax is None:
                    continue
                axes = (ax,) if isinstance(ax, str) else tuple(ax)
                m = int(np.prod([mesh.shape[a] for a in axes]))
                if dim % m == 0:
                    denom *= m
            n //= denom
        total += n
    return total


def analytic_hbm_bytes(cfg, shape, chips: int, model_size: int,
                       n_params: int, opt_bytes_dev: float,
                       cache_bytes_dev: float = 0.0,
                       param_traffic_dev: float = None) -> float:
    """First-principles per-device HBM traffic per step (lower-bound model).

    * weights: each device materializes its TP shard of every layer once per
      pass (train: fwd + remat recompute + bwd = 3 passes, f32);
    * optimizer: read m,v,p + write m,v,p (adamw) on the FSDP shard;
    * activations: ~12 materialized [tokens, d_model] f32 tensors per layer
      (norms, qkv, attn out, mlp in/out, residuals), MoE inflated by top_k;
    * decode: reads the cache once + writes the new slot.
    """
    p_dev = (param_traffic_dev if param_traffic_dev is not None
             else 4.0 * n_params / model_size)
    layers = cfg.n_layers + cfg.n_enc_layers
    tok_dev = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1) / chips
    moe_f = 1.0 + (cfg.top_k if cfg.n_experts else 0)
    act = tok_dev * cfg.d_model * 4.0 * 12.0 * layers * moe_f
    if shape.kind == "train":
        return 3.0 * p_dev + 2.5 * opt_bytes_dev + 2.0 * act
    if shape.kind == "prefill":
        return p_dev + act + cache_bytes_dev
    return p_dev + 2.0 * cache_bytes_dev + act


def cell_config(arch: str, mesh):
    """Arch config with attention heads padded up to the TP degree.

    Awkward head counts (qwen2 14H, phi4 24H, llava 56H) cannot shard over
    a 16-wide model axis; padding heads to the next multiple (zero-init
    extras) is the standard TP deployment fix.  Recorded per cell; smoke
    tests use the unpadded config.  Ring-attention via shard_map would
    avoid the extra compute — tracked as a §Perf follow-up.
    """
    cfg = get_config(arch)
    msz = mesh.shape.get("model", 1)
    if cfg.n_heads % msz and cfg.pattern != ("mlstm",) * 7 + ("slstm",):
        hd = cfg.hd
        padded = -(-cfg.n_heads // msz) * msz
        cfg = dataclasses.replace(cfg, n_heads=padded, head_dim=hd)
        return cfg, padded
    return cfg, 0


def build_cell(arch: str, shape_name: str, mesh, variant: str = "base"):
    """Returns (jitted fn, example args tree, in_shardings, meta).

    Variants (§Perf hillclimbs):
      base       — the paper-faithful baseline shardings
      full_dp    — small-model mode: pure DP over (pod,data,model), no TP
      remat_dots — remat policy saves dot outputs (skips recompute collectives)
      bf16       — serve/train with bf16 params (dense baseline for sme)
      sme        — SME-packed weights (uint8 codes + 1-bit signs) in the graph
    """
    cfg, padded = cell_config(arch, mesh)
    shape = SHAPES[shape_name]
    api = build_model(cfg, shape)
    aparams = jax.eval_shape(api.init_params, jax.random.key(0))
    tp = variant not in ("full_dp", "replicated")
    fsdp = variant != "replicated"
    if variant == "bf16":
        from repro.core.integrate import cast_params
        aparams = cast_params(aparams)
    elif variant == "sme":
        from repro.core.integrate import abstract_sme_params, cast_params
        aparams = cast_params(abstract_sme_params(aparams))
    ps = param_sharding(mesh, aparams, tp=tp, fsdp=fsdp)
    ps_traffic = param_sharding(mesh, aparams, fsdp=False, tp=tp)
    n_params = param_count(jax.eval_shape(api.init_params, jax.random.key(0)))
    n_active = active_param_count(
        jax.eval_shape(api.init_params, jax.random.key(0)), cfg)
    rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    meta = {"params": n_params, "active_params": n_active, "kind": shape.kind,
            "padded_heads": padded, "variant": variant,
            "param_traffic_dev": _tree_bytes(aparams, ps_traffic, mesh)}

    if shape.kind == "train":
        opt = adamw(cosine_schedule(3e-4, 100, 10_000), weight_decay=0.1)
        aopt = jax.eval_shape(opt.init, aparams)
        os_ = param_sharding(mesh, aopt, tp=tp, fsdp=fsdp)
        specs = api.input_specs(shape)
        bs = batch_sharding(
            mesh, specs,
            include_model=(variant in ("full_dp", "replicated")))
        astep = jax.ShapeDtypeStruct((), jnp.int32)
        import numpy as _np
        dpn = int(_np.prod([mesh.shape[a] for a in mesh.axis_names
                            if a in ("pod", "data")]))
        micro = pick_microbatches(cfg, shape, dpn)
        meta["microbatches"] = micro
        train_step = make_train_step(api.train_loss, opt, micro)

        fn = jax.jit(train_step,
                     in_shardings=(ps, os_, rep, bs),
                     out_shardings=(ps, os_, rep),
                     donate_argnums=(0, 1))
        args = (aparams, aopt, astep, specs)
        meta["tokens"] = shape.global_batch * shape.seq_len
        meta["opt_bytes_dev"] = _tree_bytes(aopt, os_, mesh)
        meta["arg_bytes_per_dev"] = (
            _tree_bytes(aparams, ps, mesh) + meta["opt_bytes_dev"]
            + _tree_bytes(specs, bs, mesh))
        return fn, args, meta

    if shape.kind == "prefill":
        specs = api.input_specs(shape)
        bs = batch_sharding(mesh, specs)
        s_max = shape.seq_len

        def prefill(params, batch):
            return api.prefill(params, batch, s_max=s_max)

        acache = jax.eval_shape(
            functools.partial(_prefill_shape_helper, api, specs, s_max))
        logits_sh, cache_sh = _prefill_out_shardings(mesh, acache, shape, cfg)
        fn = jax.jit(prefill, in_shardings=(ps, bs),
                     out_shardings=(logits_sh, cache_sh))
        args = (aparams, specs)
        meta["tokens"] = shape.global_batch * shape.seq_len
        meta["arg_bytes_per_dev"] = (
            _tree_bytes(aparams, ps, mesh) + _tree_bytes(specs, bs, mesh))
        return fn, args, meta

    # decode: one token against a seq_len-deep cache
    b, s = shape.global_batch, shape.seq_len
    if cfg.n_enc_layers:
        acache = jax.eval_shape(functools.partial(
            api.init_cache, batch=b, s_max=s, src_len=s // 2))
    else:
        acache = jax.eval_shape(functools.partial(
            api.init_cache, batch=b, s_max=s))
    cs = cache_sharding(mesh, acache, b)
    specs = api.input_specs(shape)
    bs = batch_sharding(mesh, specs)
    rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    # vectorized decode contract: per-row positions + active mask (the
    # serving engine issues one such call per step for a ragged batch)
    apos = jax.ShapeDtypeStruct((b,), jnp.int32)
    aact = jax.ShapeDtypeStruct((b,), jnp.bool_)
    logits_sh = _logits_sharding(mesh, shape, cfg)

    def serve_step(params, token, caches, pos, active):
        return api.decode_step(params, token, caches, pos, active)

    fn = jax.jit(serve_step,
                 in_shardings=(ps, bs["token"], cs, rep, rep),
                 out_shardings=(logits_sh, cs),
                 donate_argnums=(2,))
    args = (aparams, specs["token"], acache, apos, aact)
    meta["tokens"] = shape.global_batch  # one new token per sequence
    meta["cache_bytes_dev"] = _tree_bytes(acache, cs, mesh)
    meta["arg_bytes_per_dev"] = (
        _tree_bytes(aparams, ps, mesh) + meta["cache_bytes_dev"])
    return fn, args, meta


def _prefill_shape_helper(api, specs, s_max):
    # runs under eval_shape: abstract prefill to get cache structure
    zeros = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), specs)
    params = api.init_params(jax.random.key(0))
    return api.prefill(params, zeros, s_max=s_max)


def _logits_sharding(mesh, shape, cfg):
    from repro.parallel.sharding import dp_axes
    dp = dp_axes(mesh)
    dpn = int(np.prod([mesh.shape[a] for a in dp]))
    bax = dp if shape.global_batch % max(dpn, 1) == 0 else None
    vax = "model" if cfg.vocab % mesh.shape["model"] == 0 else None
    return jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(bax, vax))


def _prefill_out_shardings(mesh, acache_out, shape, cfg):
    _, acache = acache_out
    logits_sh = _logits_sharding(mesh, shape, cfg)
    cache_sh = cache_sharding(mesh, acache, shape.global_batch)
    return logits_sh, cache_sh


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             variant: str = "base") -> dict:
    cfg = get_config(arch)
    skip = cfg.skip_reason(shape_name)
    if skip:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                "status": "skipped", "reason": skip}
    if mesh_kind == "multi":
        mesh = make_mesh((2, 16, 16), ("pod", "data", "model"))
    else:
        mesh = make_mesh((16, 16))
    shape = SHAPES[shape_name]
    cfg, _pad = cell_config(arch, mesh)
    import dataclasses as _dc
    policy = policy_for(mesh, cfg, shape.kind,
                        full_dp=(variant in ("full_dp", "replicated")))
    if variant == "remat_dots":
        policy = _dc.replace(policy, remat_policy="dots")
    if shape.kind == "train" and variant != "base":
        # adaptive CE chunk: per-chunk logits <= ~1.2GB/device.  Each chunk
        # all-reduces the (tied) head gradient once — fewer, larger chunks
        # slash that collective (measured 17.4GB -> ~1GB on qwen2 full_dp).
        chips = int(np.prod(list(mesh.shape.values())))
        v_loc = cfg.vocab / (1 if not policy.heads_tp and policy.full_dp
                             else mesh.shape.get("model", 1))
        b_loc = max(shape.global_batch // policy.dp_size, 1)
        budget = 1.2e9
        c = int(budget / max(b_loc * v_loc * 4.0, 1))
        c = max(128, min(1 << (c.bit_length() - 1) if c > 0 else 128,
                         shape.seq_len))
        policy = _dc.replace(policy, loss_chunk=c)
    t0 = time.time()
    with mesh, use_policy(policy):
        fn, args, meta = build_cell(arch, shape_name, mesh, variant)
        lowered = fn.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        # --- memory analysis (proves it fits) ---
        mem = {}
        try:
            ma = compiled.memory_analysis()
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes",
                      "alias_size_in_bytes"):
                if hasattr(ma, k):
                    mem[k] = int(getattr(ma, k))
        except (RuntimeError, NotImplementedError, AttributeError) as e:
            # CPU backend may not support memory_analysis
            mem["error"] = str(e)
        mem["analytic_arg_bytes_per_dev"] = int(meta["arg_bytes_per_dev"])

        # --- cost analysis (FLOPs / bytes of the partitioned module) ---
        raw_cost = cost_summary(compiled)

        # --- loop-aware re-analysis: XLA's cost_analysis counts while
        # bodies once; analyze_hlo multiplies by parsed trip counts and
        # execution-weights collectives (see hardware/hlo_costs.py) ---
        hlo = compiled.as_text()
        la = analyze_hlo(hlo)
        raw_coll, _raw_kinds = collective_bytes(hlo)
        cost = {"flops": max(la["flops"], raw_cost["flops"]),
                "bytes": max(la["bytes"], raw_cost["bytes"])}
        coll_total, coll_kinds = la["collective_bytes"], la["collectives"]

    chips = int(np.prod(list(mesh.shape.values())))
    kind = meta["kind"]
    mf = model_flops(meta["params"], meta["tokens"],
                     "train" if kind == "train" else "serve",
                     n_active_params=meta["active_params"])
    ana_bytes = analytic_hbm_bytes(
        cfg, shape, chips, mesh.shape["model"], meta["params"],
        meta.get("opt_bytes_dev", 0.0), meta.get("cache_bytes_dev", 0.0),
        param_traffic_dev=meta.get("param_traffic_dev"))
    terms = roofline_terms(cost["flops"], ana_bytes, coll_total, V5E)
    terms["memory_s_hlo_upper"] = cost["bytes"] / V5E.hbm_bw
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind, "kind": kind,
        "variant": variant, "status": "ok", "chips": chips,
        "n_params": meta["params"], "n_active_params": meta["active_params"],
        "tokens_per_step": meta["tokens"],
        "per_device": {
            "hlo_flops": cost["flops"], "hlo_bytes": cost["bytes"],
            "hbm_bytes_analytic": ana_bytes,
            "collective_bytes": coll_total, "collectives": coll_kinds,
            "raw_cost_analysis": raw_cost,
            "raw_collective_bytes_once": raw_coll,
            "unknown_trip_loops": la["unknown_trip_loops"],
        },
        "memory": mem,
        "model_flops_global": mf,
        "model_flops_per_dev": mf / chips,
        "useful_compute_ratio": (mf / chips) / cost["flops"] if cost["flops"] else None,
        "roofline": terms,
        "timing": {"lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2)},
    }
    return rec


def cell_path(arch, shape, mesh_kind) -> pathlib.Path:
    return OUT_DIR / f"{arch}__{shape}__{mesh_kind}.json"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--variant", default="base",
                    choices=["base", "full_dp", "remat_dots", "bf16", "sme",
                             "replicated"])
    args = ap.parse_args()
    OUT_DIR.mkdir(parents=True, exist_ok=True)

    if args.all:
        # one subprocess per arch (amortizes startup over its 8 cells)
        failures = []
        for a in sorted(ARCHS):
            print(f"[arch] {a} ...", flush=True)
            r = subprocess.run(
                [sys.executable, "-m", "repro.launch.dryrun",
                 "--arch", a, "--shape", "all", "--mesh", args.mesh]
                + (["--force"] if args.force else []),
                capture_output=True, text=True,
                env={**os.environ, "PYTHONPATH": os.environ.get("PYTHONPATH", "src")},
            )
            print(r.stdout[-3000:])
            if r.returncode != 0:
                failures.append(a)
                print(f"[FAIL] {a}\n{r.stderr[-4000:]}")
        print(f"done; {len(failures)} arch failures: {failures}")
        sys.exit(1 if failures else 0)

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    n_err = 0
    for s in shapes:
        for m in meshes:
            if args.variant != "base":
                path = (pathlib.Path("experiments/perf")
                        / f"{args.arch}__{s}__{m}__{args.variant}.json")
                path.parent.mkdir(parents=True, exist_ok=True)
            else:
                path = cell_path(args.arch, s, m)
            if path.exists() and not args.force:
                print(f"[cached] {path.name}")
                continue
            try:
                rec = run_cell(args.arch, s, m, args.variant)
            except Exception:  # smelint: disable=EXC001 — sweep driver: any cell failure becomes an error record, the sweep continues
                rec = {"arch": args.arch, "shape": s, "mesh": m,
                       "status": "error", "trace": traceback.format_exc()[-6000:]}
            path.write_text(json.dumps(rec, indent=2, default=str))
            status = rec["status"]
            print(f"{args.arch} {s} {m}: {status}", flush=True)
            if status == "ok":
                pd = rec["per_device"]
                print(f"  flops/dev={pd['hlo_flops']:.3e} "
                      f"coll/dev={pd['collective_bytes']:.3e} "
                      f"temp={rec['memory'].get('temp_size_in_bytes')} "
                      f"t_compile={rec['timing']['compile_s']}s")
            elif status == "error":
                n_err += 1
                print(rec["trace"][-1500:])
    sys.exit(1 if n_err else 0)


if __name__ == "__main__":
    main()
