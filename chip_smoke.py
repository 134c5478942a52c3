"""Chip smoke: serve qwen1.5-0.5b at its published widths on a TPU.

    python chip_smoke.py                # one chip: bf16 and SME serving
    python chip_smoke.py --four-chips   # SME serving, 2x2 mesh vs 1x1 mesh

One chip (no arguments):
  a. device: platform, kind and count as JAX reports them; anything but
     a TPU fails here (no CPU fallback).
  b. bf16 serving: qwen1.5-0.5b (24 layers, d_model 1024, 16 heads, d_ff
     2816, vocab 151,936) with random weights from ``--seed``, served by
     ``ServeEngine`` with 4 slots, s_max 512 and 8 ragged greedy requests
     (prompts 16-200 tokens, 16 new tokens each).  Every request must
     complete with 16 in-vocab tokens.
  c. SME serving: the same weights through ``convert_params_to_sme(...,
     backend="v2")`` (the format ``auto`` picks on a TPU), the same
     requests and checks.  The compiled prefill and decode programs must
     hold the Pallas kernels (``tpu_custom_call``), and the first decode
     step's logits must agree with the ``xla`` dequant backend on the
     same packed weights within ``LOGITS_RTOL``.
  d. compile seconds and a rough tokens/s, printed as smoke figures
     (warm re-run of the same requests, one process, no profiler): they
     are not a benchmark.
  e. the last stdout line is ``{"ok": true, "device": {...}}``.

``--four-chips`` runs only the SME serve at published widths with depth
cut to ``FOUR_CHIP_LAYERS`` layers, on a 2x2 ("data", "model") mesh and
on the 1x1 mesh, with the same requests.  The emitted tokens must match,
or else the first decode step's logits must agree within
``LOGITS_RTOL``; parameters and caches must be spread over all 4 devices.

A failed check exits non-zero before the last line.  Inputs are made
from ``--seed`` and tracked files only: ``SME_*`` environment overrides
(autotune cache, block size, backend, ...) are dropped before import.
The compiled programs persist in JAX's compilation cache
(``repro.launch.cache``).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

ARCH = "qwen1.5-0.5b"
SLOTS, S_MAX = 4, 512
N_REQUESTS, NEW_TOKENS = 8, 16
PROMPT_MIN, PROMPT_MAX = 16, 200
#: logits prefix for the comparisons: prefill LOGITS_PREFIX tokens, then
#: one decode step
LOGITS_PREFIX = 32
#: max |a - b| / max |b| over the first decode step's logits.  The xla
#: backend rounds each dequantized weight to bf16 (relative error up to
#: 2^-9) before a bf16 MXU matmul, while the v2 kernel multiplies the
#: exact f32 weight; both accumulate in f32.  An error of that size per
#: projection, compounded through 24 residual layers and the tied head,
#: stays near 1e-2 of the logit range; a wrong kernel (a misplaced tile,
#: sign or scale) moves logits by their own magnitude.  The mesh
#: comparison uses the same bound: the exact-numerics posture makes it
#: bit-identical by construction, so any difference is one of rounding.
LOGITS_RTOL = 2e-2
FOUR_CHIP_LAYERS = 4
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


class CompileClock:
    """Sums the backend compile time JAX reports for every program."""

    def __init__(self):
        self.seconds = 0.0
        self.programs = 0

    def __call__(self, event: str, duration: float, **_kw) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.seconds += duration
            self.programs += 1


def make_prompts(vocab: int, seed: int):
    rng = np.random.default_rng(seed)
    lens = rng.integers(PROMPT_MIN, PROMPT_MAX + 1, N_REQUESTS)
    lens[0], lens[1] = PROMPT_MAX, PROMPT_MIN
    return [rng.integers(0, vocab, size=int(n), dtype=np.int32)
            for n in lens]


def serve(eng, prompts, vocab: int, label: str):
    """Run the prompts through ``eng`` and check every request."""
    from repro.serve import Request
    reqs = [Request(rid=i, prompt=p, max_new_tokens=NEW_TOKENS)
            for i, p in enumerate(prompts)]
    t0 = time.perf_counter()
    stats = eng.run(reqs, max_steps=4000)
    wall = time.perf_counter() - t0
    check(stats["completed"] == len(reqs),
          f"{label}: {stats['completed']}/{len(reqs)} requests completed")
    for r in reqs:
        toks = np.asarray(r.out_tokens)
        check(toks.size == NEW_TOKENS,
              f"{label}: request {r.rid} emitted {toks.size} tokens")
        check(bool(((toks >= 0) & (toks < vocab)).all()),
              f"{label}: request {r.rid} emitted out-of-vocab tokens")
    return [list(r.out_tokens) for r in reqs], wall


def step_logits(eng, tokens, backend: str):
    """First decode step's logits over ``eng.params`` with ``backend``:
    prefill ``tokens[:, :-1]``, then decode ``tokens[:, -1]``; traced
    under the engine's scope (mesh, sharding policy)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.backend import use_backend

    b, s = tokens.shape
    api = eng.api

    def fn(params, toks):
        _, caches = api.prefill(params, {"tokens": toks[:, :-1]}, s_max=s)
        logits, _ = api.decode_step(params, toks[:, -1:], caches,
                                    jnp.full((b,), s - 1, jnp.int32))
        return logits.astype(jnp.float32)

    rep = NamedSharding(eng.mesh, P())
    with eng.scope(), use_backend(backend):
        out = jax.jit(fn, in_shardings=(eng.param_sh, rep),
                      out_shardings=rep)(eng.params, jnp.asarray(tokens))
    return np.asarray(out)


def logits_rel_err(a, b) -> float:
    check(bool(np.isfinite(a).all() and np.isfinite(b).all()),
          "non-finite logits")
    return float(np.abs(a - b).max() / np.abs(b).max())


def kernel_programs_check(eng, label: str) -> None:
    """The engine's compiled prefill and decode programs must hold the
    Pallas kernels: no interpret mode, no xla fallback."""
    lowered = eng.lower_programs(SLOTS, LOGITS_PREFIX, k=1)
    for name, low in lowered.items():
        check("tpu_custom_call" in low.compile().as_text(),
              f"{label}: compiled {name} program holds no tpu_custom_call")
    print(f"[c] {label}: compiled prefill and decode programs hold "
          f"tpu_custom_call")


def init_params(api, seed: int):
    """Random weights from ``seed``, cast to bf16 on the device."""
    import jax
    import jax.numpy as jnp

    def init(key):
        p = api.init_params(key)
        return jax.tree.map(
            lambda x: x.astype(jnp.bfloat16)
            if jnp.issubdtype(x.dtype, jnp.floating) else x, p)

    return jax.jit(init)(jax.random.key(seed))


def to_sme(params):
    import jax
    from repro.core.integrate import convert_params_to_sme
    t0 = time.perf_counter()
    sme = convert_params_to_sme(jax.tree.map(np.asarray, params),
                                backend="v2")
    print(f"[c] SME v2 conversion on the host: "
          f"{time.perf_counter() - t0:.1f} s")
    return sme


def one_chip(seed: int, clock: CompileClock) -> None:
    import jax
    from repro.configs import ARCHS
    from repro.models import build_model
    from repro.serve import ServeEngine

    cfg = ARCHS[ARCH]
    api = build_model(cfg)
    prompts = make_prompts(cfg.vocab, seed)
    print(f"[b] {ARCH}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"{cfg.n_heads} heads, d_ff {cfg.d_ff}, vocab {cfg.vocab}; "
          f"{N_REQUESTS} requests, prompts "
          f"{min(map(len, prompts))}-{max(map(len, prompts))} tokens")

    params = init_params(api, seed)
    c0 = clock.seconds
    eng = ServeEngine(api, params, slots=SLOTS, s_max=S_MAX, seed=seed)
    bf16_toks, cold = serve(eng, prompts, cfg.vocab, "bf16")
    _, warm = serve(eng, prompts, cfg.vocab, "bf16 warm")
    n_tok = N_REQUESTS * NEW_TOKENS
    print(f"[b] bf16 serving: {N_REQUESTS}/{N_REQUESTS} completed")
    print(f"[d] bf16 smoke: compile {clock.seconds - c0:.1f} s, cold run "
          f"{cold:.1f} s, warm run {n_tok / warm:.1f} tok/s "
          f"(smoke, not a benchmark)")
    del eng

    sme = to_sme(params)
    del params
    c0 = clock.seconds
    eng = ServeEngine(api, sme, slots=SLOTS, s_max=S_MAX, seed=seed,
                      backend="auto")
    sme_toks, cold = serve(eng, prompts, cfg.vocab, "sme")
    _, warm = serve(eng, prompts, cfg.vocab, "sme warm")
    print(f"[c] SME serving: {N_REQUESTS}/{N_REQUESTS} completed; "
          f"{sum(a == b for a, b in zip(sme_toks, bf16_toks))}/"
          f"{N_REQUESTS} requests emit the bf16 tokens")
    print(f"[d] SME smoke: compile {clock.seconds - c0:.1f} s, cold run "
          f"{cold:.1f} s, warm run {n_tok / warm:.1f} tok/s "
          f"(smoke, not a benchmark)")
    kernel_programs_check(eng, "sme")

    toks = np.random.default_rng(seed + 1).integers(
        0, cfg.vocab, size=(SLOTS, LOGITS_PREFIX + 1), dtype=np.int32)
    rel = logits_rel_err(step_logits(eng, toks, "auto"),
                         step_logits(eng, toks, "xla"))
    print(f"[c] first decode step logits, v2 kernels vs xla dequant: "
          f"max|diff|/max|xla| = {rel:.3e} (tolerance {LOGITS_RTOL})")
    check(rel <= LOGITS_RTOL, f"v2 vs xla logits differ by {rel:.3e}")


def spread(tree):
    """(devices holding shards, fraction of bytes in sharded leaves)."""
    import jax
    devs, total, sharded = set(), 0, 0
    for leaf in jax.tree.leaves(tree):
        devs.update(s.device for s in leaf.addressable_shards)
        total += leaf.nbytes
        if leaf.addressable_shards[0].data.shape != leaf.shape:
            sharded += leaf.nbytes
    return devs, sharded / max(total, 1)


def four_chips(seed: int, clock: CompileClock) -> None:
    import jax
    from repro.configs import ARCHS
    from repro.launch.mesh import make_mesh
    from repro.models import build_model
    from repro.serve import ServeEngine

    check(jax.device_count() == 4,
          f"--four-chips needs 4 devices, found {jax.device_count()}")
    cfg = dataclasses.replace(ARCHS[ARCH], n_layers=FOUR_CHIP_LAYERS)
    api = build_model(cfg)
    prompts = make_prompts(cfg.vocab, seed)
    print(f"[4] {ARCH} at published widths, depth cut to {cfg.n_layers} "
          f"layers, SME v2; {N_REQUESTS} requests")
    sme = to_sme(init_params(api, seed))
    toks = np.random.default_rng(seed + 1).integers(
        0, cfg.vocab, size=(SLOTS, LOGITS_PREFIX + 1), dtype=np.int32)
    out = {}
    for shape in ((1, 1), (2, 2)):
        c0 = clock.seconds
        eng = ServeEngine(api, sme, slots=SLOTS, s_max=S_MAX, seed=seed,
                          backend="auto", mesh=make_mesh(shape))
        emitted, cold = serve(eng, prompts, cfg.vocab, f"mesh {shape}")
        kernel_programs_check(eng, f"mesh {shape}")
        logits = step_logits(eng, toks, "auto")
        p_devs, p_frac = spread(eng.params)
        c_devs, c_frac = spread(eng.caches)
        print(f"[4] mesh {shape}: {N_REQUESTS}/{N_REQUESTS} completed, "
              f"compile {clock.seconds - c0:.1f} s, cold run {cold:.1f} s; "
              f"params on {len(p_devs)} devices ({p_frac:.2f} of bytes "
              f"sharded), caches on {len(c_devs)} devices "
              f"({c_frac:.2f} sharded)")
        out[shape] = (emitted, logits, p_devs, c_devs, p_frac, c_frac)
        del eng
    e1, l1, *_ = out[(1, 1)]
    e4, l4, p_devs, c_devs, p_frac, c_frac = out[(2, 2)]
    check(len(p_devs) == 4 and p_frac > 0.5,
          f"2x2 params on {len(p_devs)} devices, {p_frac:.2f} sharded")
    check(len(c_devs) == 4 and c_frac > 0.5,
          f"2x2 caches on {len(c_devs)} devices, {c_frac:.2f} sharded")
    same = sum(a == b for a, b in zip(e1, e4))
    rel = logits_rel_err(l4, l1)
    print(f"[4] 2x2 vs 1x1: {same}/{N_REQUESTS} requests emit identical "
          f"tokens; first decode step logits max|diff|/max|1x1| = "
          f"{rel:.3e} (tolerance {LOGITS_RTOL})")
    check(same == N_REQUESTS or rel <= LOGITS_RTOL,
          f"2x2 and 1x1 disagree: {same}/{N_REQUESTS} token streams "
          f"equal, logits differ by {rel:.3e}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="only the SME serve on a 2x2 mesh vs the 1x1 mesh")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    for key in [k for k in os.environ if k.startswith("SME_")]:
        del os.environ[key]
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "src"))
    import jax
    from repro.launch.cache import enable_compile_cache

    devs = jax.devices()
    dev = devs[0]
    print(f"[a] device: platform={dev.platform} kind={dev.device_kind!r} "
          f"count={len(devs)}")
    check(dev.platform == "tpu", f"no TPU: JAX runs on {dev.platform!r}")
    print(f"[a] compile cache: {enable_compile_cache()}")
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    t0 = time.perf_counter()
    if args.four_chips:
        four_chips(args.seed, clock)
    else:
        one_chip(args.seed, clock)
    print(f"[d] total: {time.perf_counter() - t0:.1f} s, backend compile "
          f"{clock.seconds:.1f} s over {clock.programs} programs")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
