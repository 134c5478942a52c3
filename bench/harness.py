"""One run of one cell: set-up, the measured window, the trace's reading
and the check of what the window served against the plain reference.

Set-up (``setup_s``, from process start to the window's opening): the
device check, the weights drawn on the device from the seed, the
program's own SME packing of them on the host, the engine, a warm-up
that compiles (or loads from the persistent cache) every program the
window can call, and the loop's pre-roll (``loadgen``).  The window then
runs the cell's traffic for ``seconds``; with ``trace`` it runs under the
profiler for at most ``TRACE_SECONDS`` and reports the per-layer metrics
instead.  After the window the engine is freed and the reference scores
a sample of the requests the window finished.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import os
import resource
import shutil
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Dict, List, Optional

import numpy as np

import loadgen
import traffic as traffic_mod
import tracing
from arch import for_config
from peaks import peaks
from spec import BENCH, Cell, metric_reader

TRACE_SECONDS = 6.0
#: one packing call (one thread) takes at most PACK_LAYERS layers and
#: PACK_ELEMENTS elements of one leaf; the cap, 4 layers of 1024 x 2816,
#: is the largest job that layers alone make in the first configuration,
#: so its jobs stay whole.  At most PACK_THREADS calls run at once, each
#: holding a few hundred MB of float64
PACK_LAYERS = 4
PACK_ELEMENTS = 4 * 1024 * 2816
PACK_THREADS = 8
CACHE = BENCH / ".cache"
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_hits")


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def host_peak_gb() -> float:
    """The process's peak resident host memory so far."""
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20


@dataclasses.dataclass
class Ctx:
    """What a per-layer metric reader may read."""
    cell: Cell
    peaks: Dict[str, float]
    win: loadgen.WindowResult
    trace: Optional[tracing.Trace]


class CompileCounter:
    """Counts programs compiled or loaded from the cache while ``on``."""

    def __init__(self):
        self.on = False
        self.count = 0

    def __call__(self, event: str, *args, **kw) -> None:
        if self.on and event in COMPILE_EVENTS:
            self.count += 1


def devices(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX runs on {devs[0].platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chips, JAX finds "
                         f"{len(devs)}")
    return devs


def compile_cache() -> str:
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE / "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def cuts(shape: tuple, layers: bool = True) -> Optional[list]:
    """How ``pack`` cuts a leaf of ``shape`` (leading axes, then a matrix)
    into jobs: runs along the first axis of at most ``PACK_LAYERS``
    (``layers``) and at most ``PACK_ELEMENTS`` elements; where one index
    of that axis holds more, each index on its own, cut along the next
    axis the same way (layers, then experts).  ``[(slice, sub), ...]``
    with ``sub`` None or the cuts of the next axis; None for a matrix,
    which is one job."""
    if len(shape) <= 2:
        return None
    one = math.prod(shape[1:])
    step = min(PACK_LAYERS if layers else shape[0], PACK_ELEMENTS // one)
    if step >= 1:
        return [(slice(lo, lo + step), None)
                for lo in range(0, shape[0], step)]
    sub = cuts(shape[1:], layers=False)
    return [(slice(i, i + 1), sub) for i in range(shape[0])]


def _jobs(c: Optional[list]) -> List[tuple]:
    """The index of each job of the cuts ``c``, in order."""
    if c is None:
        return [()]
    return [(s,) + rest for s, sub in c for rest in _jobs(sub)]


def _join(c: Optional[list], parts, path: str, axis: int = 0) -> Dict:
    """The packed parts (an iterator, in ``_jobs`` order) joined back
    along the axes they were cut on.  Parts whose shapes differ off that
    axis (kernel operands padded to another length) cannot be joined:
    an error."""
    import jax.numpy as jnp
    if c is None:
        return next(parts)
    ps = [next(parts) if sub is None else _join(sub, parts, path, axis + 1)
          for _, sub in c]
    if len(ps) == 1:
        return ps[0]
    out = {}
    for key in ps[0]:
        shapes = {p[key].shape[:axis] + p[key].shape[axis + 1:] for p in ps}
        if len(shapes) != 1:
            raise ValueError(f"{path}/{key}: parts packed to shapes "
                             f"{sorted(shapes)}")
        out[key] = jnp.concatenate([p[key] for p in ps], axis)
    return out


def _names(path) -> tuple:
    """A tree path as the names ``convert_params_to_sme`` walks by."""
    return tuple(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


def pack(tree: Dict, fmt: Dict) -> Dict:
    """The program's SME conversion of every leaf its own predicate
    selects, anywhere in the tree, one call per job of ``cuts``, run in
    threads (the calls are independent and numpy releases the GIL), the
    parts joined back: the host holds about threads times one job."""
    import jax
    from repro.core.integrate import _eligible, convert_params_to_sme
    kw = dict(n_bits=fmt["n_bits"], window=fmt["window"],
              squeeze=fmt["squeeze"], tile=(fmt["tile"], fmt["tile"]),
              backend=fmt["pack_backend"])

    def one(path, part):
        for key in reversed(path):
            part = {key: part}
        out = convert_params_to_sme(part, **kw)
        for key in path:
            out = out[key]
        return out

    leaves = [(path, leaf, cuts(leaf.shape)) for path, leaf in
              ((_names(p), np.asarray(x))
               for p, x in jax.tree_util.tree_flatten_with_path(tree)[0])
              if _eligible(list(path), leaf)]
    threads = min(PACK_THREADS, len(os.sched_getaffinity(0)))
    with ThreadPoolExecutor(threads) as ex:
        futs = [[ex.submit(one, path, leaf[idx]) for idx in _jobs(c)]
                for path, leaf, c in leaves]
        new = {path: _join(c, (f.result() for f in fs), "/".join(path))
               for (path, _, c), fs in zip(leaves, futs)}
    return jax.tree_util.tree_map_with_path(
        lambda p, x: new.get(_names(p), x), tree)


def warm_up(eng, request_cls, traffic: Dict) -> int:
    """Admit every width 1..slots at every prefill bucket the mix can
    reach, each request for two tokens, so that every prefill, slot-write,
    sampling and step program exists before the window."""
    n = 0
    for plen in traffic_mod.warm_prompt_lengths(traffic):
        for w in range(1, traffic["slots"] + 1):
            reqs = [request_cls(rid=-1, prompt=np.ones(plen, np.int32),
                                max_new_tokens=2) for _ in range(w)]
            for r in reqs:
                eng.submit(r)
            eng.pump()
            while any(r.outcome is None for r in reqs):
                eng.step()
            n += 1
    return n


# ------------------------------------------------------------ end to end
def in_window(win, t):
    return win.t0 <= t <= win.t_end


def end_to_end(cell: Cell, win: loadgen.WindowResult,
               setup_s: float) -> Dict[str, float]:
    toks = [t for r in win.recs for t in r.times if in_window(win, t)]
    gaps = [b - a for r in win.recs for a, b in zip(r.times, r.times[1:])
            if in_window(win, a) and in_window(win, b)]
    values = {
        "output_tok_s": len(toks) / (win.t_end - win.t0),
        "itl_p99_ms": float(np.percentile(gaps, 99)) * 1e3 if gaps else None,
        "setup_s": setup_s,
    }
    return {m["name"]: values[m["name"]] for m in cell.end_to_end}


# ------------------------------------------------------------ correctness
def finished(win: loadgen.WindowResult) -> List[loadgen.Rec]:
    """The requests that completed inside the window."""
    return [r for r in win.recs if r.req.outcome == "completed"
            and r.times and r.times[-1] >= win.t0]


def sample(cell: Cell, win: loadgen.WindowResult, seed: int):
    """A seeded sample of the requests the window finished, the longest
    among them, as ``[check_requests, s_max]`` arrays: the prompt and
    served tokens fed back (``tokens``), each served token at the position
    whose logits chose it (``targets``) and where those lie
    (``valid``)."""
    tr = cell.traffic
    done = finished(win)
    b, t = int(tr["check_requests"]), int(tr["s_max"])
    pick: List = []
    if done:
        longest = max(done, key=lambda r: len(r.req.out_tokens))
        rest = [r for r in done if r is not longest]
        idx = traffic_mod.rng_for(seed, 1).permutation(len(rest))[:b - 1]
        pick = [longest] + [rest[i] for i in sorted(idx)]
    tokens = np.zeros((b, t), np.int32)
    targets = np.zeros((b, t), np.int32)
    valid = np.zeros((b, t), bool)
    for i, r in enumerate(pick):
        p, out = np.asarray(r.job.prompt), np.asarray(r.req.out_tokens)
        seq = np.concatenate([p, out[:-1]])
        tokens[i, :len(seq)] = seq
        targets[i, len(p) - 1:len(p) - 1 + len(out)] = out
        valid[i, len(p) - 1:len(p) - 1 + len(out)] = True
    log(f"check: {len(pick)} of {len(done)} finished requests, "
        f"{int(valid.sum())} served tokens")
    return tokens, targets, valid


def gaps(cell: Cell, w_host: Dict, tokens, targets, valid,
         round_to: Optional[str] = None) -> np.ndarray:
    """The gap by which each served token's logit (or, with ``round_to``,
    the control's first choice) lies below the reference's best at the
    same position, at every position ``valid`` marks."""
    import jax.numpy as jnp
    if not valid.any():
        return np.zeros(0, np.float32)
    ref = for_config(cell.config)
    p_ref = ref.prepare({k: jnp.asarray(v) for k, v in w_host.items()},
                        cell.config)
    out = np.asarray(ref.logit_gaps(p_ref, cell.config, tokens, targets,
                                    round_to=round_to))
    return out[valid]


def check(cell: Cell, win: loadgen.WindowResult,
          g: np.ndarray) -> Dict[str, Dict]:
    """Each number compared, with its limit: the mean of the sampled
    served tokens' logit gaps ``g`` (from ``gaps``), and the completed
    requests whose length is not the one asked for.  The mean, not the
    widest gap: the widest rests on one token and swings with the
    sample; the mean sets the float8 control further from sound runs."""
    lim = cell.config["limits"]
    return {
        "logit_gap_mean": {"value": float(g.mean()) if g.size else None,
                           "limit": float(lim["logit_gap_mean"])},
        "wrong_lengths": {
            "value": sum(len(r.req.out_tokens) != r.job.max_new
                         for r in win.recs
                         if r.req.outcome == "completed"),
            "limit": 0},
    }


# ------------------------------------------------------------ one run
@dataclasses.dataclass
class Served:
    """What set-up leaves for the window: the warmed engine and what the
    check needs afterwards."""
    cell: Cell
    eng: object
    request_cls: type
    w_host: Dict
    devs: list
    peaks: Optional[Dict[str, float]]
    counter: CompileCounter


def set_up(cell: Cell, seed: int, require_tpu: bool = True,
           cache: bool = True) -> Served:
    """Device check, weights from ``seed``, packing, engine and warm-up.
    ``cache=False`` leaves JAX's persistent compilation cache off."""
    import jax
    devs = devices(cell.chips, require_tpu)
    dev = devs[0]
    log(f"device {dev.platform} {dev.device_kind!r} x{len(devs)}; "
        f"compile cache {compile_cache() if cache else 'off'}")
    pk = peaks(dev.device_kind) if require_tpu else None
    counter = CompileCounter()
    jax.monitoring.register_event_listener(counter)
    jax.monitoring.register_event_duration_secs_listener(counter)

    from repro.models import build_model
    from repro.serve import Request, ServeEngine
    import weights
    cfg, tr = cell.config, cell.traffic
    api = build_model(for_config(cfg).program_config(cfg))
    t = time.perf_counter()
    w_dev = weights.make_weights(cfg, seed)
    w_host = {k: np.asarray(v) for k, v in w_dev.items()}
    del w_dev
    tree = weights.program_params(cfg, w_host, api)
    t_draw = time.perf_counter() - t
    log(f"weights drawn in {t_draw:.2f} s; host peak {host_peak_gb():.1f} GB")
    packed = pack(tree, cfg["format"])
    t_pack = time.perf_counter() - t - t_draw
    log(f"packed in {t_pack:.2f} s; host peak {host_peak_gb():.1f} GB")
    eng = ServeEngine(api, packed, slots=tr["slots"], s_max=tr["s_max"],
                      seed=seed % (1 << 31),
                      backend=cfg["format"]["serve_backend"])
    del packed, tree
    t = time.perf_counter()
    n_warm = warm_up(eng, Request, tr)
    log(f"set-up: weights {t_draw:.2f} s, packing {t_pack:.2f} s, "
        f"warm-up {time.perf_counter() - t:.2f} s over {n_warm} admissions;"
        f" host peak {host_peak_gb():.1f} GB")
    # what set-up left (traced programs, packing buffers) is not the
    # window's garbage: collect it now and keep the collector off it
    gc.collect()
    gc.freeze()
    return Served(cell, eng, Request, w_host, devs, pk, counter)


def drive(served: Served, jobs, seconds: float,
          on_open: Optional[Callable[[], None]] = None
          ) -> loadgen.WindowResult:
    """The cell's loop over ``jobs``: its pre-roll, ``on_open()``, and the
    window of ``seconds``, counting programs compiled or loaded in it."""
    tr = served.cell.traffic
    gen = loadgen.LoadGen(served.eng, served.request_cls)
    pauses: List[float] = []
    started = [0.0]

    def on_gc(phase, info):
        if phase == "start":
            started[0] = time.perf_counter()
        else:
            pauses.append(time.perf_counter() - started[0])

    def opened() -> None:
        if on_open is not None:
            on_open()
        served.counter.count = 0
        served.counter.on = True
        gc.callbacks.append(on_gc)

    try:
        win = gen.closed(jobs, tr["clients"], seconds,
                         int(tr["preroll_steps"]), opened)
    finally:
        if on_gc in gc.callbacks:
            gc.callbacks.remove(on_gc)
        served.counter.on = False
    gen.eng = None          # the requests' callbacks keep the generator
    log(f"window {win.t_end - win.t0:.3f} s: {win.steps} steps, "
        f"{win.admitted} admitted in {win.pumps} pumps, {len(win.recs)} "
        f"requests sent, longest pump+step "
        f"{win.longest_turn_s * 1e3:.1f} ms; programs compiled or loaded "
        f"in the window: {served.counter.count}; garbage collections "
        f"{len(pauses)}, longest {max(pauses, default=0) * 1e3:.1f} ms")
    return win


def gap_stats(g: np.ndarray) -> Dict[str, Optional[float]]:
    """Readings of one set of gaps: the widest, its 99th percentile, the
    mean, and the share of positions whose pick is not the best."""
    if not g.size:
        return {"max": None, "p99": None, "mean": None, "off_best": None}
    return {"max": float(g.max()), "p99": float(np.percentile(g, 99)),
            "mean": float(g.mean()), "off_best": float((g > 0).mean())}


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, require_tpu: bool = True,
             engine_hook: Optional[Callable] = None,
             control: Optional[str] = None, cache: bool = True) -> Dict:
    """One run; ``engine_hook(engine)`` runs after warm-up (the tests break
    the timed path with it).  ``control`` names a dtype: the control, the
    reference computed in it, is put in the program's place in the check,
    so ``correct`` must come out false; the readings of the program's and
    the control's gaps on the same sample are added under ``readings``
    (the benchmark's own runs leave it out).  ``cache=False`` leaves JAX's
    persistent compilation cache off (the tests)."""
    import jax
    served = set_up(cell, seed, require_tpu, cache)
    if engine_hook is not None:
        engine_hook(served.eng)
    tr, dev = cell.traffic, served.devs[0]
    seconds = min(seconds, TRACE_SECONDS) if trace else seconds
    jobs = traffic_mod.make_jobs(tr, cell.config["vocab_size"], seed)
    trace_dir = CACHE / "trace" / cell.name
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
    stamp: Dict[str, float] = {}

    def on_open() -> None:
        if trace:
            jax.profiler.start_trace(str(trace_dir))
        stamp["setup_s"] = time.perf_counter() - t_start

    win = drive(served, jobs, seconds, on_open)
    if trace:
        jax.profiler.stop_trace()
    stats = dev.memory_stats() or {}
    mem_peak = int(stats.get("peak_bytes_in_use", 0))
    w_host, pk, devs = served.w_host, served.peaks, served.devs
    del served
    gc.unfreeze()              # so that the engine's cycles are collected
    gc.collect()

    due = [r for r in win.recs if in_window(win, r.due)]
    failed = sum(1 for r in due if r.req.outcome == "rejected")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": mem_peak}
    out: Dict = {"correct": False, "attempted": len(due), "failed": failed}
    if trace:
        tr_ = tracing.Trace(tracing.read_dir(str(trace_dir)))
        ctx = Ctx(cell, pk, win, tr_)
        metrics = {}
        for m in cell.per_layer:
            v = metric_reader(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device.update(busy_s=tr_.busy_s(), window_s=tr_.window_s())
        out.update(metrics=metrics, device=device,
                   breakdown={"device_ops": tr_.top_ops(),
                              "idle_gaps": tr_.idle_gaps()})
    else:
        vals = end_to_end(cell, win, stamp["setup_s"])
        out.update(metrics={m["name"]: {"value": vals[m["name"]],
                                        "unit": m["unit"]}
                            for m in cell.end_to_end
                            if vals[m["name"]] is not None},
                   device=device)
    arrays = sample(cell, win, seed)
    t = time.perf_counter()
    g = gaps(cell, w_host, *arrays, round_to=control)
    log(f"reference check: {time.perf_counter() - t:.2f} s")
    checks = check(cell, win, g)
    log(f"gaps: {gap_stats(g)}")
    if control:
        out["readings"] = {"program": gap_stats(gaps(cell, w_host, *arrays)),
                           "control": gap_stats(g)}
    out["correct"] = all(c["value"] is not None and c["value"] <= c["limit"]
                         for c in checks.values())
    out["checks"] = checks
    for name, c in checks.items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    return out
