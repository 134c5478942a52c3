# smelint: exact-module
"""Unified SME execution-backend layer (DESIGN.md §3).

One registry behind which the three execution paths for an SME-compressed
linear layer live:

  * ``xla`` — dequantize the packed codes to a dense matrix inside the
    traced program and let XLA fuse the matmul (materializes the weight;
    correct everywhere, the CPU/dry-run default);
  * ``v1``  — the ``sme_spmm`` Pallas kernel: uint8 codewords + packed sign
    bitmap, CSC-of-tiles scalar-prefetch indexing, empty tiles skipped;
  * ``v2``  — the ``sme_spmm6`` Pallas kernel: minifloat-6 payload
    (0.75 B/weight), same CSC skipping;
  * ``v3``  — the ``sme_spmm_planes`` Pallas kernel: plane-CSC payload —
    1-bit bitmaps per occupied *(plane, tile)* pair, signs once per weight,
    spliced in a VMEM epilogue.  Bit-identical to v1/v2; smallest HBM
    payload whenever plane-level occupancy is sparse (pruned / reordered /
    narrow-band layers; the compiler prices this per layer).

Every backend exposes the same two operations:

  * ``pack_weight(smew)``   — offline: SMEWeight -> kernel-ready operand
    arrays (numpy).  Run once per weight; the vectorized hot path.
  * ``matmul2d(x2d, ops)``  — run time: [M, K] @ packed -> [M, N] f32.

Model code never calls a kernel directly: ``sme_apply(x, param)`` resolves
a backend (explicit name > ``use_backend`` context > ``SME_BACKEND`` env >
``auto``), finds or builds that backend's operands, and dispatches.
Operands emitted offline by ``integrate.convert_params_to_sme(backend=...)``
travel inside the param dict under ``sme_<name>_*`` keys; when absent and
the arrays are concrete, ``sme_apply`` packs once and memoizes per weight
(a weakref-validated identity cache), so eager callers also pay packing
exactly once.  Under tracing with no operands present, kernel backends
fall back to ``xla`` off-TPU — packing needs concrete codes — and raise
on a TPU, where the kernels must run (as must their compiled form:
interpret mode is refused there).

Static-shape discipline: the Pallas kernels take no value-dependent static
arguments.  ``n_bits`` (v1) and ``squeezed`` (v2) are folded into the
output scale as exact power-of-two factors, so the packed meta can stay
traced 0-d arrays inside jitted programs.
"""
from __future__ import annotations

import contextlib
import functools
import logging
import os
import weakref
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from .sme import SMEWeight, csc_tile_order

_LOG = logging.getLogger("repro.obs")

_TILESQ_KEY = "sme_tilesq"

__all__ = [
    "SMEBackend", "register_backend", "get_backend", "available_backends",
    "default_backend", "set_default_backend", "use_backend", "use_block",
    "use_spec_depth", "resolve_spec_depth",
    "resolve_backend", "resolve_block_m", "sme_apply",
    "smeweight_from_param", "pack_param_operands", "operand_keys",
    "ensure_operands", "clear_operand_cache",
]

_META_DEFAULTS = {"sme_nbits": 8, "sme_squeezed": 1, "sme_window": 3}


# --------------------------------------------------------------------- helpers
def _is_concrete(x) -> bool:
    return not isinstance(x, jax.core.Tracer)


def _resolve_interpret(interpret: Optional[bool]) -> bool:
    """Pallas interpret mode for one dispatch: off on a TPU, on anywhere
    else (the CPU test path) unless the caller pins it.  Interpret mode
    is refused on a TPU — it would run the kernels as slow host-emulated
    XLA programs and hide whether the compiled kernels work."""
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise ValueError("Pallas interpret mode is not allowed on a TPU: "
                         "the SME kernels must run compiled there")
    return bool(interpret)


def _meta_int(param: dict, key: str) -> int:
    """Concrete meta value from a packed param dict (offline paths only)."""
    v = param.get(key, _META_DEFAULTS[key])
    return int(np.asarray(v).reshape(-1)[0])


def smeweight_from_param(param: dict, index: Tuple[int, ...] = ()) -> SMEWeight:
    """Rebuild an :class:`SMEWeight` view of one 2-D slice of a packed param.

    ``index`` selects into the leading stacked dims (e.g. one expert of an
    [E, D, F] MoE weight).  Arrays must be concrete (offline packing path).
    """
    codes = np.asarray(param["sme_codes"])[index]
    row_exp = np.asarray(param["sme_rowexp"])[index]
    sign = np.asarray(param["sme_sign"])[index]
    scale = np.asarray(param["sme_scale"])[index]
    tile_sq = (np.asarray(param[_TILESQ_KEY])[index]
               if _TILESQ_KEY in param else None)
    k = sign.shape[-2]
    n = scale.shape[-1]
    return SMEWeight(
        shape=(k, n),
        n_bits=_meta_int(param, "sme_nbits"),
        window=_meta_int(param, "sme_window"),
        squeezed=_meta_int(param, "sme_squeezed"),
        tile=(codes.shape[-2], codes.shape[-1]),
        method="sme",
        tiled_codes=codes,
        row_exp=row_exp,
        sign_packed=sign,
        scale=scale.astype(np.float64),
        occupancy=codes.any(axis=(-1, -2)),
        tile_sq=tile_sq,
    )


def _param_lead(param: dict) -> Tuple[int, ...]:
    """Leading stacked dims of a packed param (codes base rank is 4)."""
    return tuple(param["sme_codes"].shape[:-4])


def _param_kn(param: dict) -> Tuple[int, int]:
    return param["sme_sign"].shape[-2], param["sme_scale"].shape[-1]


# ------------------------------------------------------------------- registry
class SMEBackend:
    """One execution strategy for an SME-packed linear layer."""

    name: str = ""
    #: operand array names; stored in param dicts as ``sme_<name>_<key>``
    OPERANDS: Tuple[str, ...] = ()

    # -- offline -----------------------------------------------------------
    def pack_weight(self, smew: SMEWeight,
                    pad_to: Optional[int] = None) -> Dict[str, np.ndarray]:
        """SMEWeight -> numpy operand arrays (keys = ``self.OPERANDS``)."""
        raise NotImplementedError

    def pad_hint(self, smew: SMEWeight) -> int:
        """CSC list length one slice needs — stacked slices take the max so
        operand arrays stack rectangularly.  Tile-CSC backends count
        occupied tiles per column; plane-CSC counts (plane, tile) pairs."""
        return max(int(smew.occupancy.sum(axis=0).max()), 1)

    def pack_block_key(self, bm: int):
        """Part of the operand-cache key that depends on the block-size
        choice.  The stock backends pack 128x128 weight tiles regardless
        of ``bm`` (only x/out padding changes), so they return ``None`` —
        one cache entry serves every bm.  A backend whose ``pack_weight``
        layout depends on the block size must return a value that changes
        with it, so a new bm repacks instead of serving stale operands."""
        return None

    def pack_depth_key(self, plane_depth):
        """Part of the operand-cache key that depends on the dispatch
        plane-depth (truncated drafts, DESIGN.md §11).  The stock backends
        truncate by slicing a *prefix* of the very same packed operands —
        no layout change — so they return ``None``: one cache entry serves
        every depth, and a draft dispatch can neither evict nor alias the
        full-precision entry because it deliberately IS the same entry.
        A backend that packs depth-specialized operands must return a
        value that changes with the depth, so each depth gets its own
        entry instead of serving another depth's layout."""
        return None

    # -- run time ----------------------------------------------------------
    def matmul2d(self, x2d: jax.Array, ops: Dict[str, jax.Array],
                 param: dict, *, bm: int = 128,
                 interpret: Optional[bool] = None,
                 plane_depth=None) -> jax.Array:
        """[M, K] @ packed -> [M, N] float32.

        ``plane_depth`` (``None`` = full precision) asks for the truncated
        top-k-planes draft product.  Only plane-CSC payloads can truncate;
        backends without per-plane operands accept and ignore it — their
        draft is the exact product, which is always a *correct* draft
        (acceptance 1.0), just not a cheaper one."""
        raise NotImplementedError

    # -- plumbing ----------------------------------------------------------
    def key(self, op: str) -> str:
        return f"sme_{self.name}_{op}"

    def has_operands(self, param: dict) -> bool:
        return all(self.key(op) in param for op in self.OPERANDS)

    def operands_from_param(self, param: dict) -> Dict[str, jax.Array]:
        return {op: param[self.key(op)] for op in self.OPERANDS}

    def supports(self, smew: SMEWeight) -> bool:
        return True


_REGISTRY: Dict[str, SMEBackend] = {}


def register_backend(backend_cls):
    """Class decorator: instantiate and add to the registry."""
    inst = backend_cls()
    if not inst.name:
        raise ValueError(f"{backend_cls.__name__} has no name")
    _REGISTRY[inst.name] = inst
    return backend_cls


def get_backend(name: str) -> SMEBackend:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown SME backend {name!r}; registered: {available_backends()}"
        ) from None


def available_backends() -> Tuple[str, ...]:
    return tuple(_REGISTRY)


# ------------------------------------------------------- default + resolution
_backend_stack = [os.environ.get("SME_BACKEND", "auto")]


def default_backend() -> str:
    return _backend_stack[-1]


def set_default_backend(name: str) -> None:
    if name != "auto":
        get_backend(name)                     # validate eagerly
    _backend_stack[0] = name


@contextlib.contextmanager
def use_backend(name: Optional[str]):
    """Scoped default: ``with use_backend("v1"): model.apply(...)``.

    ``None`` is a no-op (keeps the current default) so call sites can
    thread an optional choice without branching.
    """
    if name is None:
        yield
        return
    if name != "auto":
        get_backend(name)
    _backend_stack.append(name)
    try:
        yield
    finally:
        _backend_stack.pop()


# -------------------------------------------------------- block-size default
# scoped bm override (mirrors the use_backend stack); None = unset
_block_stack: list = [None]


@contextlib.contextmanager
def use_block(bm: Optional[int]):
    """Scoped M-block-size default for every ``sme_apply`` underneath:
    ``with use_block(256): engine.step(...)``.  ``None`` is a no-op so
    call sites can thread an optional knob without branching."""
    if bm is None:
        yield
        return
    _block_stack.append(int(bm))
    try:
        yield
    finally:
        _block_stack.pop()


# ------------------------------------------------------- spec-depth default
# scoped draft plane-depth override (self-speculative decode, DESIGN.md
# §11); None = full precision, "plan" = per-layer compiler depth
_spec_stack: list = [None]


@contextlib.contextmanager
def use_spec_depth(depth):
    """Scoped draft plane-depth for every ``sme_apply`` underneath — the
    self-speculative *draft* pass (DESIGN.md §11) runs its whole forward
    inside ``with use_spec_depth(...)``.  Accepts an int (uniform depth),
    the string ``"plan"`` (each layer uses its compiler-chosen
    ``sme_draft_planes`` meta, full precision where absent), or ``None``
    (no-op, so call sites thread an optional knob without branching)."""
    if depth is None:
        yield
        return
    _spec_stack.append(depth)
    try:
        yield
    finally:
        _spec_stack.pop()


def resolve_spec_depth(param: Optional[dict] = None, plane_depth=None):
    """Draft plane-depth for one dispatch: explicit arg > ``use_spec_depth``
    context > ``None`` (full precision).  ``"plan"`` resolves to the
    param's ``sme_draft_planes`` meta (written by the compiler per layer;
    absent or non-positive means the planner saw no profitable truncation
    for this layer, so it drafts at full precision).  Returns ``None``, a
    python int, or a (possibly traced / stacked) integer array."""
    depth = plane_depth if plane_depth is not None else _spec_stack[-1]
    if depth is None:
        return None
    if isinstance(depth, str):
        if depth != "plan":
            raise ValueError(
                f"plane_depth must be an int, 'plan', or None; got {depth!r}")
        if param is None or "sme_draft_planes" not in param:
            return None
        depth = param["sme_draft_planes"]
    if _is_concrete(depth):
        arr = np.asarray(depth)
        if arr.size == 0 or int(arr.max()) <= 0:
            return None
        if arr.ndim == 0:
            return int(arr)
    return depth


def resolve_block_m(backend_name: Optional[str] = None,
                    m: Optional[int] = None, k: Optional[int] = None,
                    n: Optional[int] = None) -> int:
    """Pick the M block size for one dispatch: ``use_block`` context >
    autotune-cache best (measured sweeps, when a cache is active and holds
    an entry for this backend x shape) > ``SME_BM`` env > 128.

    All inputs are static python ints (array *shapes*), so consulting the
    cache is trace-safe — the choice bakes into the jitted program just
    like the hardcoded 128 used to.
    """
    if _block_stack[-1] is not None:
        return _block_stack[-1]
    if backend_name and m and k and n:
        from repro.hardware.autotune import get_cache
        cache = get_cache()
        if cache is not None:
            best = cache.best(backend_name, m, k, n)
            if best is not None:
                return best[0]
    env = os.environ.get("SME_BM", "")
    if env.isdigit() and int(env) > 0:
        return int(env)
    return 128


def _v2_eligible(param: dict) -> bool:
    meta = [param.get(k, _META_DEFAULTS[k]) for k in
            ("sme_nbits", "sme_squeezed", "sme_window")]
    if not all(_is_concrete(m) for m in meta):
        return False
    nbits, squeezed, window = (int(np.asarray(m).reshape(-1)[0]) for m in meta)
    return SpmmV2Backend.supports_settings(nbits, window, squeezed)


def resolve_backend(param: Optional[dict] = None,
                    name: Optional[str] = None) -> SMEBackend:
    """Pick the backend for one call: explicit name > context default > auto.

    ``auto`` prefers operands already packed into the param (v2 over v1),
    then the Pallas kernels on TPU (v2 when the format is minifloat-6
    eligible), and the XLA dequant path everywhere else.
    """
    name = name or default_backend()
    if name != "auto":
        return get_backend(name)
    if param is not None:
        # v2 over v3 over v1: with several operand sets present, prefer the
        # guaranteed-smallest payload; a compiler plan that chose v3 for a
        # layer emits only v3 operands, so auto serves it through v3
        for cand in ("v2", "v3", "v1"):
            if cand in _REGISTRY and _REGISTRY[cand].has_operands(param):
                return _REGISTRY[cand]
    if jax.default_backend() == "tpu":
        if param is None or _v2_eligible(param):
            return _REGISTRY["v2"]
        return _REGISTRY["v1"]
    return _REGISTRY["xla"]


# ----------------------------------------------------------- packing + cache
def pack_param_operands(param: dict, backend: SMEBackend) -> Dict[str, jax.Array]:
    """Backend operands for a packed param (handles stacked lead dims).

    Stacked weights share one list length L (max over slices) so the
    operand arrays stack rectangularly.
    """
    lead = _param_lead(param)
    if not lead:
        ops = backend.pack_weight(smeweight_from_param(param))
        return {k: jnp.asarray(v) for k, v in ops.items()}
    idxs = list(np.ndindex(*lead))
    smews = [smeweight_from_param(param, i) for i in idxs]
    pad_to = max(backend.pad_hint(s) for s in smews)
    per = [backend.pack_weight(s, pad_to=pad_to) for s in smews]
    return {
        k: jnp.asarray(
            np.stack([p[k] for p in per]).reshape(lead + per[0][k].shape))
        for k in per[0]
    }


def operand_keys(backend_name: str) -> Tuple[str, ...]:
    be = get_backend(backend_name)
    return tuple(be.key(op) for op in be.OPERANDS)


def ensure_operands(params, backend_name: str, place=None):
    """Return ``params`` with ``backend_name``'s kernel operands present on
    every SME-packed weight, packing any that are missing (concrete arrays
    required).  Used when an artifact compiled without operands is served
    with an explicit kernel backend: packing here, once at boot, is the
    only alternative to ``sme_apply`` silently falling back to xla inside
    the jitted program (where raw codes are traced and cannot be packed).

    ``place(path, arr) -> arr`` is applied to every freshly packed operand
    array (``path`` is the '/'-joined leaf path) — mesh-native boots pass
    a placer that ``device_put``s each operand straight into its target
    shards (``parallel.sharding.leaf_sharding``) instead of leaving it on
    host for a later full-tree transfer.
    """
    be = get_backend(backend_name)
    if not be.OPERANDS:
        return params

    def walk(tree, path):
        if isinstance(tree, dict):
            if "sme_codes" in tree:
                if be.has_operands(tree):
                    return tree
                out = dict(tree)
                for op, arr in pack_param_operands(tree, be).items():
                    key = be.key(op)
                    if place is not None:
                        arr = place("/".join(path + [key]), arr)
                    out[key] = arr
                return out
            return {k: walk(v, path + [str(k)]) for k, v in tree.items()}
        if isinstance(tree, (list, tuple)):
            return type(tree)(walk(s, path + [str(i)])
                              for i, s in enumerate(tree))
        return tree

    return walk(params, [])


# ----------------------------------------------------------------- telemetry
# Dispatch hooks (DESIGN.md §9).  sme_apply runs at *trace time* inside
# jitted programs, so these counters record dispatch/packing *decisions*
# (one per traced call site, not per device execution) — which is exactly
# what goes wrong silently: the wrong backend resolved, the decode kernel
# falling back to the matmul grid, an operand repack storm.  All hooks are
# plain python counters gated on obs.enabled(): with telemetry off the
# cost is one branch, and either way nothing here can appear in the
# lowered HLO (tested in tests/test_obs.py).

def _obs_counter(name: str, help: str, labelnames: Tuple[str, ...]):
    return obs.get_registry().counter(name, help, labelnames)


def _obs_dispatch(backend_name: str, ops: Optional[Dict[str, jax.Array]],
                  param: dict) -> None:
    if not obs.enabled():
        return
    _obs_counter(
        "sme_dispatch_total",
        "sme_apply backend dispatch decisions (trace-time)",
        ("backend",)).labels(backend=backend_name).inc()
    arrs = ops if ops else {k: param[k] for k in
                            ("sme_codes", "sme_sign", "sme_scale",
                             "sme_rowexp") if k in param}
    nbytes = 0
    for v in arrs.values():
        shape = getattr(v, "shape", None)
        if shape is not None:
            nbytes += int(np.prod(shape)) * np.dtype(v.dtype).itemsize
    _obs_counter(
        "sme_modeled_bytes_total",
        "modeled HBM operand payload bytes per dispatch decision: the "
        "packed arrays one call streams (plane-occupancy-priced for v3)",
        ("backend",)).labels(backend=backend_name).inc(nbytes)


def _obs_cache_event(event: str) -> None:
    if not obs.enabled():
        return
    _obs_counter(
        "sme_operand_cache_total",
        "pack-once operand cache outcomes: prepacked = operands already "
        "in the param dict, hit/miss = cache lookup, repack = a "
        "block-size change forced a fresh pack of a known weight",
        ("event",)).labels(event=event).inc()


# (backend, id(weight)) -> [weakref, {block keys packed}, repack count]:
# the thrash detector behind the repack counter.  Validated/evicted by
# weakref exactly like _OPERAND_CACHE below.
_PACK_HISTORY: Dict[Tuple[str, int], list] = {}


def _obs_cache_miss(backend_name: str, anchor, block_key) -> None:
    """Classify a pack as miss (first sight) or repack (same weight,
    new block key) and warn once thrash sets in."""
    if not obs.enabled():
        return
    hkey = (backend_name, id(anchor))
    ent = _PACK_HISTORY.get(hkey)
    if ent is not None and ent[0]() is not anchor:
        ent = None                       # recycled id(): start fresh
    event = "miss"
    if ent is None:
        try:
            ref = weakref.ref(
                anchor, lambda _, k=hkey: _PACK_HISTORY.pop(k, None))
            _PACK_HISTORY[hkey] = [ref, {block_key}, 0]
        except TypeError:
            pass                         # non-weakrefable: count misses only
    elif block_key not in ent[1]:
        ent[1].add(block_key)
        ent[2] += 1
        event = "repack"
        if ent[2] >= 2:
            _LOG.warning(
                "operand pack thrash: %s repacked weight id=%d %d times "
                "(block keys seen: %s) — callers are alternating block "
                "sizes whose packed layout differs; pin bm to stop "
                "re-packing", backend_name, id(anchor), ent[2],
                sorted(map(str, ent[1])))
    _obs_cache_event(event)


def _draft_plane_entries(last, nnz, depth) -> Optional[int]:
    """Plane-list entries a depth-truncated draft actually streams: sum
    over tile groups of ``min(group size, depth)``.  ``None`` when any
    input is traced (nothing concrete to count)."""
    if not (_is_concrete(last) and _is_concrete(nnz) and _is_concrete(depth)):
        return None
    la = np.asarray(last)
    L = la.shape[-1]
    la2 = la.reshape(-1, L)
    d = max(int(np.asarray(depth).reshape(-1)[0]), 1)
    valid = np.arange(L)[None, :] < np.asarray(nnz).reshape(-1, 1)
    prev = np.concatenate([np.ones_like(la2[:, :1]), la2[:, :-1]], axis=1)
    starts = (prev == 1) & valid
    gidx = np.where(valid, np.cumsum(starts, axis=1) - 1, -1)
    rows = np.broadcast_to(np.arange(la2.shape[0])[:, None], gidx.shape)
    sizes = np.zeros((la2.shape[0], L), np.int64)
    np.add.at(sizes, (rows[valid], gidx[valid]), 1)
    return int(np.minimum(sizes, d).sum())


def _obs_draft_dispatch(ops: Dict[str, jax.Array], plane_depth) -> None:
    """Draft-dispatch decisions + modeled truncated HBM payload (the
    perf claim of DESIGN.md §11, observable per process)."""
    if not obs.enabled():
        return
    _obs_counter(
        "sme_draft_dispatch_total",
        "truncated-plane draft dispatch decisions (trace-time)",
        ("backend",)).labels(backend="v3").inc()
    kept = _draft_plane_entries(ops["last"], ops["nnz"], plane_depth)
    if kept is None:
        return
    planes = ops["planes"]
    per_entry = (int(np.prod(planes.shape[-2:]))
                 * np.dtype(planes.dtype).itemsize)
    side = sum(int(np.prod(v.shape)) * np.dtype(v.dtype).itemsize
               for op, v in ops.items() if op != "planes")
    _obs_counter(
        "sme_draft_modeled_bytes_total",
        "modeled HBM bytes one truncated draft dispatch streams: kept "
        "plane bitmaps (sum over tile groups of min(size, depth)) plus "
        "the full side/index operands",
        ("backend",)).labels(backend="v3").inc(kept * per_entry + side)


def _obs_decode_kernel(used_decode: bool) -> None:
    if not obs.enabled():
        return
    mode = os.environ.get("SME_DECODE_KERNEL", "auto").lower()
    _obs_counter(
        "sme_decode_kernel_total",
        "v3 shape-dispatch outcomes: path=decode is the GEMV tile-group "
        "kernel, path=matmul the (M,Nt,L) grid; mode echoes "
        "SME_DECODE_KERNEL at trace time",
        ("mode", "path")).labels(
            mode=mode, path="decode" if used_decode else "matmul").inc()


# weight identity -> packed operands; validated by weakref so a recycled
# id() can never alias a dead weight, and evicted by the weakref callback
# when the weight dies so operand arrays don't outlive their weight.  The
# key carries the backend's pack_block_key(bm) and pack_depth_key(depth)
# so a block-size or draft-depth choice that changes the packed layout
# invalidates instead of aliasing (the stock backends' depth key is None:
# truncation is an operand *prefix*, so every depth shares one entry).
_OPERAND_CACHE: Dict[tuple, Tuple[object, Dict[str, jax.Array]]] = {}


def clear_operand_cache() -> None:
    _OPERAND_CACHE.clear()


def _cached_operands(param: dict, backend: SMEBackend,
                     bm: int = 128, plane_depth=None) -> Dict[str, jax.Array]:
    anchor = param["sme_codes"]
    bkey = backend.pack_block_key(bm)
    dkey = backend.pack_depth_key(plane_depth)
    key = (backend.name, bkey, dkey, id(anchor))
    hit = _OPERAND_CACHE.get(key)
    if hit is not None and hit[0]() is anchor:
        _obs_cache_event("hit")
        return hit[1]
    _obs_cache_miss(backend.name, anchor, (bkey, dkey))
    ops = pack_param_operands(param, backend)
    try:
        ref = weakref.ref(anchor, lambda _, k=key: _OPERAND_CACHE.pop(k, None))
    except TypeError:
        return ops            # non-weakrefable leaf: don't risk pinning it
    _OPERAND_CACHE[key] = (ref, ops)
    return ops


# ------------------------------------------------------------------ backends
@register_backend
class XLABackend(SMEBackend):
    """Dequant-materialize: codes -> dense bf16/f32 in-graph, XLA matmul."""

    name = "xla"
    OPERANDS = ()

    def pack_weight(self, smew, pad_to=None):
        return {}                 # the raw packed param IS the operand set
    # no matmul2d: sme_apply short-circuits operand-free backends through
    # sme_dequant_jnp directly (handles stacked lead dims in one matmul)


def _kernel_call(kernel, x, operands, col_axes):
    """``kernel(x, *operands) -> y [M, Nt * bn]`` — one Pallas kernel
    call, made partitionable.  GSPMD cannot split a Mosaic kernel, so
    under a serving policy on a multi-device mesh the call runs inside a
    ``shard_map``: ``x`` replicated, each operand split along its
    column-tile axis (``col_axes[i]``; ``None`` = replicated) over
    'model' when the tile count divides, and ``y`` sharded by output
    column tiles — the layout ``parallel.sharding`` gives the operands.
    Every output column is still computed whole on one device, so the
    result equals the single-device call."""
    from jax.sharding import PartitionSpec as P
    from repro.parallel.policy import current_policy
    pol = current_policy()
    mesh = getattr(pol, "mesh", None)
    if mesh is None or mesh.size == 1:
        return kernel(x, *operands)
    msz = mesh.shape.get("model", 1)
    nt = next(op.shape[ax] for op, ax in zip(operands, col_axes)
              if ax is not None)
    split = msz > 1 and nt % msz == 0

    def spec(op, ax):
        if not split or ax is None:
            return P()
        return P(*([None] * ax + ["model"] + [None] * (op.ndim - ax - 1)))

    return jax.shard_map(
        kernel, mesh=mesh,
        in_specs=(P(),) + tuple(spec(op, ax)
                                for op, ax in zip(operands, col_axes)),
        out_specs=P(None, "model") if split else P(),
        check_vma=False)(x, *operands)


@functools.partial(jax.jit, static_argnames=("n", "bm", "interpret"))
def _v1_call(x2d, codes, sign, rowscale, rowid, nnz, scale, qscale,
             *, n, bm, interpret):
    from repro.kernels.sme_spmm.sme_spmm import sme_spmm
    m, k = x2d.shape
    _, _, bk, _ = codes.shape
    nr = -(-k // bk)
    mp = -(-m // bm) * bm
    xp = jnp.zeros((mp, nr * bk), x2d.dtype).at[:m, :k].set(x2d)
    # n_bits folded into qscale (= 2^-n_bits, exact), so the kernel needs
    # no value-dependent static argument and meta can stay traced
    y = _kernel_call(
        functools.partial(sme_spmm, n_bits=0, bm=bm, out_dtype=jnp.float32,
                          interpret=interpret),
        xp, (codes, sign, rowscale, rowid, nnz), (0, 0, 0, 0, 0))
    return y[:m, :n] * scale * qscale


@register_backend
class SpmmV1Backend(SMEBackend):
    """``sme_spmm`` kernel: uint8 codewords + sign bitmap, CSC tile skip."""

    name = "v1"
    OPERANDS = ("codes", "sign", "rowscale", "rowid", "nnz")

    def pack_weight(self, smew, pad_to=None):
        return smew.pack_csc(pad_to=pad_to)

    def matmul2d(self, x2d, ops, param, *, bm=128, interpret=None,
                 plane_depth=None):
        del plane_depth               # no per-plane payload: draft == exact
        interpret = _resolve_interpret(interpret)
        n = _param_kn(param)[1]
        scale = param["sme_scale"].reshape(1, -1).astype(jnp.float32)
        nbits = jnp.asarray(param.get("sme_nbits", 8), jnp.float32)
        return _v1_call(x2d, ops["codes"], ops["sign"], ops["rowscale"],
                        ops["rowid"], ops["nnz"], scale, jnp.exp2(-nbits),
                        n=n, bm=bm, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("n", "bm", "interpret"))
def _v2_call(x2d, packed, rowscale, rowid, nnz, scale, qscale,
             *, n, bm, interpret):
    from repro.kernels.sme_spmm.sme_spmm6 import sme_spmm6
    m, k = x2d.shape
    bk = packed.shape[-2] * 4 // 3
    nr = -(-k // bk)
    mp = -(-m // bm) * bm
    xp = jnp.zeros((mp, nr * bk), x2d.dtype).at[:m, :k].set(x2d)
    # squeezed folded into qscale (= 2^-squeezed, exact): see _v1_call
    y = _kernel_call(
        functools.partial(sme_spmm6, squeezed=0, bm=bm,
                          out_dtype=jnp.float32, interpret=interpret),
        xp, (packed, rowscale, rowid, nnz), (0, 0, 0, 0))
    return y[:m, :n] * scale * qscale


@register_backend
class SpmmV2Backend(SMEBackend):
    """``sme_spmm6`` kernel: minifloat-6 payload (0.75 B/weight), CSC skip."""

    name = "v2"
    OPERANDS = ("packed", "rowscale", "rowid", "nnz")

    @staticmethod
    def supports_settings(n_bits: int, window: int, squeeze: int) -> bool:
        """The one authoritative minifloat-6 format constraint — the
        compiler's planner and ``resolve_backend`` both consult it."""
        return squeeze >= 1 and window <= 3 and (n_bits - squeeze) <= 7

    def supports(self, smew):
        return self.supports_settings(smew.n_bits, smew.window, smew.squeezed)

    def pack_weight(self, smew, pad_to=None):
        from .minifloat import encode6, pack6
        if not self.supports(smew):
            raise ValueError(
                "backend v2 (minifloat-6) needs squeeze >= 1, window <= 3 "
                f"and live_bits <= 7; got squeeze={smew.squeezed}, "
                f"window={smew.window}, live_bits={smew.live_bits}")
        # one CSC gather pass; does NOT go through pack_csc, whose
        # codes/sign payloads v2 would immediately discard
        occ = smew.occupancy
        nc = smew.grid[1]
        tr, tc = smew.tile
        nnz = occ.sum(axis=0).astype(np.int32)
        L = int(pad_to if pad_to is not None else max(int(nnz.max()), 1))
        if int(nnz.max()) > L:
            raise ValueError(
                f"pad_to={L} < max nnz per column {int(nnz.max())}")
        packed = np.zeros((nc, L, 3 * tr // 4, tc), np.uint8)
        rowscale = np.ones((nc, L, tr), dtype=np.float32)
        rowid = np.zeros((nc, L), dtype=np.int32)
        col, row, slot = csc_tile_order(occ)
        if col.size:
            c6 = encode6(smew.tiled_codes[row, col],
                         smew.sign_tiled()[row, col],
                         smew.n_bits, smew.squeezed)
            packed[col, slot] = pack6(c6)
            rowscale[col, slot] = (2.0 ** smew.row_exp[row, col]
                                   ).astype(np.float32)
            rowid[col, slot] = row
        return {"packed": packed, "rowscale": rowscale,
                "rowid": rowid, "nnz": nnz}

    def matmul2d(self, x2d, ops, param, *, bm=128, interpret=None,
                 plane_depth=None):
        del plane_depth               # no per-plane payload: draft == exact
        interpret = _resolve_interpret(interpret)
        n = _param_kn(param)[1]
        scale = param["sme_scale"].reshape(1, -1).astype(jnp.float32)
        sq = jnp.asarray(param.get("sme_squeezed", 1), jnp.float32)
        return _v2_call(x2d, ops["packed"], ops["rowscale"], ops["rowid"],
                        ops["nnz"], scale, jnp.exp2(-sq),
                        n=n, bm=bm, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("n", "bm", "interpret"))
def _v3_call(x2d, planes, sign, rowscale, rowid, shift, last, nnz,
             scale, qscale, *, n, bm, interpret):
    from repro.kernels.sme_spmm.sme_spmm_planes import sme_spmm_planes
    m, k = x2d.shape
    bk = planes.shape[-2] * 8
    nr = -(-k // bk)
    mp = -(-m // bm) * bm
    xp = jnp.zeros((mp, nr * bk), x2d.dtype).at[:m, :k].set(x2d)
    # the spliced weight is the raw integer codeword (plane bit values
    # 2^shift); 2^-n_bits folds into qscale exactly as in _v1_call, so the
    # epilogue is bit-identical to v1's and meta can stay traced
    y = _kernel_call(
        functools.partial(sme_spmm_planes, bm=bm, out_dtype=jnp.float32,
                          interpret=interpret),
        xp, (planes, sign, rowscale, rowid, shift, last, nnz),
        (0, 1, 1, 0, 0, 0, 0))
    return y[:m, :n] * scale * qscale


def _use_decode_kernel(m: int, bm: int) -> bool:
    """Shape-dispatch rule for the v3 decode path (``SME_DECODE_KERNEL``):
    ``off``/``0`` never, ``on``/``1`` whenever the whole batch fits one M
    tile, ``auto`` (default) when M is at most half a tile — i.e. the
    matmul grid would waste most of its padded M rows.  Read at trace
    time, like backend resolution.

    Chunked serving (DESIGN.md §12) does not change this rule: the
    engine's chunk program is a scan whose every step is one
    ``decode_step`` over the full slot batch, so each dispatch still
    sees ``M == slots`` regardless of how many prompt/verify positions
    a step scores — mixed chunk sizes never push M past the decode
    threshold, and the ``sme_decode_kernel_total`` (mode, path) label
    set stays as-is."""
    mode = os.environ.get("SME_DECODE_KERNEL", "auto").lower()
    if mode in ("off", "0", "never"):
        return False
    if mode in ("on", "1", "always"):
        return m <= bm
    return 2 * m <= bm


def _static_group_bound(last, nnz) -> Optional[int]:
    """Tight static tile-group grid bound from concrete v3 operands (max
    groups over columns); ``None`` when traced — the kernel then uses its
    always-safe ``G = L`` bound and skips the padded steps at run time."""
    if not (_is_concrete(last) and _is_concrete(nnz)):
        return None
    la = np.asarray(last)
    valid = np.arange(la.shape[-1])[None, :] < np.asarray(nnz)[:, None]
    return max(int(((la == 1) & valid).sum(axis=-1).max()), 1)


def _v3_decode_impl(x2d, planes, sign, rowscale, rowid, shift, last, nnz,
                    scale, qscale, plane_depth, *, n, G, interpret):
    from repro.kernels.sme_spmm.sme_spmm_planes_decode import \
        sme_spmm_planes_decode
    m, k = x2d.shape
    nt, _, bk8, bn = planes.shape
    bk = bk8 * 8
    nr = -(-k // bk)
    mp = -(-max(m, 8) // 8) * 8
    xp = jnp.zeros((mp, nr * bk), x2d.dtype).at[:m, :k].set(x2d)
    # the fused epilogue needs scale * 2^-n_bits per padded output column;
    # qscale is an exact power of two, so folding it here is bitwise equal
    # to the matmul path's external (y * scale) * qscale
    colscale = jnp.zeros((nt * bn,), jnp.float32).at[:n].set(
        scale.reshape(-1).astype(jnp.float32) * qscale)
    ops = (planes, sign, rowscale, colscale.reshape(nt, 1, bn), rowid,
           shift, last, nnz)
    axes = (0, 1, 1, 0, 0, 0, 0, 0)
    if plane_depth is not None:
        # a traced depth rides as a replicated operand
        ops += (jnp.asarray(plane_depth, jnp.int32),)
        axes += (None,)

    def kernel(x, *ops):
        return sme_spmm_planes_decode(
            x, *ops[:8], G=G, plane_depth=ops[8] if len(ops) > 8 else None,
            out_dtype=jnp.float32, interpret=interpret)

    y = _kernel_call(kernel, xp, ops, axes)
    return y[:m, :n]


@functools.partial(jax.jit, static_argnames=("n", "G", "interpret"))
def _v3_decode_call(x2d, planes, sign, rowscale, rowid, shift, last, nnz,
                    scale, qscale, *, n, G, interpret):
    return _v3_decode_impl(x2d, planes, sign, rowscale, rowid, shift, last,
                           nnz, scale, qscale, None,
                           n=n, G=G, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("n", "G", "interpret"))
def _v3_decode_draft_call(x2d, planes, sign, rowscale, rowid, shift, last,
                          nnz, scale, qscale, plane_depth,
                          *, n, G, interpret):
    """Truncated-plane draft variant of :func:`_v3_decode_call`.  The
    depth rides as a *traced* i32 scalar operand, so the per-layer depths
    a compiler plan assigns share one compiled program per shape instead
    of fragmenting the jit cache."""
    return _v3_decode_impl(x2d, planes, sign, rowscale, rowid, shift, last,
                           nnz, scale, qscale, plane_depth,
                           n=n, G=G, interpret=interpret)


@register_backend
class SpmmV3Backend(SMEBackend):
    """``sme_spmm_planes`` kernel: per-(plane, tile) 1-bit bitmaps with a
    VMEM splice epilogue — the plane-CSC format (DESIGN.md §2)."""

    name = "v3"
    OPERANDS = ("planes", "sign", "rowscale", "rowid", "shift", "last",
                "nnz")

    def pad_hint(self, smew):
        return max(int(smew.plane_occupancy().sum(axis=(0, 1)).max()), 1)

    def pack_weight(self, smew, pad_to=None):
        return smew.pack_plane_csc(pad_to=pad_to)

    def matmul2d(self, x2d, ops, param, *, bm=128, interpret=None,
                 plane_depth=None):
        interpret = _resolve_interpret(interpret)
        n = _param_kn(param)[1]
        scale = param["sme_scale"].reshape(1, -1).astype(jnp.float32)
        nbits = jnp.asarray(param.get("sme_nbits", 8), jnp.float32)
        use_decode = _use_decode_kernel(x2d.shape[0], bm)
        if plane_depth is not None and not use_decode:
            # truncation lives in the tile-group decode kernel — the
            # matmul grid steps through mid-group list slots and cannot
            # skip them — so drafts force the decode path whenever the
            # batch fits one M tile (SME_DECODE_KERNEL=off still wins)
            use_decode = (x2d.shape[0] <= bm and
                          os.environ.get("SME_DECODE_KERNEL", "auto").lower()
                          not in ("off", "0", "never"))
        if not use_decode:
            # full-precision fallback is still a *correct* draft (exact
            # product, acceptance 1.0) — just not a shortcut
            plane_depth = None
        _obs_decode_kernel(use_decode)
        if use_decode:
            # GEMV-shaped batch: tile-group grid + double-buffered bitmap
            # DMA + fused epilogue (sme_spmm_planes_decode); bit-identical
            # to the matmul grid below
            if plane_depth is not None:
                _obs_draft_dispatch(ops, plane_depth)
                return _v3_decode_draft_call(
                    x2d, ops["planes"], ops["sign"], ops["rowscale"],
                    ops["rowid"], ops["shift"], ops["last"], ops["nnz"],
                    scale, jnp.exp2(-nbits),
                    jnp.asarray(plane_depth, jnp.int32), n=n,
                    G=_static_group_bound(ops["last"], ops["nnz"]),
                    interpret=interpret)
            return _v3_decode_call(
                x2d, ops["planes"], ops["sign"], ops["rowscale"],
                ops["rowid"], ops["shift"], ops["last"], ops["nnz"],
                scale, jnp.exp2(-nbits), n=n,
                G=_static_group_bound(ops["last"], ops["nnz"]),
                interpret=interpret)
        return _v3_call(x2d, ops["planes"], ops["sign"], ops["rowscale"],
                        ops["rowid"], ops["shift"], ops["last"], ops["nnz"],
                        scale, jnp.exp2(-nbits),
                        n=n, bm=bm, interpret=interpret)


# ------------------------------------------------------------------ dispatch
def _constrain_features(y: jax.Array) -> jax.Array:
    """Pin a dispatch result to the active ShardPolicy's output-feature
    layout (mesh-native serving, DESIGN.md §7): SME operand trees shard
    whole output-column tiles over 'model', so the spliced result is
    constrained to land sharded the same way instead of leaving GSPMD to
    pick a layout per call site.  A no-op outside a policy context."""
    from repro.parallel.policy import constrain, current_policy
    if current_policy() is None:
        return y
    return constrain(y, "features")


# smelint: trace-time
def sme_apply(x: jax.Array, param: dict, backend: Optional[str] = None,
              *, out_dtype=None, bm: Optional[int] = None,
              interpret: Optional[bool] = None,
              plane_depth=None) -> jax.Array:
    """y = x @ W_eff for an SME-packed param dict; x: [..., K] -> [..., N].

    The single entry point every model layer dispatches through.  Handles
    leading stacked weight dims (MoE experts): when the param has lead dims
    ``E``, ``x`` must be [*E, ..., K] and each slice runs its own kernel
    call (the grids differ only in the nnz prefetch values, so they share
    one compiled program).  Under an active ShardPolicy (mesh serving) the
    result is constrained to the policy's output-feature sharding.

    ``bm`` (the kernels' M block size) defaults through
    :func:`resolve_block_m`: explicit arg > ``use_block`` context >
    autotune-cache best for this (backend, shape) > ``SME_BM`` env > 128.

    ``plane_depth`` (default through :func:`resolve_spec_depth`: explicit
    arg > ``use_spec_depth`` context > ``None``) asks for the truncated
    top-k-planes *draft* product (DESIGN.md §11).  Only the plane-CSC v3
    backend can truncate; everywhere else the draft is served at full
    precision — exact, never wrong, just not a shortcut.
    """
    be = resolve_backend(param, backend)
    pd = resolve_spec_depth(param, plane_depth) if be.name == "v3" else None
    if out_dtype is None:
        out_dtype = x.dtype
    lead = _param_lead(param)
    k, n = _param_kn(param)
    if bm is None:
        m_rows = 1
        for d in x.shape[len(lead):-1]:
            m_rows *= int(d)
        bm = resolve_block_m(be.name, m_rows, k, n)
    ops: Optional[Dict[str, jax.Array]] = None
    if be.OPERANDS:
        if be.has_operands(param):
            _obs_cache_event("prepacked")
            ops = be.operands_from_param(param)
        elif _is_concrete(param["sme_codes"]):
            ops = _cached_operands(param, be, bm, pd)
        elif jax.default_backend() == "tpu":
            raise ValueError(
                f"SME backend {be.name!r} has no packed operands for a "
                "traced weight; on a TPU the kernels must run, so pack "
                "them first (convert_params_to_sme(..., backend=...) or "
                "ensure_operands) instead of falling back to xla")
        else:
            be = get_backend("xla")   # traced raw codes: cannot pack here
            pd = None
    _obs_dispatch(be.name, ops, param)

    if "sme_perm" in param and be.OPERANDS:
        # compiler-reordered weight: kernel operands hold W[perm, :], so
        # gather the input once to match — x[..., p] @ W[p, :] == x @ W
        # exactly (compiler.reorder; DESIGN.md §4).  The operand-free xla
        # path needs no gather: sme_dequant_jnp restores the row order
        # itself (checked after the traced-codes fallback above so a
        # downgraded call never compensates twice).
        x = jnp.take(x, param["sme_perm"], axis=-1)

    if not be.OPERANDS:               # xla: dequant handles lead dims itself
        from .integrate import sme_dequant_jnp
        w = sme_dequant_jnp(param, dtype=x.dtype)
        return _constrain_features(jnp.matmul(x, w).astype(out_dtype))

    if not lead:
        x2d = x.reshape(-1, x.shape[-1])
        y = be.matmul2d(x2d, ops, param, bm=bm, interpret=interpret,
                        plane_depth=pd)
        return _constrain_features(
            y.reshape(*x.shape[:-1], n).astype(out_dtype))

    nl = len(lead)
    if tuple(x.shape[:nl]) != lead:
        raise ValueError(
            f"stacked SME param lead dims {lead} do not match x "
            f"leading shape {x.shape[:nl]}")
    inner = x.shape[nl:-1]
    ys = []
    for idx in np.ndindex(*lead):
        ops_i = {key: v[idx] for key, v in ops.items()}
        # meta arrays stack with shape == lead (scan-compatibility); slice
        # them down to scalars alongside the payload
        meta_i = {mk: (param[mk][idx]
                       if getattr(param[mk], "ndim", 0) == len(lead)
                       else param[mk])
                  for mk in _META_DEFAULTS if mk in param}
        param_i = {"sme_scale": param["sme_scale"][idx],
                   "sme_sign": param["sme_sign"][idx], **meta_i}
        # a plan-resolved draft depth stacks with shape == lead, exactly
        # like the meta arrays: slice it down to this expert's scalar
        pd_i = (pd[idx] if getattr(pd, "ndim", 0) == len(lead) else pd)
        x2d = x[idx].reshape(-1, k)
        ys.append(be.matmul2d(x2d, ops_i, param_i, bm=bm,
                              interpret=interpret, plane_depth=pd_i))
    y = jnp.stack(ys).reshape(lead + inner + (n,))
    return _constrain_features(y.astype(out_dtype))
