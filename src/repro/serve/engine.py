"""Batched serving engine: mesh-native continuous batching over an open
request stream.

Real-system behaviors covered at small scale:

* fixed decode batch of ``slots`` sequences, each with its own cache region
  (caches are batched pytrees; a slot joins by writing its prefill cache in
  and leaves by being marked free — no reshapes/recompiles);
* **mesh-native end to end** (DESIGN.md §7): the engine always runs on a
  device mesh — single-device is the degenerate 1x1 mesh through the same
  code path.  Params (dense and SME-packed, every backend) are placed
  per-leaf with ``parallel.sharding.param_sharding(exact=True)``; slot
  caches stay device-resident under ``cache_sharding(exact=True)``;
  prefill/decode are jitted programs with explicit in/out shardings, so
  outputs are bit-identical across mesh shapes (only output-feature /
  head / batch dims ever shard — no float reduction crosses devices);
* prefill and decode are separate jitted programs (the standard
  prefill/decode split).  **Prefill is batched per admission window**: all
  requests admitted in one drain window share a single right-padded
  prefill call (per-row ``plen`` keeps it bit-identical per request);
  prompt lengths are bucketed to powers of two so admission windows reuse
  compiled programs;
* **open-stream continuous scheduling** (DESIGN.md §12): requests enter
  through :meth:`ServeEngine.submit` and a bounded queue; :meth:`pump`
  forms admission windows whenever slots free up, and prompts longer
  than ``chunk_len`` are *chunk-prefilled* — their first ``chunk_len``
  tokens go through the one-shot prefill program, the rest are scored
  ``chunk_len`` positions per engine step **inside the same jitted call
  that decodes the running rows**, so a long prompt never stalls decode;
* **every engine step is exactly one jitted call** however mixed the
  batch is: each row brings a per-step quota (1 for decode, up to
  ``chunk_len`` for prefill, ``spec_len + 1`` for speculative verify) and
  the ``decode_chunk`` scan masks rows past their quota as inactive —
  the §6 contract, so per-row results are independent of the padded scan
  length.  Sampling (per-row temperature, greedy iff 0) runs *inside*
  the program, so each step transfers ``[K, B]`` token ids to host, not
  logits; the program donates the cache argument (no per-step
  double-buffer);
* **prefix caching** (opt-in, ``SME_PREFIX_CACHE``): at every
  ``chunk_len`` prefill boundary the slot's cache row is snapshotted
  into a refcounted page pool (``serve/paged.py`` does the accounting;
  page size ``SME_PAGE_TOKENS``), and a later request sharing that exact
  token prefix restores the snapshot instead of recomputing it.  Reuse
  is gated by full token-id comparison, and because the chunk schedule
  over a shared prefix is deterministic, restored state is bit-identical
  to recomputation (DESIGN.md §12);
* per-request temperature sampling, per-request max_new_tokens and eos,
  per-token streaming callbacks (``Request.on_token`` / :meth:`poll`);
* **one clock for tracing** (DESIGN.md §9): the host phases are
  ``jax.profiler.TraceAnnotation`` spans (``serve.pump``, ``serve.admit``,
  ``serve.step`` tiled by ``serve.step.plan/dispatch/wait/emit``), so they
  land in the profiler's trace beside the device ops; the step program's
  ops carry ``attention`` / ``kv_cache`` / ``lm_head`` named scopes.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import itertools
import os
import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import obs

__all__ = ["Request", "ServeEngine", "PromptTooLong"]

# engine label values for the process-wide metrics registry: each engine
# instance gets its own label so per-engine series never mix (and the
# engine's derived stats dict reads back only its own counters)
_ENGINE_IDS = itertools.count()

#: 0..1 deciles for occupancy/fraction histograms
_FRACTION_BUCKETS = tuple(round(i / 10, 1) for i in range(1, 11))


class PromptTooLong(ValueError):
    """Prompt (plus frontend tokens) cannot fit the engine's cache ring."""


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray                  # [len] int32
    max_new_tokens: int = 16
    eos_id: Optional[int] = None
    temperature: float = 0.0
    #: per-request opt-out of self-speculative decode (DESIGN.md §11);
    #: only greedy (temperature == 0) rows ever speculate either way
    spec: bool = True
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    #: streaming hook: called as ``on_token(req, tok)`` on every emitted
    #: token (including the first), from the engine's host loop
    on_token: Optional[Callable] = None
    #: terminal outcome, set exactly when the matching
    #: ``serve_requests_total`` counter is incremented:
    #: "completed" | "evicted" | "rejected" | "unserved"
    outcome: Optional[str] = None


@dataclasses.dataclass
class _StepPlan:
    """One engine step's per-row work and the step program's device
    arguments after the cache (``pos, quota, gated, active, temps, key``);
    ``t_planned`` is when the plan was ready (the verify timer's start)."""
    act: np.ndarray
    quota: np.ndarray
    gated: np.ndarray
    prefilling: np.ndarray
    spec_rows: np.ndarray
    dtoks: Optional[np.ndarray]
    k: int
    tokens: jax.Array
    args: tuple
    t_planned: float


def _prompt_bucket(n: int, s_max: int) -> int:
    """Padded prefill length for a max prompt length ``n``: the next power
    of two (>= 8), clamped to the cache ring.  Bucketing keeps the number
    of compiled prefill programs logarithmic in prompt length; it does not
    affect results — every length-sensitive computation (caches, recurrent
    states, logits position, MoE capacity thresholds) keys off the per-row
    ``plen``, never the padded length (DESIGN.md §7)."""
    b = 1 << max(3, (max(n, 1) - 1).bit_length())
    return min(b, s_max)


class ServeEngine:
    def __init__(self, api, params, *, slots: int = 4, s_max: int = 128,
                 seed: int = 0, backend: Optional[str] = None, mesh=None,
                 bm: Optional[int] = None,
                 spec_len: int = 0, spec_depth=None,
                 chunk_len: Optional[int] = None,
                 page_tokens: Optional[int] = None,
                 prefix_cache: Optional[bool] = None,
                 prefix_pages: Optional[int] = None,
                 prefix_entries: int = 8):
        """``backend`` picks the SME execution backend ("xla" | "v1" | "v2"
        | "auto") for packed weights: every jitted prefill/decode call runs
        under ``core.backend.use_backend``, so serving goes through the
        Pallas block-sparse kernels on TPU (interpret-mode elsewhere)
        without touching model code.  None keeps the process default.

        ``bm`` overrides the kernels' M block size the same way (traced
        under ``core.backend.use_block``); None defers to the autotune
        cache / ``SME_BM`` env / 128 default (DESIGN.md §8).

        ``spec_depth`` enables self-speculative decode (DESIGN.md §11):
        an int runs the draft pass with that uniform truncated plane
        depth, ``"auto"``/``"plan"`` uses each layer's compiler-chosen
        ``sme_draft_planes`` depth, ``None`` (default) disables
        speculation entirely.  ``spec_len`` is the number of tokens
        drafted per round (defaults to 4 once a depth is set).  Accepted
        tokens are bit-identical to non-speculative greedy decode by
        construction — every emitted token comes from a full-precision
        decode step over fully verified context; the draft only decides
        how many verify steps a round runs.  Verify scores all
        ``spec_len + 1`` positions in ONE chunked call (DESIGN.md §12).

        ``chunk_len`` bounds how many prompt tokens a prefilling row
        scores per engine step (``SME_CHUNK_LEN`` env, default 32): a
        prompt longer than this one-shot budget keeps its slot and is
        chunk-prefilled inside the regular decode steps, interleaved
        with running decode rows.  ``page_tokens`` is the prefix-cache
        page size (``SME_PAGE_TOKENS``, default 16) and ``prefix_cache``
        (``SME_PREFIX_CACHE``, default off) enables snapshot/reuse of
        shared prompt prefixes at chunk boundaries, with
        ``prefix_pages`` pool pages (default ``4 * s_max/page_tokens``)
        and ``prefix_entries`` snapshot slots.

        ``mesh`` is a jax Mesh with ("data", "model") axes; None builds the
        degenerate 1x1 mesh — there is no unsharded code path.

        Telemetry (DESIGN.md §9) is host-side, recorded around the jitted
        programs: registry metrics, and profiler spans that cost one
        constructor call each while no profiler records.  Tokens are
        identical with either on or off (tested), and
        ``repro.obs.set_enabled(False)`` reduces the metric hooks to one
        branch."""
        from repro.parallel.policy import policy_for
        from repro.parallel.sharding import (cache_sharding, param_sharding,
                                             place_tree)
        self.api = api
        self.slots = slots
        self.s_max = s_max
        self.backend = backend
        self.bm = bm
        self.plan = None          # CompilePlan when booted from_artifact
        self.cfg = api.cfg
        self.key = jax.random.key(seed)
        if mesh is None:
            from repro.launch.mesh import make_mesh
            mesh = make_mesh((1, 1))
        self.mesh = mesh
        self.policy = dataclasses.replace(
            policy_for(self.mesh, self.cfg, "decode"), exact=True)
        self._rep = NamedSharding(self.mesh, P())

        # per-leaf placement straight into the exact-numerics shards:
        # host (numpy / mmap) leaves are sliced to their devices without an
        # intermediate replicated copy; committed leaves pass through
        self.param_sh = param_sharding(self.mesh, params, exact=True)
        self.params = place_tree(params, self.param_sh)

        # batched caches for all slots, resident under cache_sharding
        acache = api.abstract_cache(batch=slots, s_max=s_max)
        self.cache_sh = cache_sharding(self.mesh, acache, slots, exact=True)
        self.caches = jax.jit(
            lambda: api.init_cache(batch=slots, s_max=s_max),
            out_shardings=self.cache_sh)()
        # the batch dim of every cache leaf, found structurally (batch=1
        # vs batch=2 abstract shapes) — slot writes index it dynamically
        a1 = api.abstract_cache(batch=1, s_max=s_max)
        a2 = api.abstract_cache(batch=2, s_max=s_max)
        self._cache_bdim = jax.tree.map(
            lambda l1, l2: next(d for d in range(l1.ndim)
                                if l1.shape[d] != l2.shape[d]), a1, a2)

        self.pos = np.zeros(slots, dtype=np.int32)      # next position per slot
        self.active: List[Optional[Request]] = [None] * slots
        self.last_token = np.zeros((slots, 1), dtype=np.int32)

        # ragged (one padded call per admission window) prefill needs the
        # per-row plen contract; the enc-dec family prefills per request
        # (its cross-attention over padded frames is not length-masked)
        self._ragged_prefill = not self.cfg.n_enc_layers

        # -- continuous scheduler (DESIGN.md §12) -----------------------
        if chunk_len is None:
            chunk_len = int(os.environ.get("SME_CHUNK_LEN", "32"))
        if page_tokens is None:
            page_tokens = int(os.environ.get("SME_PAGE_TOKENS", "16"))
        if prefix_cache is None:
            prefix_cache = os.environ.get(
                "SME_PREFIX_CACHE", "0").lower() in ("1", "on", "true",
                                                     "yes")
        chunk_len, page_tokens = int(chunk_len), int(page_tokens)
        if chunk_len < 1:
            raise ValueError(f"chunk_len must be >= 1, got {chunk_len}")
        if page_tokens < 1:
            raise ValueError(f"page_tokens must be >= 1, got {page_tokens}")
        self.chunk_len = chunk_len
        self.page_tokens = page_tokens
        # chunked prefill re-scores the prompt tail through the decode
        # contract, so it needs the ragged decoder-only family without a
        # frontend (frontend tokens only exist in the one-shot program)
        self._chunk_prefill = self._ragged_prefill and not self.cfg.frontend
        #: per-admission one-shot prefill budget; whole prompt otherwise
        self._c = min(chunk_len, s_max) if self._chunk_prefill else s_max
        #: per-slot count of prompt tokens already scored (a slot is
        #: *prefilling* while this is < len(prompt): no output yet)
        self._pf_next = np.zeros(slots, np.int32)
        self._queue: collections.deque = collections.deque()
        #: bounded stream of {"kind": "token"|"finish"|...} events for
        #: :meth:`poll` consumers (newest win once full)
        self.events: collections.deque = collections.deque(maxlen=4096)
        self._max_pages = max(s_max // page_tokens, 1)
        self._prefix = None

        # prefill outputs replicate: the window cache is transient (one
        # slot write later it is gone) and the logits feed host sampling;
        # pinning them replicated keeps the slot-write program's input
        # contract independent of GSPMD layout choices
        if self._ragged_prefill:
            def prefill_fn(p, batch, plen):
                return api.prefill(p, batch, s_max=s_max, plen=plen)
            self._prefill = jax.jit(
                prefill_fn, in_shardings=(self.param_sh, self._rep,
                                          self._rep),
                out_shardings=(self._rep, self._rep))
        else:
            def prefill_fn(p, batch):
                return api.prefill(p, batch, s_max=s_max)
            self._prefill = jax.jit(
                prefill_fn, in_shardings=(self.param_sh, self._rep),
                out_shardings=(self._rep, self._rep))

        # one jitted scoring program for every step shape: each row
        # consumes its first nvalid[i] of the K fed tokens as consecutive
        # decode steps (K = 1 is the plain ragged decode).  Sampling per
        # scan step runs in-graph; gated rows stop at the first greedy
        # mismatch (speculative verify).  Retraces once per distinct K.
        def chunk_fn(p, tokens, caches, pos, nvalid, gated, active, temps,
                     key):
            logits, live, newc = api.decode_chunk(
                p, tokens, caches, pos, nvalid, active, gated)

            def samp(l, kk):
                greedy = jnp.argmax(l, axis=-1).astype(jnp.int32)
                drawn = jax.random.categorical(
                    kk, l.astype(jnp.float32)
                    / jnp.maximum(temps, 1e-6)[:, None], axis=-1)
                return jnp.where(temps > 0, drawn.astype(jnp.int32), greedy)

            with jax.named_scope("lm_head"):
                keys = jax.random.split(key, tokens.shape[1])
                emitted = jax.vmap(samp)(logits, keys)
            return emitted, live, newc

        self._chunk = jax.jit(
            chunk_fn,
            in_shardings=(self.param_sh, self._rep, self.cache_sh,
                          self._rep, self._rep, self._rep, self._rep,
                          self._rep, self._rep),
            out_shardings=(self._rep, self._rep, self.cache_sh),
            donate_argnums=(2,))

        # -- self-speculative decode (DESIGN.md §11) --------------------
        if spec_depth == "auto":
            spec_depth = "plan"
        if spec_depth is not None and not isinstance(spec_depth, str):
            spec_depth = int(spec_depth)
            if spec_depth < 1:
                raise ValueError(f"spec_depth must be >= 1, got {spec_depth}")
        self.spec_depth = spec_depth
        self.spec_len = int(spec_len)
        if spec_depth is not None and self.spec_len <= 0:
            self.spec_len = 4
        d = self.spec_len

        def draft_fn(p, token, caches, pos, active):
            # d greedy truncated-precision steps on a throwaway cache
            # view: the cache argument is NOT donated, so the engine
            # cache is untouched and draft KV writes die with the scan
            def one(carry, _):
                tok, c, ps = carry
                logits, c = api.decode_step(p, tok, c, ps, active)
                l = logits if logits.ndim == 2 else logits[:, -1]
                nxt = jnp.argmax(l, axis=-1).astype(jnp.int32)[:, None]
                return (nxt, c, ps + 1), nxt[:, 0]
            _, toks = jax.lax.scan(one, (token, caches, pos), None, length=d)
            return toks                                        # [d, B]

        self._draft = jax.jit(
            draft_fn,
            in_shardings=(self.param_sh, self._rep, self.cache_sh,
                          self._rep, self._rep),
            out_shardings=self._rep)

        def write_fn(full, pre, row, slot):
            def one(f, p, bd):
                src = jax.lax.dynamic_slice_in_dim(p, row, 1, axis=bd)
                return jax.lax.dynamic_update_slice_in_dim(
                    f, src.astype(f.dtype), slot, axis=bd)
            return jax.tree.map(one, full, pre, self._cache_bdim)

        # row/slot are traced scalars: one compile per prefill shape, not
        # per slot; donating the engine cache avoids an admission-time copy
        self._write = jax.jit(
            write_fn, in_shardings=(self.cache_sh, self._rep, self._rep,
                                    self._rep),
            out_shardings=self.cache_sh, donate_argnums=(0,))

        # -- telemetry (DESIGN.md §9) -----------------------------------
        # Lifetime counters live in the process-wide registry under this
        # engine's label and double as the engine's stats (the `_stats`
        # property and run()'s returned dict derive from them — one
        # source of truth), so they count unconditionally.  Latency
        # histograms are instrumentation only and check obs.enabled() at
        # every hook; the host spans are profiler annotations.
        self._eid = str(next(_ENGINE_IDS))
        R = obs.get_registry()
        eid = dict(engine=self._eid)
        self._m_requests = R.counter(
            "serve_requests_total",
            "terminal request outcomes per engine",
            ("engine", "outcome"))
        self._m = {
            "prefills": R.counter(
                "serve_prefills_total", "batched prefill calls",
                ("engine",)).labels(**eid),
            "prefill_reqs": R.counter(
                "serve_prefill_requests_total",
                "requests admitted through batched prefill",
                ("engine",)).labels(**eid),
            "decode_steps": R.counter(
                "serve_decode_steps_total",
                "jitted decode steps (one per engine step)",
                ("engine",)).labels(**eid),
            "tokens": R.counter(
                "serve_tokens_total", "decode tokens emitted",
                ("engine",)).labels(**eid),
            "ttft": R.histogram(
                "serve_ttft_seconds",
                "enqueue to first token (the prefill-sampled one)",
                ("engine",)).labels(**eid),
            "itl": R.histogram(
                "serve_inter_token_seconds",
                "per-request gap between consecutive decode tokens",
                ("engine",)).labels(**eid),
            "qwait": R.histogram(
                "serve_queue_wait_seconds",
                "enqueue to the start of the admitting prefill",
                ("engine",)).labels(**eid),
            "occupancy": R.histogram(
                "serve_batch_occupancy",
                "active slots / total slots, observed per decode step",
                ("engine",), buckets=_FRACTION_BUCKETS).labels(**eid),
            "padded": R.histogram(
                "serve_padded_slot_fraction",
                "free (padded) slots / total slots per decode step",
                ("engine",), buckets=_FRACTION_BUCKETS).labels(**eid),
            "pad_frac": R.histogram(
                "serve_prefill_pad_fraction",
                "padding fraction of each batched prefill call",
                ("engine",), buckets=_FRACTION_BUCKETS).labels(**eid),
            # -- continuous scheduler (DESIGN.md §12) -------------------
            "preemptions": R.counter(
                "serve_preemptions_total",
                "prefilling rows bumped back to the queue",
                ("engine",)).labels(**eid),
            "prefix_hits": R.counter(
                "serve_prefix_hits_total",
                "admissions served from a prefix-cache snapshot",
                ("engine",)).labels(**eid),
            "prefix_misses": R.counter(
                "serve_prefix_misses_total",
                "admissions with no reusable prefix snapshot",
                ("engine",)).labels(**eid),
            "prefix_snapshots": R.counter(
                "serve_prefix_snapshots_total",
                "prefix snapshots taken at chunk boundaries",
                ("engine",)).labels(**eid),
            "prefix_evictions": R.counter(
                "serve_prefix_evictions_total",
                "prefix entries evicted (LRU) to free pages or slots",
                ("engine",)).labels(**eid),
            # -- self-speculative decode (DESIGN.md §11) ----------------
            "spec_rounds": R.counter(
                "serve_spec_rounds_total",
                "speculative draft/verify rounds",
                ("engine",)).labels(**eid),
            "spec_draft_tokens": R.counter(
                "serve_spec_draft_tokens_total",
                "tokens proposed by truncated-plane draft passes",
                ("engine",)).labels(**eid),
            "spec_accepted": R.counter(
                "serve_spec_accepted_total",
                "draft tokens confirmed by full-precision verify",
                ("engine",)).labels(**eid),
            "spec_rolled_back": R.counter(
                "serve_spec_rolled_back_total",
                "draft tokens discarded after verify — host bookkeeping "
                "only: unverified tokens never reach the KV cache, so "
                "there is no device state to rewind",
                ("engine",)).labels(**eid),
            "spec_verify_steps": R.counter(
                "serve_spec_verify_steps_total",
                "full-precision verify positions scored inside spec "
                "rounds (scan steps with a live gated row)",
                ("engine",)).labels(**eid),
            "spec_accept_frac": R.histogram(
                "serve_spec_acceptance",
                "accepted / drafted fraction per spec row-round",
                ("engine",), buckets=_FRACTION_BUCKETS).labels(**eid),
            "spec_verify_s": R.histogram(
                "serve_spec_verify_seconds",
                "wall-clock of the one-call batched verify (the chunked "
                "scoring call of a step with spec rows)",
                ("engine",)).labels(**eid),
        }
        self._g_queue = R.gauge(
            "serve_queue_depth", "requests waiting for admission",
            ("engine",)).labels(**eid)
        self._g_pages = R.gauge(
            "serve_slot_pages_in_use",
            "page-granular cache working set across active slots",
            ("engine",)).labels(**eid)
        self._g_pool = R.gauge(
            "serve_prefix_pool_pages_in_use",
            "prefix-cache pool pages currently referenced",
            ("engine",)).labels(**eid)
        self._g_entries = R.gauge(
            "serve_prefix_entries", "live prefix-cache snapshots",
            ("engine",)).labels(**eid)
        self._t_enq: Dict[int, float] = {}     # id(req) -> enqueue ts
        self._last_tok_t = np.zeros(slots)     # last token ts per slot

        if prefix_cache and self._chunk_prefill:
            if self._c % page_tokens:
                raise ValueError(
                    f"prefix caching needs the chunk boundary ({self._c}) "
                    f"to be a multiple of page_tokens ({page_tokens}) so "
                    f"snapshots are page-aligned")
            self._init_prefix(prefix_pages, int(prefix_entries))

    @classmethod
    def from_artifact(cls, api, path, *, verify: bool = False, mesh=None,
                      **kw):
        """Boot from a compiled ``.smez`` artifact (DESIGN.md §4).

        The artifact already holds the packed codes and kernel-ready CSC
        operands, so there is no per-boot quantize/pack work.  On a mesh,
        every leaf is ``device_put`` **at load time** straight into its
        target shards (``parallel.sharding.leaf_sharding`` from the
        manifest key) — the memory-mapped payload is sliced per device and
        a full host-replicated param copy never exists.  ``backend``
        defaults to the artifact's recorded serve backend (manifest
        ``extra.serve_backend``) when present.  If a kernel backend is
        requested but the artifact was compiled without its operands, they
        are packed once here at boot — inside the jitted programs the
        codes are traced and ``sme_apply`` would silently fall back to xla
        instead.
        """
        from repro.compiler.artifact import load_artifact
        from repro.core.backend import ensure_operands
        place = None
        if mesh is not None:
            from repro.parallel.sharding import leaf_sharding

            def place(path_key, arr):
                return jax.device_put(
                    arr, leaf_sharding(mesh, path_key, arr.shape))
        params, plan, manifest = load_artifact(path, verify=verify,
                                               place=place)
        kw.setdefault("backend",
                      manifest.get("extra", {}).get("serve_backend"))
        if kw.get("backend") in ("v1", "v2", "v3"):
            params = ensure_operands(params, kw["backend"], place=place)
        if plan is not None and "bm" not in kw:
            # a plan built against an autotune cache records each layer's
            # measured-best block size; when they agree, serve with it
            bms = {lp.bm for lp in plan.layers.values()
                   if getattr(lp, "bm", 0)}
            if len(bms) == 1:
                kw["bm"] = bms.pop()
        eng = cls(api, params, mesh=mesh, **kw)
        eng.plan = plan
        return eng

    def scope(self):
        """Trace-time context for the jitted programs: the SME backend
        choice, the block-size override, the engine's ShardPolicy
        (activation constraints + the sme_apply output-feature constraint)
        and the mesh (so PartitionSpec-based constraints resolve).  A
        caller that jits its own function over ``self.params`` traces it
        under this context to get the engine's numerics and layout."""
        from repro.core.backend import use_backend, use_block
        from repro.parallel.policy import use_policy
        stack = contextlib.ExitStack()
        stack.enter_context(use_backend(self.backend))
        stack.enter_context(use_block(self.bm))
        stack.enter_context(use_policy(self.policy))
        stack.enter_context(self.mesh)
        return stack

    def lower_programs(self, prefill_batch: int, prefill_len: int,
                       k: int = 1) -> Dict[str, "jax.stages.Lowered"]:
        """The engine's ``prefill`` program (``prefill_batch`` prompts
        padded to ``prefill_len``) and its step program (``k`` scored
        positions per row; ``k == 1`` is plain decode), lowered under the
        engine's scope at exactly the shardings a served call uses.
        ``.compile().as_text()`` then shows what the device runs, e.g.
        whether the SME kernels are in it (``tpu_custom_call``)."""
        if not self._ragged_prefill:
            raise NotImplementedError(
                "lower_programs covers the ragged decoder-only prefill")
        sds, i32 = jax.ShapeDtypeStruct, jnp.int32
        b = self.slots
        with self.scope():
            prefill = self._prefill.lower(
                self.params, {"tokens": sds((prefill_batch, prefill_len), i32)},
                sds((prefill_batch,), i32))
            step = self._chunk.lower(
                self.params, sds((b, k), i32), self.caches, sds((b,), i32),
                sds((b,), i32), sds((b,), jnp.bool_), sds((b,), jnp.bool_),
                sds((b,), jnp.float32), self.key)
        return {"prefill": prefill, "step": step}

    # ------------------------------------------------------------ telemetry
    @property
    def _stats(self) -> Dict[str, int]:
        """Engine-lifetime stats, derived from the metrics registry (the
        counters ARE the stats; kept as a dict for backward compat)."""
        return {k: int(self._m[k].value)
                for k in ("prefills", "prefill_reqs", "decode_steps",
                          "tokens")}

    def _outcome(self, req: Request, outcome: str) -> None:
        """Terminal outcome: stamped on the request AND counted in the
        registry in the same breath, so per-run splits stay derivable
        under continuous admission (requests from other submitters can
        reach their outcomes between one ``run()``'s steps)."""
        req.outcome = outcome
        self._m_requests.labels(engine=self._eid, outcome=outcome).inc()

    def _outcome_count(self, outcome: str) -> int:
        return int(self._m_requests.labels(engine=self._eid,
                                           outcome=outcome).value)

    def _mark_enqueue(self, req: Request) -> None:
        # stamped unconditionally: the queue-wait histogram and the
        # ``serve.admit`` span's argument both read it
        self._t_enq.setdefault(id(req), time.perf_counter())

    def _reject(self, req: Request) -> None:
        self._outcome(req, "rejected")
        self.events.append({"kind": "reject", "rid": req.rid})
        self._t_enq.pop(id(req), None)

    def _emit(self, req: Request, slot: int, tok: int, t_tok: float,
              first: bool = False) -> None:
        """One emitted token from the step loop: output list, counters
        (the request's *first* token observes ttft instead of the
        tokens/itl pair, keeping ``itl.count == tokens`` — §9), streaming
        callback and event."""
        req.out_tokens.append(tok)
        if not first:
            self._m["tokens"].inc()
        if req.on_token is not None:
            req.on_token(req, tok)
        self.events.append({"kind": "token", "rid": req.rid, "token": tok})
        if obs.enabled():
            if first:
                tq = self._t_enq.get(id(req))
                if tq is not None:
                    self._m["ttft"].observe(t_tok - tq)
            else:
                self._m["itl"].observe(t_tok - self._last_tok_t[slot])
            self._last_tok_t[slot] = t_tok

    def _finish(self, req: Request, slot: int) -> None:
        req.done = True
        self._outcome(req, "completed")
        self.events.append({"kind": "finish", "rid": req.rid,
                            "outcome": "completed"})
        self._t_enq.pop(id(req), None)
        self.active[slot] = None
        # park the freed row at 0 so inactive rows are in-bounds by
        # construction, not by JAX's OOB scatter-drop semantics
        self.pos[slot] = 0
        self._pf_next[slot] = 0

    # ---------------------------------------------------------------- slots
    def _free_slot(self) -> Optional[int]:
        for i, r in enumerate(self.active):
            if r is None:
                return i
        return None

    def _free_slots(self) -> List[int]:
        return [i for i, r in enumerate(self.active) if r is None]

    def _prefilling(self, i: int) -> bool:
        """True while slot ``i``'s request still has unscored prompt
        tokens (it holds a slot but has emitted nothing)."""
        r = self.active[i]
        return r is not None and int(self._pf_next[i]) < len(r.prompt)

    def _prefill_len(self, req: Request) -> int:
        """Validated prefill length (prompt + frontend tokens); raises
        PromptTooLong when the first decoded token could not fit the
        cache ring."""
        plen = len(req.prompt) + (self.cfg.n_frontend_tokens
                                  if self.cfg.frontend else 0)
        if plen >= self.s_max:
            front = (f" + {self.cfg.n_frontend_tokens} frontend tokens"
                     if self.cfg.frontend else "")
            raise PromptTooLong(
                f"request {req.rid}: prefill length {plen} "
                f"({len(req.prompt)} prompt tokens{front}) must be "
                f"< s_max={self.s_max} — the first decoded token would "
                f"overflow the cache ring; raise s_max or shorten the prompt")
        return plen

    def add_request(self, req: Request) -> bool:
        """Prefill ``req`` into a free slot. Returns False when no slot is
        free; raises PromptTooLong when the prompt cannot fit the cache
        ring. A request whose prefill-sampled token already satisfies
        eos/max_new_tokens completes immediately without taking a slot."""
        self._mark_enqueue(req)
        try:
            self._prefill_len(req)
        except PromptTooLong:
            self._reject(req)
            raise
        if self._free_slot() is None:
            return False
        self._admit([req])
        return True

    # ---------------------------------------------------- streaming API
    def submit(self, req: Request) -> Request:
        """Enqueue on the open stream — no admission here; :meth:`pump`
        forms admission windows as slots free up.  Attach
        ``req.on_token`` or drain :meth:`poll` for streaming output."""
        self._mark_enqueue(req)
        self._queue.append(req)
        self._g_queue.set(len(self._queue))
        return req

    def pump(self) -> int:
        """Admit every fittable queued request the free slots allow — one
        batched prefill (or prefix restore) per drain window.  Unfittable
        prompts at the queue head are rejected, the rest keep flowing.
        Returns the number of requests admitted."""
        with TraceAnnotation("serve.pump"):
            admitted = 0
            while self._queue:
                free = len(self._free_slots())
                cap = free if self._ragged_prefill else min(1, free)
                window = []
                while self._queue and len(window) < cap:
                    req = self._queue[0]
                    try:
                        self._prefill_len(req)
                    except PromptTooLong:
                        self._queue.popleft()
                        self._reject(req)
                        continue
                    window.append(self._queue.popleft())
                if not window:
                    break
                self._admit(window)
                admitted += len(window)
            self._g_queue.set(len(self._queue))
            return admitted

    def poll(self) -> List[Dict]:
        """Drain and return the pending stream events (token / finish /
        reject / preempt dicts, oldest first)."""
        out = list(self.events)
        self.events.clear()
        return out

    def preempt(self, slot: int) -> bool:
        """Bump a still-prefilling row back to the queue head, freeing its
        slot.  Only rows with no emitted tokens are preemptible — their
        re-prefill is deterministic, so the request's eventual output is
        unchanged (bit-identity survives preemption).  Returns False for
        free, decoding, or already-emitting slots."""
        req = self.active[slot]
        if req is None or not self._prefilling(slot) or req.out_tokens:
            return False
        self.active[slot] = None
        self.pos[slot] = 0
        self._pf_next[slot] = 0
        self._queue.appendleft(req)
        self._m["preemptions"].inc()
        self._g_queue.set(len(self._queue))
        self.events.append({"kind": "preempt", "rid": req.rid})
        return True

    # ------------------------------------------------------------ admission
    def _admit(self, reqs: List[Request]) -> None:
        """One admission window under a ``serve.admit`` span, whose
        arguments (computed only while the profiler records) are the
        window's request count, its padded prompt length and its longest
        queue wait."""
        with TraceAnnotation("serve.admit") as span:
            if not span.is_enabled():
                self._admit_window(reqs)
                return
            t0 = time.perf_counter()
            wait = max((t0 - self._t_enq[id(r)] for r in reqs
                        if id(r) in self._t_enq), default=0.0)
            pad_to = self._admit_window(reqs)
            span.set_metadata(n_reqs=len(reqs), pad_to=pad_to,
                              qwait_ms_max=round(wait * 1e3, 3))

    def _admit_window(self, reqs: List[Request]) -> int:
        """One admission window: prefix-cache hits restore their snapshot
        into a free slot; the rest share a single padded prefill call
        over each prompt's one-shot budget (``min(len, chunk_len)``).

        Prompts are right-padded to a shared bucketed length; the per-row
        ``plen`` vector keeps each row bit-identical to an unpadded
        prefill of that request alone (DESIGN.md §7).  Fully-fed requests
        sample their first token here (and may complete without taking a
        slot); longer prompts keep their slot in the *prefilling* state
        and are chunk-scored by :meth:`step`.  Callers must have
        validated lengths (``_prefill_len``) and free-slot counts.
        Returns the padded prompt length (0 when every request hit the
        prefix cache)."""
        assert reqs and len(reqs) <= len(self._free_slots())
        if self._prefix is not None:
            cold = []
            for r in reqs:
                ent = self._prefix_lookup(r)
                if ent is not None:
                    self._restore_entry(r, ent)
                else:
                    cold.append(r)
            reqs = cold
            if not reqs:
                return 0
        plens = np.array([self._prefill_len(r) for r in reqs], np.int32)
        tok_lens = [len(r.prompt) for r in reqs]
        feed = [min(tl, self._c) for tl in tok_lens]
        # clamp the scored prefix to the one-shot budget: the prompt tail
        # past it is chunk-scored through the decode contract (§12)
        plens = np.minimum(plens, np.int32(self._c))
        b = len(reqs)
        if self._ragged_prefill:
            pad_to = _prompt_bucket(max(feed), self.s_max)
        else:
            pad_to = max(feed)          # enc-dec: one request per window
        toks = np.zeros((b, pad_to), np.int32)
        for i, r in enumerate(reqs):
            toks[i, :feed[i]] = r.prompt[:feed[i]]
        batch = {"tokens": jnp.asarray(toks)}
        if self.cfg.frontend == "vision_stub":
            batch["patches"] = jnp.zeros(
                (b, self.cfg.n_frontend_tokens, self.cfg.d_model),
                jnp.bfloat16)
        if self.cfg.n_enc_layers:
            batch["frames"] = jnp.zeros(
                (b, max(max(tok_lens), 2), self.cfg.d_model), jnp.bfloat16)
        tr = obs.enabled()
        t_pf = time.perf_counter() if tr else 0.0
        if tr:
            # queue wait ends when the admitting prefill starts
            for r in reqs:
                tq = self._t_enq.get(id(r))
                if tq is not None:
                    self._m["qwait"].observe(t_pf - tq)
        with self.scope():
            if self._ragged_prefill:
                logits, pre = self._prefill(self.params, batch,
                                            jnp.asarray(plens))
            else:
                logits, pre = self._prefill(self.params, batch)
        self._m["prefills"].inc()
        self._m["prefill_reqs"].inc(b)
        if tr:
            self._m["pad_frac"].observe(1.0 - sum(feed) / float(b * pad_to))
        temps = np.array([r.temperature for r in reqs], np.float32)
        first = self._sample(logits, temps)
        t_first = time.perf_counter() if tr else 0.0
        for i, req in enumerate(reqs):
            full_fed = feed[i] == tok_lens[i]
            if full_fed:
                tok = int(first[i])
                req.out_tokens.append(tok)
                if req.on_token is not None:
                    req.on_token(req, tok)
                self.events.append({"kind": "token", "rid": req.rid,
                                    "token": tok})
                if tr:
                    tq = self._t_enq.get(id(req))
                    if tq is not None:
                        self._m["ttft"].observe(t_first - tq)
                # the prefill-sampled token can already satisfy the request
                if (req.eos_id is not None and tok == req.eos_id) or \
                        len(req.out_tokens) >= req.max_new_tokens:
                    req.done = True
                    self._outcome(req, "completed")
                    self.events.append({"kind": "finish", "rid": req.rid,
                                        "outcome": "completed"})
                    self._t_enq.pop(id(req), None)
                    continue
            slot = self._free_slot()
            self.caches = self._write(self.caches, pre,
                                      jnp.int32(i), jnp.int32(slot))
            self.pos[slot] = plens[i]
            self._pf_next[slot] = feed[i]
            self.active[slot] = req
            self._last_tok_t[slot] = t_first
            if full_fed:
                self.last_token[slot, 0] = tok
            self._maybe_snapshot(slot, req)
        return pad_to

    # --------------------------------------------------------------- decode
    def step(self):
        """One engine step for all active slots — exactly **one** jitted
        scoring call however mixed the batch is.  Each row brings a
        per-step token quota: 1 for a decoding row, up to ``chunk_len``
        prompt tokens for a prefilling row, and ``spec_len + 1``
        (last token + the drafted tokens, gated on greedy agreement) for
        a speculative verify row — PR 9's sequential verify loop scored
        these one call per position.  The scan masks each row inactive
        past its quota (§6: masked rows never write cache), so per-row
        results are independent of the padded scan length and of what
        the other rows are doing — the bit-identity argument of
        DESIGN.md §12.  Sampling runs in-graph; the cache argument is
        donated (no per-step double-buffer).

        The step is one ``serve.step`` profiler span, tiled by four
        children: ``plan`` (host arrays, key split, transfers),
        ``dispatch`` (the jitted call until it returns), ``wait`` (the
        read-back, the only place the host blocks on the device) and
        ``emit`` (bookkeeping and callbacks).  Its counts become span
        arguments only while the profiler records."""
        act = np.array([r is not None for r in self.active])
        if not act.any():
            return
        with TraceAnnotation("serve.step") as span:
            with TraceAnnotation("serve.step.plan"):
                plan = self._plan_step(act)
            with TraceAnnotation("serve.step.dispatch"), self.scope():
                emitted, live, self.caches = self._chunk(
                    self.params, plan.tokens, self.caches, *plan.args)
            with TraceAnnotation("serve.step.wait"):
                emitted = np.asarray(emitted)                  # [K, B]
                live = np.asarray(live)                        # [K, B]
            with TraceAnnotation("serve.step.emit"):
                n_tok = self._emit_step(plan, emitted, live)
            if span.is_enabled():
                span.set_metadata(active=int(act.sum()), slots=self.slots,
                                  chunk=plan.k,
                                  prefilling=int(plan.prefilling.sum()),
                                  tokens=n_tok)

    def _plan_step(self, act: np.ndarray) -> "_StepPlan":
        """The step's per-row work plan, fixed BEFORE any bookkeeping
        mutates (a speculative draft runs here: its tokens are part of
        the plan), and the step program's arguments on the device."""
        d = self.spec_len
        spec_rows = np.zeros(self.slots, bool)
        dtoks = None
        if self.spec_depth is not None:
            spec_rows = self._spec_rows()
            if spec_rows.any():
                from repro.core.backend import use_spec_depth
                with self.scope(), use_spec_depth(self.spec_depth):
                    dtoks = np.asarray(self._draft(
                        self.params, jnp.asarray(self.last_token),
                        self.caches, jnp.asarray(self.pos),
                        jnp.asarray(spec_rows)))
                self._m["spec_rounds"].inc()
                self._m["spec_draft_tokens"].inc(d * int(spec_rows.sum()))
        quota = np.zeros(self.slots, np.int32)
        gated = np.zeros(self.slots, bool)
        prefilling = np.zeros(self.slots, bool)
        for i, r in enumerate(self.active):
            if r is None:
                continue
            if self._prefilling(i):
                prefilling[i] = True
                quota[i] = min(len(r.prompt) - int(self._pf_next[i]),
                               self._c)
            elif spec_rows[i]:
                quota[i] = d + 1
                gated[i] = True
            else:
                quota[i] = 1
        k = 1 << (int(quota.max()) - 1).bit_length()
        toks = np.zeros((self.slots, k), np.int32)
        for i in np.flatnonzero(act):
            if prefilling[i]:
                pf = int(self._pf_next[i])
                toks[i, :quota[i]] = \
                    self.active[i].prompt[pf:pf + int(quota[i])]
            else:
                toks[i, 0] = self.last_token[i, 0]
                if gated[i]:
                    toks[i, 1:d + 1] = dtoks[:, i]
        temps = np.array([r.temperature if r is not None else 0.0
                          for r in self.active], np.float32)
        self.key, sub = jax.random.split(self.key)
        args = (jnp.asarray(self.pos), jnp.asarray(quota),
                jnp.asarray(gated), jnp.asarray(act), jnp.asarray(temps),
                sub)
        return _StepPlan(act, quota, gated, prefilling, spec_rows, dtoks, k,
                         jnp.asarray(toks), args, time.perf_counter())

    def _emit_step(self, plan: "_StepPlan", emitted: np.ndarray,
                   live: np.ndarray) -> int:
        """Per-row bookkeeping after a step: emit each live row's tokens
        (callbacks, counters), retire finished rows, account the
        speculative rounds.  Returns the number of tokens emitted."""
        act, quota, gated = plan.act, plan.quota, plan.gated
        spec_rows, dtoks, d = plan.spec_rows, plan.dtoks, self.spec_len
        tr = obs.enabled()
        self._m["decode_steps"].inc()
        if spec_rows.any():
            self._m["spec_verify_steps"].inc(
                int(live[:, spec_rows].any(axis=1).sum()))
            if tr:
                self._m["spec_verify_s"].observe(
                    time.perf_counter() - plan.t_planned)
        if tr:
            occ = float(act.mean())
            self._m["occupancy"].observe(occ)
            self._m["padded"].observe(1.0 - occ)
            self._g_pages.set(int(np.sum(
                -(-self.pos[act] // self.page_tokens))))
        t_tok = time.perf_counter() if tr else 0.0
        n_tok = 0
        accepted = np.zeros(self.slots, np.int64)
        for i in np.flatnonzero(act):
            req = self.active[i]
            q = int(quota[i])
            if plan.prefilling[i]:
                self._pf_next[i] += q
                self.pos[i] += q
                self._maybe_snapshot(i, req)
                if int(self._pf_next[i]) >= len(req.prompt):
                    # the final chunk step's logits ARE the first-token
                    # logits — same position the one-shot path samples
                    tok = int(emitted[q - 1, i])
                    self._emit(req, i, tok, t_tok, first=True)
                    n_tok += 1
                    if (req.eos_id is not None and tok == req.eos_id) or \
                            len(req.out_tokens) >= req.max_new_tokens:
                        self._finish(req, i)
                    else:
                        self.last_token[i, 0] = tok
                continue
            for v in range(q):
                if not live[v, i]:
                    break
                tok = int(emitted[v, i])
                self._emit(req, i, tok, t_tok)
                n_tok += 1
                self.pos[i] += 1
                self.last_token[i, 0] = tok
                matched = bool(gated[i]) and v < d \
                    and tok == int(dtoks[v, i])
                if matched:
                    accepted[i] += 1
                # pos is the *next* write index; retire once it passes the
                # last valid cache slot s_max-1 (matches the admission
                # bound plen < s_max)
                if (req.eos_id is not None and tok == req.eos_id) or \
                        len(req.out_tokens) >= req.max_new_tokens or \
                        self.pos[i] >= self.s_max:
                    self._finish(req, i)
                    break
                if gated[i] and not matched:
                    # the correction token was already emitted above;
                    # nothing to rewind (unverified draft KV was only
                    # written past this row's final pos — never read)
                    break
        for i in np.flatnonzero(spec_rows):
            self._m["spec_accepted"].inc(int(accepted[i]))
            self._m["spec_rolled_back"].inc(d - int(accepted[i]))
            if tr:
                self._m["spec_accept_frac"].observe(accepted[i] / d)
        return n_tok

    # ------------------------------------------------- speculative decode
    def _spec_rows(self) -> np.ndarray:
        """Rows eligible to draft this round: active, fully prefilled,
        opted in, greedy (temperature 0 — stochastic rows cannot be
        verified by argmax), at least 2 tokens still wanted (a 1-token
        round gains nothing over a plain step), and enough cache ring
        left for full acceptance."""
        ok = np.zeros(self.slots, bool)
        for i, r in enumerate(self.active):
            if r is None or not r.spec or r.temperature != 0.0:
                continue
            if self._prefilling(i):
                continue
            if r.max_new_tokens - len(r.out_tokens) < 2:
                continue
            if self.pos[i] + self.spec_len >= self.s_max:
                continue
            ok[i] = True
        return ok

    def _sample(self, logits, temperatures) -> np.ndarray:
        """Host-side batched sampling: greedy where ``temperatures[i] ==
        0``, else a softmax draw at that row's temperature (one key split
        per call).  The decode path samples in-graph with the same
        semantics; this stays for prefill logits and as the reference for
        tests."""
        l = logits if logits.ndim == 2 else logits[:, -1]
        self.key, sub = jax.random.split(self.key)
        greedy = jnp.argmax(l, axis=-1)
        temps = np.asarray(temperatures, np.float32)
        if not np.any(temps > 0):
            return np.asarray(greedy, dtype=np.int32)
        t = jnp.asarray(temps)
        sampled = jax.random.categorical(
            sub, l.astype(jnp.float32) / jnp.maximum(t, 1e-6)[:, None],
            axis=-1)
        return np.asarray(jnp.where(t > 0, sampled, greedy), dtype=np.int32)

    # ------------------------------------------------------- prefix cache
    def _init_prefix(self, prefix_pages, prefix_entries: int) -> None:
        """Build the device half of the prefix cache: a page-pool pytree
        (one pool leaf per *paged* cache leaf, ``n_pages`` rows of
        ``page_tokens`` positions) plus a side slab holding whole rows of
        the non-paged leaves (rings, recurrent state) at each snapshot
        boundary, and the jitted snapshot/restore copy programs.  Cache
        families whose leaves cannot be classified (a sequence dim that
        does not scale 1:1 with ``s_max``) silently serve without reuse —
        correctness never depends on the cache."""
        from repro.serve.paged import PageAllocator, PrefixIndex
        api, P_ = self.api, self.page_tokens
        try:
            sdims, ok = self._classify_cache_leaves()
        except Exception:  # smelint: disable=EXC001 — probe over arbitrary arch cache builders: any classification failure means "serve without reuse", never abort serving
            ok = False
        if not ok:
            return
        n_pages = int(prefix_pages) if prefix_pages else 4 * self._max_pages
        self._pool = jax.jit(
            lambda: api.init_cache(batch=n_pages, s_max=P_),
            out_shardings=self._rep)()
        self._side = jax.jit(
            lambda: api.init_cache(batch=prefix_entries, s_max=P_),
            out_shardings=self._rep)()
        bdims = self._cache_bdim

        def snap_fn(pool, side, caches, slot, ids, first_new, n, entry):
            # pages [first_new, n) of the slot row -> pool rows ids[j];
            # the chain prefix [0, first_new) is already resident
            def per_pool(pl, cl, bd, sd):
                if sd < 0:
                    return pl
                row = jax.lax.dynamic_slice_in_dim(cl, slot, 1, axis=bd)

                def body(j, acc):
                    src = jax.lax.dynamic_slice_in_dim(
                        row, j * P_, P_, axis=sd)
                    return jax.lax.dynamic_update_slice_in_dim(
                        acc, src.astype(acc.dtype), ids[j], axis=bd)
                return jax.lax.fori_loop(first_new, n, body, pl)

            def per_side(sl, cl, bd, sd):
                if sd >= 0:
                    return sl
                row = jax.lax.dynamic_slice_in_dim(cl, slot, 1, axis=bd)
                return jax.lax.dynamic_update_slice_in_dim(
                    sl, row.astype(sl.dtype), entry, axis=bd)

            return (jax.tree.map(per_pool, pool, caches, bdims, sdims),
                    jax.tree.map(per_side, side, caches, bdims, sdims))

        self._snap = jax.jit(
            snap_fn,
            in_shardings=(self._rep, self._rep, self.cache_sh, self._rep,
                          self._rep, self._rep, self._rep, self._rep),
            out_shardings=(self._rep, self._rep),
            donate_argnums=(0, 1))

        def restore_fn(caches, pool, side, slot, ids, n, entry):
            def per_leaf(cl, pl, sl, bd, sd):
                if sd < 0:
                    row = jax.lax.dynamic_slice_in_dim(sl, entry, 1,
                                                       axis=bd)
                    return jax.lax.dynamic_update_slice_in_dim(
                        cl, row.astype(cl.dtype), slot, axis=bd)
                row = jax.lax.dynamic_slice_in_dim(cl, slot, 1, axis=bd)

                def body(j, acc):
                    page = jax.lax.dynamic_slice_in_dim(
                        pl, ids[j], 1, axis=bd)
                    return jax.lax.dynamic_update_slice_in_dim(
                        acc, page.astype(acc.dtype), j * P_, axis=sd)
                row = jax.lax.fori_loop(0, n, body, row)
                return jax.lax.dynamic_update_slice_in_dim(
                    cl, row, slot, axis=bd)
            return jax.tree.map(per_leaf, caches, pool, side, bdims, sdims)

        self._restore = jax.jit(
            restore_fn,
            in_shardings=(self.cache_sh, self._rep, self._rep, self._rep,
                          self._rep, self._rep, self._rep),
            out_shardings=self.cache_sh,
            donate_argnums=(0,))
        self._prefix_sdims = sdims
        self._prefix = PrefixIndex(PageAllocator(n_pages), prefix_entries,
                                   P_)

    def _classify_cache_leaves(self):
        """Structurally split cache leaves into *paged* (exactly one
        non-batch dim scaling 1:1 with ``s_max`` — KV rings at full
        length) and *side* (shape independent of ``s_max`` — recurrent
        state, windowed rings, conv tails).  Probes abstract shapes at
        ``s_max``, ``2*s_max`` and ``page_tokens``; any leaf fitting
        neither pattern disables the prefix cache for this family."""
        P_ = self.page_tokens
        a1 = self.api.abstract_cache(batch=self.slots, s_max=self.s_max)
        a2 = self.api.abstract_cache(batch=self.slots, s_max=2 * self.s_max)
        ap = self.api.abstract_cache(batch=self.slots, s_max=P_)
        ok = [True]

        def one(l1, l2, lp, bd):
            diffs = [dd for dd in range(l1.ndim)
                     if l1.shape[dd] != l2.shape[dd]]
            if not diffs:
                if lp.shape != l1.shape:
                    ok[0] = False
                return -1
            if len(diffs) != 1:
                ok[0] = False
                return -1
            dd = diffs[0]
            if dd == bd or l1.shape[dd] != self.s_max \
                    or l2.shape[dd] != 2 * self.s_max \
                    or lp.shape[dd] != P_:
                ok[0] = False
                return -1
            return dd

        sdims = jax.tree.map(one, a1, a2, ap, self._cache_bdim)
        return sdims, ok[0]

    def _prefix_lookup(self, req: Request):
        """Longest token-id-exact snapshot usable for this prompt (at
        least one prompt token is always left to recompute so the
        first-token logits exist)."""
        ent = self._prefix.lookup(np.asarray(req.prompt, np.int32),
                                  len(req.prompt) - 1)
        self._m["prefix_hits" if ent is not None else
                "prefix_misses"].inc()
        return ent

    def _restore_entry(self, req: Request, ent) -> None:
        """Admit a prefix-cache hit: copy the snapshot's pages + side row
        into a free slot and resume prefilling at ``ent.length``.  The
        snapshot is the deterministic chunk-schedule state of exactly
        these token ids, so the restored request's tokens are
        bit-identical to a cold admission (DESIGN.md §12)."""
        slot = self._free_slot()
        ids = np.zeros(self._max_pages, np.int32)
        n = len(ent.page_ids)
        ids[:n] = ent.page_ids
        tr = obs.enabled()
        t0 = time.perf_counter() if tr else 0.0
        if tr:
            tq = self._t_enq.get(id(req))
            if tq is not None:
                self._m["qwait"].observe(t0 - tq)
        with self.scope():
            self.caches = self._restore(
                self.caches, self._pool, self._side, jnp.int32(slot),
                jnp.asarray(ids), jnp.int32(n), jnp.int32(ent.entry_slot))
        self.pos[slot] = ent.length
        self._pf_next[slot] = ent.length
        self.active[slot] = req
        self._last_tok_t[slot] = time.perf_counter() if tr else 0.0

    def _maybe_snapshot(self, slot: int, req: Request) -> None:
        """Snapshot the slot's cache row at a chunk boundary (``pf_next``
        a positive multiple of the one-shot budget — page-aligned by the
        constructor check).  Safe to call for just-finished rows: the
        device cache row is intact until the slot is rewritten."""
        if self._prefix is None:
            return
        L = int(self._pf_next[slot])
        if L <= 0 or L % self._c or L % self.page_tokens:
            return
        toks = np.asarray(req.prompt[:L], np.int32)
        if self._prefix.has(toks):
            return
        ev0 = self._prefix.evictions
        plan = self._prefix.prepare(toks)
        self._m["prefix_evictions"].inc(self._prefix.evictions - ev0)
        if plan is None:
            return
        ids = np.zeros(self._max_pages, np.int32)
        n = len(plan.entry.page_ids)
        ids[:n] = plan.entry.page_ids
        with self.scope():
            self._pool, self._side = self._snap(
                self._pool, self._side, self.caches, jnp.int32(slot),
                jnp.asarray(ids), jnp.int32(plan.first_new), jnp.int32(n),
                jnp.int32(plan.entry.entry_slot))
        self._prefix.commit(plan)
        self._m["prefix_snapshots"].inc()
        self._g_pool.set(self._prefix.alloc.in_use)
        self._g_entries.set(len(self._prefix))

    # ------------------------------------------------------------------ run
    def run(self, requests: List[Request], max_steps: int = 1000) -> Dict:
        """Drive ``requests`` to completion (or ``max_steps``) through the
        open-stream path: every request is :meth:`submit`-ted, then each
        loop iteration :meth:`pump`-s the queue (one batched prefill per
        drain window) and runs one engine :meth:`step`.  Stats split
        ``completed`` (reached eos/max_new_tokens/cache end), ``evicted``
        (cut off at ``max_steps`` with partial output), ``rejected``
        (prompt cannot fit the cache — skipped, the rest of the batch
        keeps running) and ``unserved`` (never admitted); the four always
        sum to ``len(requests)``.

        Every outcome increments this engine's
        ``serve_requests_total{outcome=...}`` child the moment it happens
        AND stamps ``Request.outcome`` (DESIGN.md §9/§12): the returned
        split is computed from **this call's requests**, so it stays
        correct when other submitters' requests reach their outcomes
        between this run's steps (registry deltas no longer assume the
        engine serves one closed batch at a time)."""
        t0 = time.time()
        mine = {id(r) for r in requests}
        for r in requests:
            self.submit(r)
        steps = 0
        while (self._queue or any(r is not None for r in self.active)) \
                and steps < max_steps:
            self.pump()
            self.step()
            steps += 1
        # cutoff classification: anything not completed/rejected by now is
        # evicted (partial output) or unserved (never admitted)
        for r in requests:
            if r.done or r.outcome is not None:
                continue
            if r.out_tokens:
                self._outcome(r, "evicted")
            else:
                self._outcome(r, "unserved")
            self._t_enq.pop(id(r), None)
        if self._queue:
            # drop this run's unserved leftovers; foreign requests stay
            self._queue = collections.deque(
                q for q in self._queue if id(q) not in mine)
            self._g_queue.set(len(self._queue))
        counts = {o: 0 for o in ("completed", "evicted", "rejected",
                                 "unserved")}
        for r in requests:
            if r.outcome in counts:
                counts[r.outcome] += 1
        return {**counts, "wall_s": time.time() - t0, **self._stats}


def _slot_write(full, one, slot: int):
    """Write a batch-1 cache leaf into slot `slot` of the batched leaf.

    Handles leading stacked dims: the batch dim is the one where
    full.shape[d] == slots and one.shape[d] == 1 (first mismatch match).
    With slots == 1 no dim mismatches — the single slot IS the whole
    batch, so the prefill leaf replaces the batched leaf outright.

    Kept as the eager single-leaf reference for the engine's jitted
    ``_write`` program (tests exercise it directly)."""
    if one.shape == full.shape:
        return one.astype(full.dtype)
    for d in range(full.ndim):
        if one.shape[d] == 1 and full.shape[d] != 1:
            idx = tuple([slice(None)] * d + [slice(slot, slot + 1)])
            return full.at[idx].set(one.astype(full.dtype))
    return full
