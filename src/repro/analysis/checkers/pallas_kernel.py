"""pallas-kernel (PLK0xx): structural invariants of the Pallas kernels.

  * PLK001 — every ``make_async_copy`` has a started *and* awaited DMA in
    its enclosing kernel: a start without a wait races the consumer (the
    double-buffered plane streaming in ``sme_spmm_planes_decode`` is the
    pattern under protection); a copy constructed but never started is
    dead code that still allocates a semaphore slot.
  * PLK002 — grid/BlockSpec/scratch arity consistency: inline
    ``pl.BlockSpec`` index-map lambdas must take exactly ``len(grid)``
    positional args (scalar-prefetch refs ride ``*args``), and a locally
    resolvable kernel passed to ``pl.pallas_call`` must declare
    ``num_scalar_prefetch + len(in_specs) + n_outputs + len(scratch_shapes)``
    positional parameters — a drifted signature otherwise fails only at
    Mosaic lowering time, with a far worse error.
  * PLK003 — ``interpret=`` passed to ``pl.pallas_call`` as a literal
    constant: interpret mode must be plumbed from the caller (the
    off-TPU default lives in ``core.backend._resolve_interpret``), never
    baked into a kernel.
"""
from __future__ import annotations

import ast
from typing import Dict, List, Optional

from ..astutil import call_target, dotted, iter_functions
from ..core import Checker, FileContext, Finding, register_checker


def _outermost_functions(tree):
    """Top-level function defs (methods included), each owning its whole
    subtree — nested defs (DMA closures) stay with their kernel."""
    done = set()
    for fn in iter_functions(tree):
        if any(fn.qualname.startswith(q + ".") for q in done):
            continue
        done.add(fn.qualname)
        yield fn


@register_checker
class PallasKernelChecker(Checker):
    category = "pallas-kernel"
    rules = {
        "PLK001": "make_async_copy without a matching start()/wait() in "
                  "the enclosing kernel",
        "PLK002": "grid/BlockSpec/scratch arity mismatch",
        "PLK003": "interpret= hardcoded as a literal in pallas_call",
    }

    def check(self, ctx: FileContext) -> List[Finding]:
        findings: List[Finding] = []
        findings += self._check_dma(ctx)
        findings += self._check_arity(ctx)
        findings += self._check_interpret(ctx)
        return findings

    # ---------------------------------------------------------------- DMA
    def _check_dma(self, ctx: FileContext) -> List[Finding]:
        findings: List[Finding] = []
        for fn in _outermost_functions(ctx.tree):
            copies, starts, waits = [], 0, 0
            for node in ast.walk(fn.node):
                if not isinstance(node, ast.Call):
                    continue
                tgt = call_target(node)
                if tgt and tgt.endswith("make_async_copy"):
                    copies.append(node)
                # .start()/.wait() are often called on a *call result*
                # (`dma(i, slot).start()`), where the dotted chain does
                # not resolve — match the method name directly.
                elif isinstance(node.func, ast.Attribute):
                    if node.func.attr == "start":
                        starts += 1
                    elif node.func.attr == "wait":
                        waits += 1
            if not copies:
                continue
            if starts == 0:
                findings.append(ctx.finding(
                    copies[0], "PLK001",
                    f"make_async_copy in `{fn.qualname}` is never "
                    f".start()ed — dead DMA"))
            elif waits == 0:
                findings.append(ctx.finding(
                    copies[0], "PLK001",
                    f"make_async_copy in `{fn.qualname}` is started but "
                    f"never .wait()ed — the consumer races the DMA"))
        return findings

    # -------------------------------------------------------------- arity
    def _check_arity(self, ctx: FileContext) -> List[Finding]:
        findings: List[Finding] = []
        #: local defs by bare name, for kernel signature resolution
        local = {fn.name: fn.node for fn in iter_functions(ctx.tree)}
        #: assignment name -> grid-spec Call node, per file (kernels bind
        #: `grid_spec = pltpu.PrefetchScalarGridSpec(...)` right before
        #: the pallas_call)
        spec_assign: Dict[str, ast.Call] = {}
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 and \
                    isinstance(node.targets[0], ast.Name) and \
                    isinstance(node.value, ast.Call):
                tgt = call_target(node.value)
                if tgt and tgt.endswith("GridSpec"):
                    spec_assign[node.targets[0].id] = node.value

        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Call):
                tgt = call_target(node)
                if tgt and tgt.endswith("GridSpec"):
                    findings += self._check_gridspec(ctx, node)
                elif tgt and tgt.endswith("pallas_call"):
                    findings += self._check_kernel_sig(
                        ctx, node, local, spec_assign)
        return findings

    @staticmethod
    def _kw(call: ast.Call, name: str):
        for kw in call.keywords:
            if kw.arg == name:
                return kw.value
        return None

    def _check_gridspec(self, ctx, call: ast.Call) -> List[Finding]:
        findings: List[Finding] = []
        grid = self._kw(call, "grid")
        if not isinstance(grid, ast.Tuple):
            return findings
        n = len(grid.elts)
        specs: List[ast.AST] = []
        for field in ("in_specs", "out_specs"):
            v = self._kw(call, field)
            if isinstance(v, (ast.List, ast.Tuple)):
                specs += list(v.elts)
            elif v is not None:
                specs.append(v)
        # in_specs may be assembled as `[x_spec(...)] + list(tensor_specs)`
        # — only inline pl.BlockSpec(...) literals are checkable
        for spec in specs:
            if not (isinstance(spec, ast.Call) and
                    (call_target(spec) or "").endswith("BlockSpec")):
                continue
            lam = next((a for a in list(spec.args) +
                        [k.value for k in spec.keywords]
                        if isinstance(a, ast.Lambda)), None)
            if lam is None:
                continue
            npos = len(lam.args.posonlyargs) + len(lam.args.args) \
                - len(lam.args.defaults)
            if npos != n:
                findings.append(ctx.finding(
                    spec, "PLK002",
                    f"BlockSpec index map takes {npos} positional args "
                    f"but the grid has {n} dims — every grid index must "
                    f"be accepted (scalar-prefetch refs ride *args)"))
        return findings

    def _check_kernel_sig(self, ctx, call: ast.Call, local,
                          spec_assign) -> List[Finding]:
        findings: List[Finding] = []
        if not call.args:
            return findings
        kernel = call.args[0]
        if isinstance(kernel, ast.Call) and \
                (call_target(kernel) or "").endswith("partial") and \
                kernel.args:
            kernel = kernel.args[0]
        kname = dotted(kernel)
        if kname is None:
            return findings
        fn = local.get(kname.rsplit(".", 1)[-1])
        if fn is None:
            return findings
        gs = self._kw(call, "grid_spec")
        if isinstance(gs, ast.Name):
            gs = spec_assign.get(gs.id)
        elif not (isinstance(gs, ast.Call) and
                  (call_target(gs) or "").endswith("GridSpec")):
            gs = None
        if gs is None:
            return findings
        nsp_node = self._kw(gs, "num_scalar_prefetch")
        in_specs = self._kw(gs, "in_specs")
        scratch = self._kw(gs, "scratch_shapes")
        out_specs = self._kw(gs, "out_specs")
        if not (isinstance(nsp_node, ast.Constant) and
                isinstance(in_specs, (ast.List, ast.Tuple)) and
                isinstance(scratch, (ast.List, ast.Tuple))):
            return findings     # assembled dynamically: not checkable
        n_out = (len(out_specs.elts)
                 if isinstance(out_specs, (ast.List, ast.Tuple)) else 1)
        expect = (int(nsp_node.value) + len(in_specs.elts) + n_out
                  + len(scratch.elts))
        a = fn.args
        got = len(getattr(a, "posonlyargs", [])) + len(a.args)
        if got != expect:
            findings.append(ctx.finding(
                call, "PLK002",
                f"kernel `{kname}` takes {got} positional refs but the "
                f"grid spec provides {expect} (= num_scalar_prefetch "
                f"{int(nsp_node.value)} + {len(in_specs.elts)} inputs + "
                f"{n_out} outputs + {len(scratch.elts)} scratch)"))
        return findings

    # ---------------------------------------------------------- interpret
    def _check_interpret(self, ctx: FileContext) -> List[Finding]:
        findings: List[Finding] = []
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            tgt = call_target(node)
            if not (tgt and tgt.endswith("pallas_call")):
                continue
            for kw in node.keywords:
                if kw.arg == "interpret" and \
                        isinstance(kw.value, ast.Constant) and \
                        isinstance(kw.value.value, bool):
                    findings.append(ctx.finding(
                        node, "PLK003",
                        "interpret= hardcoded in pallas_call — plumb it "
                        "from the caller (off-TPU default: "
                        "core.backend._resolve_interpret)"))
        return findings
