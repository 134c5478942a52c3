"""What belongs to one architecture, one module each: ``arch/<name>.py``
for the first name of a configuration's ``architectures``, as its
published ``config.json`` gives it.

A module provides:

* ``program_config(cfg)``: the program's ``ModelConfig`` with every size
  from the file;
* ``leaf_specs(cfg)``: ``{name: (shape, kind)}`` of the weights that
  ``weights.make_weights`` draws, in the reference's own layout;
* ``program_params(w, api)``: the program's param tree over those arrays
  (``weights.program_params`` checks it against ``api.init_params``);
* ``prepare(w, cfg)`` and ``logit_gaps(p, cfg, tokens, targets,
  round_to=None)``: the plain reference, as ``harness.gaps`` calls it;
* ``sme_calls(cfg, rows)``: ``(operations, bytes)`` of each SME call of
  one decode step on ``rows`` rows (``work.sme_call``);
* ``token_flops(cfg, pos, head)``: operations to process one token at
  position ``pos``, with the head when ``head``.

and may declare, where its program does more than every decoder's:

* ``SCOPES``: the ``jax.named_scope`` names its program sets beyond
  ``program_trace.SCOPES`` (``("moe",)``), which the per-layer readers
  then read (``scopes``, ``program_trace.Program.scope_ms_per_step``);
* ``KINDS``: ``{kind: fn(z, shape) -> z}``, draws of its own for its
  ``leaf_specs``, each from a standard normal ``z`` of ``shape`` in
  float32, beside the generic kinds of ``weights.DRAWS``.

A module is found on this package's path (``__path__``).
"""
from __future__ import annotations

import importlib
import pathlib
from typing import Dict, Tuple

ARCH = pathlib.Path(__file__).resolve().parent


def for_config(cfg: Dict):
    """The module of ``cfg``'s architecture; a name without one is an
    error that names the file to add."""
    name = cfg["architectures"][0]
    if not name.isidentifier() or not any(
            (pathlib.Path(p) / f"{name}.py").is_file() for p in __path__):
        raise ValueError(f"no benchmark module for the architecture "
                         f"{name!r}: add {ARCH.parent.name}/{ARCH.name}/"
                         f"{name}.py")
    return importlib.import_module(f"{__name__}.{name}")


def scopes(cfg: Dict) -> Tuple[str, ...]:
    """The scopes a trace of ``cfg``'s program is read by: every
    decoder's (``program_trace.SCOPES``), then those its architecture's
    module declares in ``SCOPES``.  A declared name that is one of
    those, or ``unscoped``, is an error."""
    from program_trace import SCOPES, UNSCOPED
    mod = for_config(cfg)
    own = tuple(getattr(mod, "SCOPES", ()))
    clash = sorted(set(own) & set(SCOPES + (UNSCOPED,)))
    if clash:
        raise ValueError(f"{mod.__name__}.SCOPES names {clash}, which "
                         f"every trace already reads")
    return SCOPES + own
