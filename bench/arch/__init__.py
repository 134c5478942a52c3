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
"""
from __future__ import annotations

import importlib
import pathlib
from typing import Dict

ARCH = pathlib.Path(__file__).resolve().parent


def for_config(cfg: Dict):
    """The module of ``cfg``'s architecture; a name without one is an
    error that names the file to add."""
    name = cfg["architectures"][0]
    if not name.isidentifier() or not (ARCH / f"{name}.py").is_file():
        raise ValueError(f"no benchmark module for the architecture "
                         f"{name!r}: add {ARCH.parent.name}/{ARCH.name}/"
                         f"{name}.py")
    return importlib.import_module(f"{__name__}.{name}")
