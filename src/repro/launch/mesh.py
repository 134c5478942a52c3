"""Device-mesh construction: the one place a ``Mesh`` is built.

``make_mesh`` is a FUNCTION so importing this module never touches jax
device state.  Every mesh in the repo (the serving engine's default 1x1
mesh, ``--mesh data,model`` serving, the multi-pod dry-run, tests) comes
from it, with ``AxisType.Auto`` axes: shardings are propagated by GSPMD
from the jit in/out shardings, never typed into avals.  (JAX >= 0.9
defaults ``jax.make_mesh`` to Explicit axes, under which an unannotated
gather such as the embedding lookup has no resolvable out-sharding.)

``parse_mesh`` backs the serving launcher's ``--mesh data,model`` flag:
CPU hosts get testable multi-device meshes by forcing host platform
devices (``--host-devices N``, which the launcher must translate into
XLA_FLAGS *before* the first jax import — jax locks the device count on
first init).
"""
from __future__ import annotations

from typing import Sequence, Tuple

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh", "parse_mesh"]


def make_mesh(shape: Sequence[int] = (1, 1),
              axes: Sequence[str] = ("data", "model")):
    """Mesh of ``shape`` over the first ``prod(shape)`` visible devices,
    every axis ``AxisType.Auto``.  Raises when too few devices exist."""
    shape, axes = tuple(int(s) for s in shape), tuple(axes)
    need = 1
    for s in shape:
        need *= s
    have = jax.device_count()
    if need > have:
        raise ValueError(
            f"mesh {shape} needs {need} devices but only {have} are "
            f"visible; on CPU pass --host-devices {need} (sets "
            f"--xla_force_host_platform_device_count before jax init)")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def parse_mesh(spec: str) -> Tuple[int, int]:
    """'data,model' string -> (data, model), e.g. '2,2' -> (2, 2)."""
    parts = spec.split(",")
    if len(parts) != 2:
        raise ValueError(
            f"--mesh expects 'data,model' (e.g. 2,2), got {spec!r}")
    data, model = (int(p) for p in parts)
    if data < 1 or model < 1:
        raise ValueError(f"mesh axes must be >= 1, got {spec!r}")
    return data, model
