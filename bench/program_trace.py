"""The program's own spans and scopes in a profiler trace.

``extract`` is ``tracing.extract`` with two keys added and nothing else
changed:

    {"devices": [{"plane", "modules", "ops", "paths": [op_path, ...]}],
     "host": [...],
     "program": [[name, start_ns, dur_ns, {arg: value}], ...]}

``program`` holds the engine's host spans (``serve.*``
``TraceAnnotation``s, ``serve/engine.py``) with their arguments.
``paths`` runs beside ``ops``: for each op, its ``op_name`` metadata
(``jit(chunk_fn)/while/body/attention/dot_general``), or None.  A TPU
trace keeps that metadata on each op's event metadata (the ``tf_op``
stat), which ``ProfileData`` does not expose, so it is read from the
serialized XSpace with a protobuf wire decoder and matched to the op
events by program id and HLO text.

An op's scope is resolved when it is read, against the cell's set of
scopes (``arch.scopes``: ``SCOPES`` and those the architecture's module
declares): the innermost of the set its path names, or None
(``unscoped``: never guessed into a layer).  A recorded trace may hold
``scopes`` in place of ``paths``, already resolved against ``SCOPES``.

A program older than the spans and scopes reads as no spans and no
scopes, and every reader of them then returns None.  Readers reach the
added keys through ``program(ctx)``: a trace built from ``extract`` (the
tests, the fixtures) has them; for a benchmark run they are read once
from the xplane the harness left in the cell's trace directory, and
kept on the trace.  A traced run that left no xplane there is an error,
never a reading of None.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import arch
import tracing

PREFIX = "serve."
#: the named scopes every architecture's step program sets
#: (``jax.named_scope``); an architecture's module may declare more
SCOPES = ("attention", "kv_cache", "lm_head")
UNSCOPED = "unscoped"
#: the engine's step program, as the trace names its runs
STEP_PROGRAM = "jit_chunk_fn"
_PROGRAM_ID = re.compile(r"\((\d+)\)$")


def scope_of(op_path: str, scopes: Sequence[str] = SCOPES) -> Optional[str]:
    """The innermost of ``scopes`` among the components of an ``a/b/c``
    op path, or None."""
    found = [p for p in op_path.split("/") if p in scopes]
    return found[-1] if found else None


# -- the XSpace protobuf, as far as the op metadata needs it ---------------
def _varint(buf, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """``(field number, value)`` of one protobuf message: varints as
    ints, length-delimited fields as memoryviews, fixed-width as bytes."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            value, i = bytes(buf[i:i + n]), i + n
        else:
            raise ValueError(f"protobuf wire type {wire} in an xplane")
        yield key >> 3, value


def _map_values(entries) -> Iterator:
    """The values (field 2) of protobuf map entries."""
    for entry in entries:
        for f, v in _fields(entry):
            if f == 2:
                yield v


def op_paths(xspace: bytes) -> Dict[str, Dict[Tuple[int, str], str]]:
    """Per device plane, ``{(program id, op event name): op path}`` for
    every op with a ``tf_op`` stat: one entry per op of a program, not
    per event.  XSpace: planes = 1;
    XPlane: name = 2, event_metadata = 4, stat_metadata = 5;
    XEventMetadata: name = 2, stats = 5; XStat: metadata_id = 1,
    uint64 = 3, int64 = 4, str = 5; XStatMetadata: id = 1, name = 2."""
    out = {}
    for f, plane in _fields(memoryview(xspace)):
        if f != 1:
            continue
        name, events, stat_meta = "", [], []
        for pf, pv in _fields(plane):
            if pf == 2:
                name = bytes(pv).decode()
            elif pf == 4:
                events.append(pv)
            elif pf == 5:
                stat_meta.append(pv)
        if not name.startswith("/device:"):
            continue
        ids = {}
        for meta in _map_values(stat_meta):
            d = dict(_fields(meta))             # proto3: 0 is left out
            ids[bytes(d.get(2, b"")).decode()] = d.get(1, 0)
        found = out[name] = {}
        if "tf_op" not in ids:
            continue
        for meta in _map_values(events):
            op_name, op_path, program = "", "", None
            for mf, mv in _fields(meta):
                if mf == 2:
                    op_name = bytes(mv).decode()
                elif mf == 5:
                    stat = dict(_fields(mv))
                    if stat.get(1, 0) == ids["tf_op"]:
                        op_path = bytes(stat.get(5, b"")).decode()
                    elif stat.get(1, 0) == ids.get("program_id"):
                        program = stat.get(3, stat.get(4))
            if op_path:
                found[(program, op_name)] = op_path
    return out


def extract(xspace: bytes) -> Dict:
    """``tracing.extract`` of the serialized XSpace ``xspace``, plus
    ``program`` and each device's ``paths``."""
    import jax
    pd = jax.profiler.ProfileData.from_serialized_xspace(xspace)
    data = tracing.extract(pd)
    by_plane = op_paths(xspace)
    program, paths = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            found = by_plane.get(plane.name, {})
            runs, ops = [], []
            for line in plane.lines:
                if line.name == tracing.MODULES_LINE:
                    for ev in line.events:
                        pid = _PROGRAM_ID.search(ev.name)
                        runs.append((int(ev.start_ns),
                                     int(ev.start_ns + ev.duration_ns),
                                     int(pid.group(1)) if pid else None))
                elif line.name == tracing.OPS_LINE:
                    ops += [(int(ev.start_ns), ev.name)
                            for ev in line.events]
            if runs:
                run = tracing.enclosing(sorted(runs))
                # ``tracing.extract`` sorts the same events the same way
                paths.append([found.get((run(s), name))
                              for s, name in sorted(ops,
                                                    key=lambda o: o[0])])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PREFIX):
                        args = {k: v for k, v in ev.stats
                                if not k.startswith("_")}
                        program.append([ev.name, int(ev.start_ns),
                                        int(ev.duration_ns), args])
    for dev, found in zip(data["devices"], paths):
        dev["paths"] = found
    program.sort(key=lambda p: p[1])
    data["program"] = program
    return data


def read_dir(trace_dir: str) -> Dict:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    with open(files[-1], "rb") as f:
        return extract(f.read())


class Program:
    """The program's spans and scoped device time in one trace."""

    def __init__(self, trace: tracing.Trace, scopes: Sequence[str] = SCOPES):
        self.trace = trace
        self.scopes = tuple(scopes)
        lo, hi = trace.window()
        self.spans_ = [p for p in trace.data["program"] if lo <= p[1] < hi]

    def spans(self, name: str) -> List[List]:
        """``[start, dur, args]`` of the ``name`` spans that start in the
        window."""
        return [[s, d, a] for n, s, d, a in self.spans_ if n == name]

    def children(self, parent: str, child: str) -> List[List[List]]:
        """For each ``parent`` span, the ``child`` spans inside it."""
        kids = self.spans(child)
        return [[k for k in kids if s <= k[0] and k[0] + k[1] <= s + d]
                for s, d, _ in self.spans(parent)]

    def skew_ns(self, dev: Dict) -> int:
        """How far ``dev``'s stamps run ahead of the host's, at least: a
        step program cannot start before its ``serve.step.dispatch``
        opened, so the earliest any run is stamped before the span that
        launched it (the run starting nearest that span, within 5 ms) is
        a lower bound.  0 where no run is stamped early."""
        starts = sorted(s for n, s, _ in dev["modules"] if n == STEP_PROGRAM)
        skew = 0
        for s, _, _ in self.spans("serve.step.dispatch"):
            i = bisect.bisect_left(starts, s - 5_000_000)
            near = [t for t in starts[i:i + 4] if abs(t - s) < 5_000_000]
            if near:
                skew = max(skew, s - min(near, key=lambda t: abs(t - s)))
        return skew

    def op_scopes(self, dev: Dict) -> List[Optional[str]]:
        """Each op's scope among ``self.scopes``, or None: resolved from
        ``dev``'s ``paths``, or as a recorded trace holds it resolved."""
        paths = dev.get("paths")
        if paths is None:
            return dev.get("scopes") or [None] * len(dev["ops"])
        of = {p: scope_of(p, self.scopes) for p in set(paths) if p}
        return [of.get(p) for p in paths]

    def scoped_ns(self, spans: List[Tuple[int, int]]) -> Dict[str, int]:
        """Device ns of the ops (containers and async ends left out, as in
        ``Trace.top_ops``) that start inside host ``spans``, by scope of
        ``self.scopes``, summed over devices; ``unscoped`` holds the ops
        no scope names.  Op stamps are moved onto the host clock by
        ``skew_ns`` first."""
        inside = tracing.enclosing(sorted((s, e, "") for s, e in spans))
        tot = {s: 0 for s in self.scopes + (UNSCOPED,)}
        for dev in self.trace.devices:
            skew = self.skew_ns(dev)
            for (name, s, dur, _), scope in zip(dev["ops"],
                                                 self.op_scopes(dev)):
                cls = tracing.op_class(name)
                if inside(s + skew) is None or cls in tracing.CONTAINERS \
                        or tracing._ASYNC.search(cls):
                    continue
                tot[scope or UNSCOPED] += dur
        return tot

    def scope_ms_per_step(self, scope: str) -> Optional[float]:
        """Device ms per ``bench.step`` of the ops scoped ``scope``; None
        when the trace names no scope at all (a program without them).
        A scope the cell's architecture does not declare is an error."""
        if scope not in self.scopes:
            raise ValueError(f"scope {scope!r} is not among {self.scopes}: "
                             f"the architecture's module declares its own "
                             f"in SCOPES")
        if not any(any(self.op_scopes(dev)) for dev in self.trace.devices):
            return None
        steps = self.trace.spans(tracing.HOST_PREFIX + "step")
        if not steps:
            return None
        ns = self.scoped_ns(steps)[scope]
        return ns / len(self.trace.devices) / len(steps) / 1e6


def trace_dir(cell_name: str) -> str:
    """Where ``harness.run_cell`` leaves a traced run's xplane."""
    from harness import CACHE
    return str(CACHE / "trace" / cell_name)


def program(ctx) -> Optional[Program]:
    """The run's ``Program``, reading the scopes of the cell's
    architecture (``arch.scopes``), or None when the run was not traced.
    A program older than the spans and scopes gives one with no spans
    and no scopes.  A traced run whose xplane is not where ``trace_dir``
    says raises ``FileNotFoundError``: a moved trace directory must not
    read as a program without spans."""
    trace = getattr(ctx, "trace", None)
    if trace is None:
        return None
    if "program" not in trace.data:
        extra = read_dir(trace_dir(ctx.cell.name))
        trace.data["program"] = extra["program"]
        for dev, more in zip(trace.devices, extra["devices"]):
            dev.setdefault("paths", more.get("paths"))
    return Program(trace, arch.scopes(ctx.cell.config))
