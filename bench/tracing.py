"""From a profiler trace to the numbers the per-layer metrics read.

``extract`` reduces a ``jax.profiler`` xplane to a small dict, which is
also the format of the recorded fixture the tests use:

    {"devices": [{"plane": str,
                  "modules": [[program, start_ns, dur_ns], ...],
                  "ops": [[op, start_ns, dur_ns, custom_call_target], ...]}],
     "host": [[name, start_ns, dur_ns], ...]}     # bench.* annotations

On a TPU each device plane has an ``XLA Modules`` line, one event per
program run (``jit_chunk_fn(<hash>)``; kept without the hash), and an
``XLA Ops`` line, one event per HLO op, named by its HLO text
(``%_v2_call.63 = f32[128,1024] custom-call(...), custom_call_target=
"tpu_custom_call", ...``; kept as the op's name, ``_v2_call.63``, and
the custom-call target).  Ops nest (a ``while`` holds its body's ops) and
async copies span the work they overlap, so the device counts as busy
while a program runs: the union of the module intervals.  Host spans are
the benchmark's own ``TraceAnnotation``s; all share the profiler's clock.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Callable, Dict, Iterable, List, Optional, Tuple

MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench."
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_HASH = re.compile(r"\(\d+\)$")
#: op classes left out of the breakdown: control flow that holds other
#: ops, and the two ends of async copies, which span the work they overlap
CONTAINERS = ("while", "conditional", "call")
_ASYNC = re.compile(r"-(start|done)$")


def op_class(op: str) -> str:
    """``_v2_call.63`` -> ``_v2_call``."""
    return re.sub(r"(\.\d+)+$", "", op)


def extract(pd) -> Dict:
    """Compact events of a ``jax.profiler.ProfileData``."""
    devices, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            modules, ops = [], []
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    modules += [[_HASH.sub("", ev.name), int(ev.start_ns),
                                 int(ev.duration_ns)] for ev in line.events]
                elif line.name == OPS_LINE:
                    for ev in line.events:
                        text = ev.name
                        m = _TARGET.search(text)
                        ops.append([text.split(" = ", 1)[0].lstrip("%"),
                                    int(ev.start_ns), int(ev.duration_ns),
                                    m.group(1) if m else ""])
            if modules:
                devices.append({"plane": plane.name,
                                "modules": sorted(modules,
                                                  key=lambda m: m[1]),
                                "ops": sorted(ops, key=lambda o: o[1])})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        host.append([ev.name, int(ev.start_ns),
                                     int(ev.duration_ns)])
    host.sort(key=lambda h: h[1])
    return {"devices": devices, "host": host}


def read_dir(trace_dir: str) -> Dict:
    import jax
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return extract(jax.profiler.ProfileData.from_file(files[-1]))


def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merged, sorted [start, end) intervals."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def overlap(a: List[Tuple[int, int]], b: List[Tuple[int, int]]) -> int:
    """Length of the intersection of two merged, sorted interval lists."""
    i = j = tot = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        tot += max(0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def enclosing(spans: List[Tuple[int, int, str]]
              ) -> Callable[[int], Optional[str]]:
    """For ``spans`` (start, end, name), sorted and not overlapping: the
    function from a time to the name of the span holding it, or None."""
    starts = [s for s, _, _ in spans]

    def find(t: int) -> Optional[str]:
        i = bisect.bisect_right(starts, t) - 1
        return spans[i][2] if i >= 0 and t < spans[i][1] else None
    return find


class Trace:
    def __init__(self, data: Dict):
        self.data = data
        self.host = data["host"]
        self.devices = data["devices"]
        self._busy = [union((m[1], m[1] + m[2]) for m in d["modules"])
                      for d in self.devices]

    # -- host spans --------------------------------------------------------
    def spans(self, name: str) -> List[Tuple[int, int]]:
        return [(s, s + d) for n, s, d in self.host if n == name]

    def window(self) -> Tuple[int, int]:
        """The traced window: the ``bench.window`` span."""
        w = self.spans(HOST_PREFIX + "window")
        if not w:
            raise ValueError("trace holds no bench.window span")
        return w[0]

    def window_s(self) -> float:
        lo, hi = self.window()
        return (hi - lo) / 1e9

    # -- device time, averaged over the devices traced ------------------------
    def busy_s(self, spans: Optional[List[Tuple[int, int]]] = None) -> float:
        """Seconds in which a program ran, inside ``spans`` (default: the
        window), averaged over the devices."""
        spans = union(spans if spans is not None else [self.window()])
        if not self.devices:
            return 0.0
        tot = sum(overlap(b, spans) for b in self._busy)
        return tot / len(self.devices) / 1e9

    def idle_pct(self) -> Optional[float]:
        if not self.devices:
            return None
        return 100.0 * (1.0 - self.busy_s() / self.window_s())

    def ops_in(self, spans: List[Tuple[int, int]],
               match: Callable[[List], bool]) -> List[List]:
        """Ops matching ``match`` whose start lies in one of ``spans``."""
        inside = enclosing(sorted((s, e, "") for s, e in spans))
        return [op for d in self.devices for op in d["ops"]
                if inside(op[1]) is not None and match(op)]

    # -- breakdown -------------------------------------------------------------
    def top_ops(self, n: int = 10) -> List[List]:
        """The op classes that took most device time in the window, each
        as ``program/op_class`` with its seconds averaged over devices."""
        lo, hi = self.window()
        tot: Dict[str, int] = {}
        for d in self.devices:
            module = enclosing([(s, s + dur, name)
                                for name, s, dur in d["modules"]])
            for name, s, dur, _ in d["ops"]:
                cls = op_class(name)
                if not lo <= s < hi or cls in CONTAINERS or \
                        _ASYNC.search(cls):
                    continue
                key = f"{module(s) or '?'}/{cls}"
                tot[key] = tot.get(key, 0) + dur
        nd = max(len(self.devices), 1)
        best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / nd / 1e9] for k, v in best]

    def idle_gaps(self, n: int = 10) -> List[List]:
        """Idle time inside the window by what the host was doing: one
        total per host span name, then the longest single gaps."""
        lo, hi = self.window()
        doing = enclosing(sorted((s, s + d, name) for name, s, d in self.host
                                 if name != HOST_PREFIX + "window"))
        gaps = []
        for busy in self._busy:
            prev = lo
            for s, e in busy + [(hi, hi)]:
                s, e = max(s, lo), min(e, hi)
                if s > prev:
                    gaps.append((prev, s))
                prev = max(prev, e)
        named = [(doing((g0 + g1) // 2) or "other",
                  (g1 - g0) / 1e9) for g0, g1 in gaps]
        nd = max(len(self.devices), 1)
        totals: Dict[str, float] = {}
        for label, sec in named:
            totals[label] = totals.get(label, 0.0) + sec / nd
        out = [[f"total:{k}", v] for k, v in
               sorted(totals.items(), key=lambda kv: -kv[1])]
        for label, sec in sorted(named, key=lambda x: -x[1]):
            if len(out) >= n:
                break
            out.append([f"gap:{label}", sec])
        return out[:n]
