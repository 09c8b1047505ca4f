"""Reduction of a JAX profiler trace to the benchmark's intervals.

``jax.profiler`` writes ``<dir>/plugins/profile/<run>/<host>.xplane.pb``.
``ProfileData`` reads it with nothing but JAX.  On a TPU the device plane
(``/device:TPU:0``) has an ``XLA Modules`` line (one event per execution
of a compiled program, named after its jitted function) and an
``XLA Ops`` line (one event per operation, kernels included); the host
plane holds the ``bench.*`` spans the load generator writes with
``TraceAnnotation``.  Everything is kept in nanoseconds on the trace's
own clock, relative to the start of the first ``bench.*`` span.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, List, Tuple

Interval = Tuple[str, int, int]          # (name, start_ns, end_ns)
DEVICE_PREFIX = "/device:TPU:"
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
HOST_SPAN_PREFIX = "bench."
TOP = 10


def union_ns(spans: List[Tuple[int, int]]) -> int:
    """Length covered by the union of ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(spans):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_ns(spans: List[Tuple[int, int]], lo: int, hi: int):
    """The idle intervals of ``[lo, hi]`` that no span covers."""
    out, cur = [], lo
    for s, e in sorted(spans):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


@dataclasses.dataclass
class Reduced:
    window_ns: Tuple[int, int]
    ops: Dict[int, List[Interval]]        # per device id
    modules: Dict[int, List[Interval]]
    host: List[Interval]

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        """Seconds of the window with an operation running, averaged over
        devices (an operation that straddles an end counts its part
        inside)."""
        if not self.ops:
            return 0.0
        lo, hi = self.window_ns
        return sum(union_ns([(max(s, lo), min(e, hi)) for _, s, e in ops
                             if e > lo and s < hi])
                   for ops in self.ops.values()) * 1e-9 / len(self.ops)

    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def device_ops(self) -> List[Tuple[str, float]]:
        """Total device seconds per operation name, largest first."""
        acc: Dict[str, int] = {}
        for ops in self.ops.values():
            for name, s, e in ops:
                acc[name] = acc.get(name, 0) + e - s
        return sorted(((n, t * 1e-9) for n, t in acc.items()),
                      key=lambda x: -x[1])

    def idle_by_host(self) -> List[Tuple[str, float]]:
        """Idle device seconds (first device) by the host span that covers
        the most of each gap; gaps outside every span are ``untraced``."""
        if not self.ops:
            return []
        dev = min(self.ops)
        acc: Dict[str, int] = {}
        for gs, ge in gaps_ns([(s, e) for _, s, e in self.ops[dev]],
                              *self.window_ns):
            best, cover = "untraced", 0
            for name, s, e in self.host:
                c = min(e, ge) - max(s, gs)
                if c > cover:
                    best, cover = name, c
            acc[best] = acc.get(best, 0) + ge - gs
        return sorted(((n, t * 1e-9) for n, t in acc.items()),
                      key=lambda x: -x[1])

    def breakdown(self) -> dict:
        return {"device_ops": [[n, s] for n, s in self.device_ops()[:TOP]],
                "idle_gaps": [[n, s] for n, s in self.idle_by_host()[:TOP]]}

    def executions(self, program: str):
        """Executions of the compiled program whose name contains
        ``program``, each with the operations that ran inside it."""
        out = []
        for dev, mods in self.modules.items():
            ops = sorted(self.ops.get(dev, []), key=lambda x: x[1])
            for name, s, e in mods:
                if program in name:
                    inside = [o for o in ops if o[1] >= s and o[2] <= e]
                    out.append((name, s, e, inside))
        return out


def xplane_path(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(f"{len(found)} xplane files in {trace_dir}")
    return found[0]


def reduce(trace_dir: str) -> Reduced:
    """Read the trace under ``trace_dir`` into a :class:`Reduced`."""
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(xplane_path(trace_dir)).planes)


def _kept(plane, line) -> bool:
    """Whether a line of a plane is one the reduction reads."""
    if plane.name.startswith(DEVICE_PREFIX):
        return line.name in (OPS_LINE, MODULES_LINE)
    return True


def reduce_planes(planes) -> Reduced:
    """Reduce planes (``ProfileData.planes``, or :func:`planes_from_json`)
    to a :class:`Reduced`.  Only what falls inside the ``bench.*`` host
    spans' extent is kept."""
    ops: Dict[int, List[Interval]] = {}
    modules: Dict[int, List[Interval]] = {}
    host: List[Interval] = []
    for plane in planes:
        if plane.name.startswith(DEVICE_PREFIX):
            dev = int(plane.name[len(DEVICE_PREFIX):])
            for line in plane.lines:
                dest = {OPS_LINE: ops, MODULES_LINE: modules}.get(line.name)
                if dest is None:
                    continue
                dest[dev] = [(ev.name, ev.start_ns, ev.start_ns
                              + ev.duration_ns) for ev in line.events]
        else:
            for line in plane.lines:
                host += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                         for ev in line.events
                         if ev.name.startswith(HOST_SPAN_PREFIX)]
    if not host:
        raise ValueError("the trace holds no bench.* host span")
    lo = min(s for _, s, _ in host)
    hi = max(e for _, _, e in host)

    def clip(items):
        return [(n, int(s - lo), int(e - lo)) for n, s, e in items
                if e > lo and s < hi]

    return Reduced((0, int(hi - lo)),
                   {d: clip(v) for d, v in ops.items()},
                   {d: clip(v) for d, v in modules.items()},
                   clip(host))


def excerpt(trace_dir: str, seconds: float) -> dict:
    """The lines :func:`reduce_planes` reads, cut to ``seconds`` from the
    first ``bench.*`` span, as JSON: a recorded trace small enough to keep
    as a test fixture (read back by :func:`planes_from_json`)."""
    from jax.profiler import ProfileData

    planes = ProfileData.from_file(xplane_path(trace_dir)).planes
    planes = [(p.name, [(ln.name, [(e.name, e.start_ns, e.duration_ns)
                                   for e in ln.events])
                        for ln in p.lines if _kept(p, ln)])
              for p in planes]
    lo = min(s for _, lines in planes for _, evs in lines
             for n, s, _ in evs if n.startswith(HOST_SPAN_PREFIX))
    hi = lo + int(seconds * 1e9)
    out = []
    for name, lines in planes:
        kept = []
        for ln, evs in lines:
            if not name.startswith(DEVICE_PREFIX):
                evs = [e for e in evs if e[0].startswith(HOST_SPAN_PREFIX)]
            evs = [[n, s - lo, d] for n, s, d in evs if lo <= s and s + d <= hi]
            if evs:
                kept.append({"name": ln, "events": evs})
        if kept:
            out.append({"name": name, "lines": kept})
    return {"planes": out}


def planes_from_json(obj: dict) -> list:
    """Planes with the attributes :func:`reduce_planes` reads, from the
    JSON :func:`excerpt` writes."""
    from types import SimpleNamespace as NS

    return [NS(name=p["name"], lines=[
        NS(name=ln["name"], events=[NS(name=n, start_ns=s, duration_ns=d)
                                    for n, s, d in ln["events"]])
        for ln in p["lines"]]) for p in obj["planes"]]
