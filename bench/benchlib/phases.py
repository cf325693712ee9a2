"""What the program's own engine spans and step records say about a run.

The serve engine (``repro/serve/spans.py``) runs each phase of a step
under a host span named ``engine.<phase>`` (children of ``engine.step``)
and keeps a record of every step: its kind, the seconds of each phase,
the tokens fed against the rows computed, and the KV positions in use
against those reserved. This module reads both:

* ``idle_phases(run)``: each stretch of the traced window in which no op
  ran on a device, split over the innermost ``engine.*`` span above it
  (``engine.step`` itself where the step was in no phase), and over
  ``outside_engine`` where no engine span was open. It reads the run's
  ``.xplane.pb`` with the helpers of ``trace.py`` and takes the window
  from ``trace.py``'s reduction of the same trace (host step spans
  widened by ``SLACK_NS``), so its parts add up to that reduction's idle
  time. The result is cached per trace.
* ``engine_steps(run)``: the engine's step records that ran inside the
  run's window.

A program without the spans or the records gives neither; the readers
then return None.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Tuple

from . import trace

PREFIX = "engine."
STEP = "engine.step"
SYNC = "engine.sync"
HOST_PHASES = ("engine.admit", "engine.plan", "engine.inputs",
               "engine.dispatch", "engine.advance")
OUTSIDE = "outside_engine"

Span = Tuple[str, float, float]     # (name, start ns, end ns)


@dataclasses.dataclass
class Phases:
    idle: Dict[str, float]          # idle ns by label, averaged over devices
    steps: int                      # engine.step spans inside the window
    devices: int

    def per_step_ms(self, labels: Iterable[str]) -> Optional[float]:
        """Idle ms under ``labels`` per engine step of the window; None
        where the trace holds no engine step or no device."""
        if not self.steps or not self.devices:
            return None
        return sum(self.idle.get(k, 0.0) for k in labels) / self.steps / 1e6


def load_planes(path: Path):
    """Planes of an ``.xplane.pb`` (a file or a directory above it) in the
    form ``trace.reduce_events`` takes, holding the devices' ops and the
    host's ``engine.*`` spans."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(trace._find(Path(path))))
    out = []
    for plane in pd.planes:
        dev = bool(trace.DEVICE_PLANE.match(plane.name))
        if not (dev or plane.name.startswith("/host:")):
            continue
        lines = []
        for line in plane.lines:
            if dev and line.name != trace.OPS_LINE:
                continue
            lines.append((line.name, [
                (e.name, float(e.start_ns), float(e.duration_ns))
                for e in line.events
                if dev or e.name.startswith(PREFIX)]))
        out.append((plane.name, lines))
    return out


def events(planes):
    """(device op intervals by device plane, ``engine.*`` host spans)."""
    ops: Dict[str, List[Tuple[float, float]]] = {}
    spans: List[Span] = []
    for pname, lines in planes:
        for lname, evs in lines:
            if trace.DEVICE_PLANE.match(pname):
                if lname == trace.OPS_LINE:
                    ops.setdefault(pname, []).extend(
                        (s, s + d) for _, s, d in evs)
            elif pname.startswith("/host:"):
                spans.extend((n, s, s + d) for n, s, d in evs
                             if n.startswith(PREFIX))
    return ops, spans


def pieces(spans: List[Span]) -> List[Span]:
    """The time under at least one span, cut where any span starts or
    ends, each piece labelled with its innermost span: of the spans open
    over it, the one that started last (the shorter one on a tie)."""
    bounds = sorted({t for _, a, b in spans for t in (a, b)})
    by_start = sorted(spans, key=lambda s: s[1])
    out: List[Span] = []
    open_: List[Span] = []
    j = 0
    for a, b in zip(bounds, bounds[1:]):
        while j < len(by_start) and by_start[j][1] <= a:
            open_.append(by_start[j])
            j += 1
        open_ = [s for s in open_ if s[2] > a]
        if open_:
            out.append((max(open_, key=lambda s: (s[1], -s[2]))[0], a, b))
    return out


def reduce_events(planes, lo: float, hi: float) -> Phases:
    """Split the idle time of [lo, hi) on each device of ``planes`` over
    the innermost ``engine.*`` span."""
    ops, spans = events(planes)
    cut = pieces(spans)
    starts = [a for _, a, _ in cut]
    idle: Dict[str, float] = collections.Counter()
    for intervals in ops.values():
        merged = trace.union(intervals, lo, hi)
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b <= a:
                continue
            covered = 0.0
            i = max(bisect.bisect_right(starts, a) - 1, 0)
            while i < len(cut) and cut[i][1] < b:
                name, s0, s1 = cut[i]
                ov = min(b, s1) - max(a, s0)
                if ov > 0:
                    idle[name] += ov
                    covered += ov
                i += 1
            if b - a - covered > 0:
                idle[OUTSIDE] += b - a - covered
    n = max(len(ops), 1)
    steps = sum(1 for name, a, b in spans
                if name == STEP and lo <= a and b <= hi)
    return Phases({k: v / n for k, v in idle.items()}, steps, len(ops))


_CACHE: Dict[Tuple[str, float, float], Phases] = {}


def reduce(path: Path, lo: float, hi: float) -> Phases:
    key = (str(path), lo, hi)
    if key not in _CACHE:
        _CACHE[key] = reduce_events(load_planes(path), lo, hi)
    return _CACHE[key]


def idle_phases(run) -> Optional[Phases]:
    """The idle split of the run's traced window; None without a trace."""
    tracer = getattr(run.session, "tracer", None)
    if run.trace is None or tracer is None:
        return None
    return reduce(tracer.dir, run.trace.lo, run.trace.hi)


def engine_steps(run, traced: Optional[bool] = None) -> List:
    """The engine's own step records that ran inside the window (only
    those inside, or outside, a session step the profiler covered, when
    ``traced`` says so); [] for a program that keeps no records."""
    eng = getattr(run.session, "eng", None)
    records = getattr(getattr(eng, "stats", None), "steps", None)
    if records is None:
        return []
    out = [r for r in records
           if run.t_start <= r.t0 and r.t1 <= run.t_end]
    if traced is not None:
        cover = sorted((s.t0, s.t1) for s in run.session.steps if s.traced)
        starts = [a for a, _ in cover]

        def covered(r) -> bool:
            i = bisect.bisect_right(starts, r.t0) - 1
            return i >= 0 and r.t1 <= cover[i][1]
        out = [r for r in out if covered(r) == traced]
    return out
