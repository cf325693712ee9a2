"""Engine spans and per-step records, on the profiler's clock.

Each phase of a ``DecodeEngine`` step runs under a span named
``engine.<phase>``. A span opens a ``jax.profiler.TraceAnnotation``, so
under a running profiler it lands on the host plane on the same clock as
the device's ops, and adds its ``time.perf_counter`` duration to the
step's :class:`StepRecord`. The phases are children of one
``engine.step`` span, whose trace arguments name the program the step ran
(``kind``) and the requests it admitted and finished.

| Span | Covers |
|---|---|
| ``engine.step`` | the whole step |
| ``engine.admit`` | admission: slot resets, per-slot keys, paged admission |
| ``engine.plan`` | the step plan, paged page preparation and its flush |
| ``engine.inputs`` | the host input arrays of a program |
| ``engine.dispatch`` | the call of a jitted step program, until it returns |
| ``engine.sync`` | the transfer of the sampled tokens to the host |
| ``engine.advance`` | acceptance, scheduler advance, paged bookkeeping |

Nothing here reads a device value. With no profiler running a span costs
a few microseconds, so the spans are always on. Records are kept in a
bounded deque (:data:`MAX_RECORDS` steps) together with the speculation
totals; ``DecodeEngine.spec_stats()`` and ``kv_stats()`` read them.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Deque, Dict, Optional, Tuple

import jax

PREFIX = "engine."
PHASES = ("admit", "plan", "inputs", "dispatch", "sync", "advance")
MAX_RECORDS = 65536


@dataclasses.dataclass
class StepRecord:
    """One engine step. Times are ``time.perf_counter`` seconds; positions
    count KV entries summed over the batch slots."""
    t0: float
    t1: float = 0.0
    kind: str = "idle"              # prefill, decode, spec or idle
    phase_s: Dict[str, float] = dataclasses.field(default_factory=dict)
    fed: int = 0                    # tokens the programs took as input
    rows: int = 0                   # rows the programs computed
    admitted: Tuple[int, ...] = ()  # request ids admitted in this step
    finished: Tuple[int, ...] = ()  # request ids finished in this step
    live_kv: int = 0                # positions the step's program attends
    reserved_kv: int = 0            # positions the cache holds room for

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


def _ids(ids) -> str:
    # Trace arguments are comma-separated, so ids are joined by spaces.
    return " ".join(map(str, ids))


class _Span:
    """A phase span: one ``TraceAnnotation`` and one perf_counter
    duration, added to the current step's ``phase_s[name]``."""
    __slots__ = ("rec", "name", "ann", "t")

    def __init__(self, rec: StepRecord, name: str):
        self.rec, self.name = rec, name

    def __enter__(self):
        self.ann = jax.profiler.TraceAnnotation(PREFIX + self.name)
        self.ann.__enter__()
        self.t = time.perf_counter()

    def __exit__(self, *exc):
        dt = time.perf_counter() - self.t
        self.ann.__exit__(*exc)
        self.rec.phase_s[self.name] = self.rec.phase_s.get(self.name, 0.0) \
            + dt


class _StepSpan:
    """The ``engine.step`` span; its record is kept once the step returns
    normally."""
    __slots__ = ("stats", "rec", "ann")

    def __init__(self, stats: "EngineStats"):
        self.stats = stats

    def __enter__(self) -> StepRecord:
        self.ann = jax.profiler.TraceAnnotation(PREFIX + "step")
        self.ann.__enter__()
        self.rec = StepRecord(time.perf_counter())
        self.stats.current = self.rec
        return self.rec

    def __exit__(self, exc_type, *rest):
        rec = self.rec
        rec.t1 = time.perf_counter()
        self.ann.set_metadata(kind=rec.kind, admitted=_ids(rec.admitted),
                              finished=_ids(rec.finished))
        self.ann.__exit__(exc_type, *rest)
        self.stats.current = None
        if exc_type is None:
            self.stats.steps.append(rec)


class EngineStats:
    """The engine's counters: one :class:`StepRecord` per step and the
    speculation totals (rounds drafted, draft tokens proposed and
    accepted)."""

    def __init__(self):
        self.steps: Deque[StepRecord] = collections.deque(maxlen=MAX_RECORDS)
        self.current: Optional[StepRecord] = None
        self.spec_rounds = 0
        self.spec_drafted = 0
        self.spec_accepted = 0

    def step(self) -> _StepSpan:
        """``with stats.step() as rec:`` around one engine step."""
        return _StepSpan(self)

    def phase(self, name: str) -> _Span:
        """``with stats.phase(name):`` around one phase of the current
        step (a phase may run several times in one step; its seconds
        add up)."""
        return _Span(self.current, name)
