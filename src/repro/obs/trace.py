"""Thread-safe tracing core for ``repro.obs``.

One ``Tracer`` owns one monotonic clock domain (``time.perf_counter``
anchored at construction), so spans recorded from the executor driver
thread, the serve loop, and the ``train_while_serve`` background
thread all land on a single comparable timeline. Producers record:

* ``span(name, **attrs)`` — a context manager for a timed region, or
  the manual ``add_span(name, t0, t1=None, **attrs)`` when the region
  does not nest lexically (recorded after the fact).
* ``event(name, **attrs)`` — an instantaneous marker (prefetch hit,
  retry, ...).
* ``counter(name, value)`` — an accumulating scalar (checkpoint /
  restore / recovery seconds, folding the executor's scattered
  resilience timers onto the tracer).

Every lexical ``span`` — on a ``Tracer`` and on the disabled ``NOOP``
alike — is also a ``jax.profiler.TraceAnnotation`` for its extent, so
it shows on the host thread's line of any profiler trace
(``jax.profiler.trace(dir)``, the benchmark's ``--trace 1``) on the
device trace's own clock: the device's idle gaps can be attributed to
the span the host was in without offset arithmetic. ``annotate(name,
**attrs)`` opens the annotation alone, for sub-task regions that must
not become records (``task:wait``, ``task:handoff``: the recorded
``task:*`` spans are DAG tasks, which ``obs.analyze`` and the
executor's records read). ``add_span`` runs after the region has
ended, so its spans never reach the profiler: the serve loop, the
checkpoint writer, the autotuner and the executor's closing ``run``
span stay in the in-memory trace only.

Attributes are Python ints and strings: a span never reads a device
value or blocks, since that would add a sync the untraced run does not
have. ``jax.profiler`` is imported on the first span, not here.

The default tracer is the module-level ``NOOP`` singleton: every hot
path in the repo calls through it unconditionally. It records nothing;
its spans cost one profiler annotation (about a microsecond with no
profiler session running) and its other methods are constant-time
attribute hits, so an untraced run pays little (the ``<2%`` overhead
gate in ``benchmarks/trace.py`` measures exactly this). Producers that
would do real work just to *build* a record (formatting attrs,
snapshotting queue depths) must guard on ``tracer.enabled`` first.

``block_tasks`` is the JAX-async knob: with it (the default) the
executor calls ``jax.block_until_ready`` before closing each task
span, so span durations are real device time and the analyzer's
critical path is meaningful — at the cost of serializing per-task
overlap (an observer effect). With ``block_tasks=False`` spans measure
dispatch only; ``benchmarks/trace.py`` therefore uses a two-run
protocol (traced+blocked run for the timeline, untraced warm run for
the makespan) mirroring ``benchmarks/pff_exec.py``.

This module imports nothing from the rest of the repo (and no jax at
import time), so ``checkpoint.py`` and every ``core``/``serve`` module
can depend on it without import cycles.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import threading
import time
from typing import Any, Dict, List, Optional


@functools.cache
def _trace_annotation():
    from jax.profiler import TraceAnnotation
    return TraceAnnotation


def annotate(name: str, **attrs):
    """A profiler annotation over a ``with`` block, recorded by no
    tracer: the span shows in a profiler trace only."""
    return _trace_annotation()(name, **attrs)


@dataclasses.dataclass
class Span:
    """A closed timed region on the tracer's clock (seconds since t0)."""
    name: str
    t0: float
    t1: float
    thread: str
    attrs: Dict[str, Any]

    @property
    def duration(self) -> float:
        return self.t1 - self.t0


@dataclasses.dataclass
class Event:
    """An instantaneous marker on the tracer's clock."""
    name: str
    t: float
    thread: str
    attrs: Dict[str, Any]


class Tracer:
    """Collects spans/events/counters on one shared monotonic clock.

    Thread-safe: ``add_span``/``event``/``counter`` may be called
    concurrently from any thread; each record carries the recording
    thread's name (the Chrome exporter maps it to ``tid``).
    """

    enabled = True

    def __init__(self, *, block_tasks: bool = True,
                 meta: Optional[Dict[str, Any]] = None):
        self.block_tasks = block_tasks
        self.meta: Dict[str, Any] = dict(meta or {})
        self.spans: List[Span] = []
        self.events: List[Event] = []
        self.counters: Dict[str, float] = {}
        self._t0 = time.perf_counter()
        self._lock = threading.Lock()

    # -- clock ------------------------------------------------------------
    def now(self) -> float:
        """Seconds since this tracer was created (monotonic)."""
        return time.perf_counter() - self._t0

    # -- recording --------------------------------------------------------
    def add_span(self, name: str, t0: float, t1: Optional[float] = None,
                 **attrs) -> Span:
        """Record a region [t0, t1] (both in ``now()`` time; t1 defaults
        to the current instant)."""
        if t1 is None:
            t1 = self.now()
        sp = Span(name, t0, t1, threading.current_thread().name, attrs)
        with self._lock:
            self.spans.append(sp)
        return sp

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        """Record the ``with`` block as a span, also annotated in any
        running profiler session. Yields the span's attrs: the block
        may add to them before the span closes (the annotation carries
        only those given here)."""
        with annotate(name, **attrs):
            t0 = self.now()
            try:
                yield attrs
            finally:
                self.add_span(name, t0, **attrs)

    def event(self, name: str, **attrs) -> Event:
        ev = Event(name, self.now(), threading.current_thread().name, attrs)
        with self._lock:
            self.events.append(ev)
        return ev

    def counter(self, name: str, value: float = 1.0) -> None:
        """Accumulate ``value`` onto the named counter."""
        with self._lock:
            self.counters[name] = self.counters.get(name, 0.0) + value

    # -- reading ----------------------------------------------------------
    def span_count(self) -> int:
        with self._lock:
            return len(self.spans)

    def snapshot(self, *, start: int = 0) -> List[Span]:
        """A consistent copy of ``spans[start:]`` (appends-only list, so
        the slice is the spans recorded since ``span_count()`` returned
        ``start``)."""
        with self._lock:
            return list(self.spans[start:])

    def to_dict(self) -> Dict[str, Any]:
        """Plain-data form consumed by the exporters and the analyzer."""
        with self._lock:
            return {
                "meta": dict(self.meta),
                "spans": [dataclasses.asdict(s) for s in self.spans],
                "events": [dataclasses.asdict(e) for e in self.events],
                "counters": dict(self.counters),
            }


class _NoopTracer:
    """Shared disabled tracer: the default.

    Records nothing; ``span()`` is a profiler annotation alone and
    every other method is a cheap constant.
    """

    enabled = False
    block_tasks = False
    meta: Dict[str, Any] = {}
    spans: List[Span] = []
    events: List[Event] = []
    counters: Dict[str, float] = {}

    def now(self) -> float:
        return 0.0

    def add_span(self, name, t0, t1=None, **attrs):
        return None

    @contextlib.contextmanager
    def span(self, name, **attrs):
        with annotate(name, **attrs):
            yield attrs

    def event(self, name, **attrs):
        return None

    def counter(self, name, value=1.0):
        return None

    def span_count(self) -> int:
        return 0

    def snapshot(self, *, start: int = 0):
        return []

    def to_dict(self):
        return {"meta": {}, "spans": [], "events": [], "counters": {}}


NOOP = _NoopTracer()


def as_tracer(trace) -> "Tracer | _NoopTracer":
    """Normalize an ``api``-level ``trace=`` argument.

    ``None``/``False`` -> ``NOOP``; ``True`` -> a fresh ``Tracer()``;
    an existing tracer object passes through (anything with ``enabled``
    and ``add_span`` duck-types).
    """
    if trace is None or trace is False:
        return NOOP
    if trace is True:
        return Tracer()
    return trace
