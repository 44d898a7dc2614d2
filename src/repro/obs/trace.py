"""Thread-aware span tracer: the single timing substrate for the repo.

Every host-side duration the repo reports — pipeline phase times, trainer
step walls, layer-wise inference times, serving gather/compute splits —
is derived from the spans recorded here, so there is exactly one timing
source of truth (``time.perf_counter``, a monotonic clock) instead of
ad-hoc ``perf_counter()`` pairs scattered per module.

Design constraints, in order:

  * **Disabled means untouched.** The module-level singleton starts
    disabled; every record method is a single attribute check away from a
    no-op, its spans and phase clocks are plain timers that record and
    annotate nothing, and the :class:`PhaseClock` used on the pipeline
    hot path takes exactly as many ``perf_counter()`` readings as the
    inline timestamps it replaced. The "four phases sum exactly to the step
    wall" invariant and the overlapped==serial bitwise tests hold with
    tracing on or off because the *timestamps themselves* are what feed
    ``StepMetrics`` — the spans are the same numbers, not a second clock.
  * **Thread-aware.** Spans capture the recording thread's name/ident at
    record time; the exporter lays producer, sampler-pool workers and the
    consumer out on separate tracks. A ``track=`` override places events
    on a logical track instead (e.g. per-worker serving queues), and
    ``clock="model"`` marks virtual-time spans from the serving simulator
    so they export under their own process and never mix timelines with
    wall-clock spans.
  * **Bounded.** Events land in ring buffers (``deque(maxlen=...)``), so
    a long traced run degrades to "most recent N events" instead of
    unbounded memory. Counter *totals* are kept separately and never
    truncate — reconciliation sums stay exact even if the event ring
    wrapped.

Byte accounting rides the same tracer: cumulative counters (``add``),
gauges (``gauge``) and trace-time collective records (``collective``, fed
by the sync strategies while jax traces the step function) are what
``obs.reconcile`` holds against the analytic cost model.

On the profiler's clock. While an enabled tracer is installed, every live
span (``Tracer.span``, each phase of a ``PhaseClock``) also opens a
``jax.profiler.TraceAnnotation`` named ``repro/<span name>``, so a JAX
profile shows the program's own phases beside the device's ops; spans
recorded from explicit timestamps (``record_span``) stay in the ring
buffer only. The installed tracer also counts JAX's tracing, lowering and
backend compiles (``jit.compile_s``, ``jit.compiles``; persistent-cache
loads, which a backend compile includes, in ``jit.cache_load_s``) and
records each garbage collection as a ``host.gc`` span.
"""

from __future__ import annotations

import collections
import dataclasses
import gc
import threading
import time
from contextlib import contextmanager
from typing import Dict, Iterator, List, Optional, Tuple

import jax

__all__ = [
    "SpanEvent", "CounterEvent", "CollectiveEvent", "Span", "PhaseClock",
    "Tracer", "get_tracer", "install", "uninstall", "tracing",
]

_DEFAULT_CAPACITY = 1 << 16

#: prefix of the program's spans on the JAX profiler's timeline
ANNOTATION_PREFIX = "repro/"

# JAX's compile events, each reported with its start and end as it closes
_JIT_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
               "/jax/core/compile/jaxpr_to_mlir_module_duration",
               "/jax/core/compile/backend_compile_duration")
_BACKEND_COMPILE = _JIT_EVENTS[2]
_CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"


@dataclasses.dataclass(frozen=True)
class SpanEvent:
    """One closed span: ``[t0, t1]`` on the recording thread's track."""

    name: str
    cat: str
    t0: float
    t1: float
    tid: int
    thread: str
    track: Optional[str] = None
    clock: str = "wall"          # "wall" (perf_counter) | "model" (sim time)
    args: Optional[dict] = None

    @property
    def duration(self) -> float:
        return self.t1 - self.t0


@dataclasses.dataclass(frozen=True)
class CounterEvent:
    """A counter sample: cumulative (``add``) or instantaneous (``gauge``)."""

    name: str
    t: float
    value: float
    track: Optional[str] = None
    clock: str = "wall"


@dataclasses.dataclass(frozen=True)
class CollectiveEvent:
    """One collective op recorded at jax trace time by a sync strategy.

    ``cluster_bytes`` follows the compiled-HLO output-shape convention the
    static gate's ``collective_budget`` uses (per-device output nbytes x
    k); ``wire_bytes`` follows the transport convention of
    ``sync_wire_bytes_per_round`` (k x per-device encoded payload+meta).
    ``wire_bytes`` is ``None`` where the transport formula intentionally
    diverges from what the op moves (DenseSync reduces *decoded* fp32).
    """

    kind: str
    cluster_bytes: int
    wire_bytes: Optional[int] = None
    layer: int = 0
    program: str = "sync"


def _annotation(name: str):
    """Open a profiler annotation for the span ``name``; the caller closes
    it with ``__exit__``."""
    ann = jax.profiler.TraceAnnotation(ANNOTATION_PREFIX + name)
    ann.__enter__()
    return ann


class Span:
    """Context-manager span. Always measures (``duration`` is consumed by
    the call sites even when tracing is off); a disabled tracer hands out
    this plain form, which records nothing."""

    __slots__ = ("_tracer", "name", "cat", "track", "args", "t0", "t1")

    def __init__(self, tracer: "Tracer", name: str, cat: str,
                 track: Optional[str], args: Optional[dict]):
        self._tracer = tracer
        self.name = name
        self.cat = cat
        self.track = track
        self.args = args
        self.t0 = 0.0
        self.t1 = 0.0

    def __enter__(self) -> "Span":
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = time.perf_counter()

    @property
    def duration(self) -> float:
        return self.t1 - self.t0


class _LiveSpan(Span):
    """The enabled tracer's span: also a profiler annotation, and recorded
    when it closes."""

    __slots__ = ("_ann",)

    def __enter__(self) -> "Span":
        self._ann = _annotation(self.name)
        return super().__enter__()

    def __exit__(self, *exc) -> None:
        super().__exit__()
        self._ann.__exit__(None, None, None)
        self._tracer.record_span(self.name, self.t0, self.t1, cat=self.cat,
                                 track=self.track, args=self.args)


class PhaseClock:
    """Contiguous phase timer: each ``split`` closes the current phase at
    the exact instant the next one opens, so phase durations sum to the
    wall *bitwise* (the same ``perf_counter`` reading ends one span and
    starts the next — no gap, no overlap, and exactly one clock reading
    per boundary, matching the inline ``t0..t3`` code it replaced).

    A phase is named when it closes; the profiler's annotation needs its
    name when it opens, so ``first`` (at ``Tracer.phase_clock``) and each
    split's ``then`` name the phase that opens. A disabled tracer hands
    out this plain form, which records nothing."""

    __slots__ = ("_tracer", "cat", "track", "args", "_t")

    def __init__(self, tracer: "Tracer", cat: str, track: Optional[str],
                 args: Optional[dict]):
        self._tracer = tracer
        self.cat = cat
        self.track = track
        self.args = args
        self._t = time.perf_counter()

    def split(self, name: str, then: Optional[str] = None) -> float:
        """Close the running phase as ``name``; return its duration.
        ``then`` names the phase that opens, for the profiler's timeline."""
        t0, t1 = self._t, time.perf_counter()
        self._t = t1
        return t1 - t0


class _LivePhaseClock(PhaseClock):
    """The enabled tracer's phase clock: records each phase, and keeps one
    profiler annotation open for the running phase."""

    __slots__ = ("_ann",)

    def __init__(self, tracer: "Tracer", cat: str, track: Optional[str],
                 args: Optional[dict], first: Optional[str]):
        self._ann = _annotation(first) if first else None
        super().__init__(tracer, cat, track, args)

    def split(self, name: str, then: Optional[str] = None) -> float:
        t0 = self._t
        duration = super().split(name)
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        self._ann = _annotation(then) if then else None
        self._tracer.record_span(name, t0, self._t, cat=self.cat,
                                 track=self.track, args=self.args)
        return duration


class Tracer:
    """Ring-buffered event sink. Thread-safe: spans/counters append from
    the producer thread, sampler pool and consumer concurrently (deque
    appends are atomic under the GIL; totals take a small lock)."""

    def __init__(self, enabled: bool = True,
                 capacity: int = _DEFAULT_CAPACITY):
        self.enabled = enabled
        self._spans: collections.deque = collections.deque(maxlen=capacity)
        self._counters: collections.deque = collections.deque(maxlen=capacity)
        self._collectives: collections.deque = collections.deque(
            maxlen=capacity)
        self._totals: Dict[str, float] = {}
        self._lock = threading.Lock()
        self._jit_spans: List[List[float]] = []   # merged compile intervals
        self._gc: Optional[tuple] = None          # (annotation, t0) of a gc

    # -- recording ----------------------------------------------------------

    def span(self, name: str, *, cat: str = "span",
             track: Optional[str] = None,
             args: Optional[dict] = None) -> Span:
        if self.enabled:
            return _LiveSpan(self, name, cat, track, args)
        return Span(self, name, cat, track, args)

    def phase_clock(self, *, cat: str = "phase",
                    track: Optional[str] = None,
                    args: Optional[dict] = None,
                    first: Optional[str] = None) -> PhaseClock:
        """A clock whose first phase opens now; ``first`` names it for the
        profiler's timeline."""
        if self.enabled:
            return _LivePhaseClock(self, cat, track, args, first)
        return PhaseClock(self, cat, track, args)

    def record_span(self, name: str, t0: float, t1: float, *,
                    cat: str = "span", track: Optional[str] = None,
                    clock: str = "wall", args: Optional[dict] = None) -> None:
        """Record a span from explicit timestamps (the migration path for
        call sites that already hold ``perf_counter`` readings, and the
        only path for virtual-time spans, which pass ``clock='model'``)."""
        if not self.enabled:
            return
        th = threading.current_thread()
        self._spans.append(SpanEvent(
            name=name, cat=cat, t0=t0, t1=t1, tid=th.ident or 0,
            thread=th.name, track=track, clock=clock, args=args))

    def add(self, name: str, delta: float, *, track: Optional[str] = None,
            t: Optional[float] = None, clock: str = "wall") -> None:
        """Cumulative counter (e.g. wire bytes): records the running total
        so the exported track is monotone and ``total(name)`` is exact."""
        if not self.enabled:
            return
        with self._lock:
            value = self._totals.get(name, 0.0) + delta
            self._totals[name] = value
        self._counters.append(CounterEvent(
            name=name, t=time.perf_counter() if t is None else t,
            value=value, track=track, clock=clock))

    def gauge(self, name: str, value: float, *, track: Optional[str] = None,
              t: Optional[float] = None, clock: str = "wall") -> None:
        """Instantaneous counter (e.g. queue depth, cache hit rate)."""
        if not self.enabled:
            return
        self._counters.append(CounterEvent(
            name=name, t=time.perf_counter() if t is None else t,
            value=float(value), track=track, clock=clock))

    def collective(self, kind: str, cluster_bytes: int, *,
                   wire_bytes: Optional[int] = None, layer: int = 0,
                   program: str = "sync") -> None:
        """Record one collective op (called by sync strategies at jax
        trace time, where shapes/dtypes are static even under vmap)."""
        if not self.enabled:
            return
        self._collectives.append(CollectiveEvent(
            kind=kind, cluster_bytes=int(cluster_bytes),
            wire_bytes=None if wire_bytes is None else int(wire_bytes),
            layer=layer, program=program))

    def _jit(self, event: str, start: float, end: float) -> None:
        """One JAX compile event. A jit traced inside another reports its
        own event inside the outer one's, so only the seconds the union of
        the events grows by count."""
        with self._lock:
            grown, lo, hi, keep = end - start, start, end, []
            for a, b in self._jit_spans:
                if b < start or a > end:
                    keep.append([a, b])
                else:
                    grown -= min(b, end) - max(a, start)
                    lo, hi = min(lo, a), max(hi, b)
            keep.append([lo, hi])
            self._jit_spans = keep
        self.add("jit.compile_s", grown)
        if event == _BACKEND_COMPILE:
            self.add("jit.compiles", 1)

    def _on_gc(self, phase: str, info: dict) -> None:
        """`gc.callbacks` hook: each collection as a ``host.gc`` span."""
        if phase == "start":
            self._gc = (_annotation("host.gc"), time.perf_counter())
        elif self._gc is not None:
            ann, t0 = self._gc
            self._gc = None
            t1 = time.perf_counter()
            ann.__exit__(None, None, None)
            self.record_span("host.gc", t0, t1, cat="gc",
                             args={"generation": info["generation"]})

    # -- reading ------------------------------------------------------------

    def spans(self, name: Optional[str] = None) -> List[SpanEvent]:
        evs = list(self._spans)
        return evs if name is None else [e for e in evs if e.name == name]

    def counters(self, name: Optional[str] = None) -> List[CounterEvent]:
        evs = list(self._counters)
        return evs if name is None else [e for e in evs if e.name == name]

    def collectives(self, program: Optional[str] = None
                    ) -> List[CollectiveEvent]:
        evs = list(self._collectives)
        if program is None:
            return evs
        return [e for e in evs if e.program == program]

    def total(self, name: str) -> Optional[float]:
        """Exact cumulative total for an ``add`` counter (``None`` if the
        counter never fired — distinguishes "measured zero" from "not
        instrumented / tracing was off")."""
        with self._lock:
            return self._totals.get(name)

    def totals(self) -> Dict[str, float]:
        with self._lock:
            return dict(self._totals)

    def __len__(self) -> int:
        return len(self._spans) + len(self._counters) + len(self._collectives)

    def clear(self) -> None:
        self._spans.clear()
        self._counters.clear()
        self._collectives.clear()
        with self._lock:
            self._totals.clear()
            self._jit_spans.clear()


# -- module-level singleton -------------------------------------------------

_NULL = Tracer(enabled=False, capacity=1)
_current: Tracer = _NULL


def get_tracer() -> Tracer:
    """The installed tracer (the disabled no-op singleton by default)."""
    return _current


def install(tracer: Tracer) -> Tracer:
    """Install ``tracer`` as the process-wide sink; returns it. An enabled
    tracer also hooks the garbage collector while it is installed."""
    global _current
    if _current._on_gc in gc.callbacks:
        gc.callbacks.remove(_current._on_gc)
    _current = tracer
    if tracer.enabled:
        gc.callbacks.append(tracer._on_gc)
    return tracer


def uninstall() -> None:
    """Restore the disabled no-op singleton."""
    install(_NULL)


@contextmanager
def tracing(capacity: int = _DEFAULT_CAPACITY) -> Iterator[Tracer]:
    """Install a fresh enabled tracer for the block; restore on exit."""
    prev = _current
    tr = install(Tracer(enabled=True, capacity=capacity))
    try:
        yield tr
    finally:
        install(prev)


def _on_jit_event(event: str, start: float, end: float, **_kw) -> None:
    tr = _current
    if tr.enabled and event in _JIT_EVENTS:
        tr._jit(event, start, end)


def _on_duration_event(event: str, seconds: float, **_kw) -> None:
    tr = _current
    if tr.enabled and event == _CACHE_LOAD:
        tr.add("jit.cache_load_s", seconds)


jax.monitoring.register_event_time_span_listener(_on_jit_event)
jax.monitoring.register_event_duration_secs_listener(_on_duration_event)
