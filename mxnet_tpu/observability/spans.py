"""Spans — one instrumentation point, one record, three readers.

A span is a named timed region (context manager or decorator). With
telemetry on it

- holds a ``jax.profiler.TraceAnnotation`` of its own name while it is open,
  so every xplane anyone records (``jax.profiler.start_trace``,
  ``mx.profiler`` with ``xla_trace_dir``) shows the program's spans in
  ``/host:CPU`` on the device planes' clock, and on exit
- appends one :class:`Record` to a process-wide bounded ring
  (:func:`records`): name, ``perf_counter`` start and end, thread, the span
  that was open around it on that thread, and the ``unit`` of work it
  belongs to (``("step", n)``, ``("batch", m)``; inherited from the
  enclosing span when not given), and
- observes its duration into the ``mxtpu_span_ms`` histogram (labeled by
  span name, plus any user labels).

When a :mod:`mxnet_tpu.profiler` session is recording it also emits a
chrome-trace event there. The flight recorder reads the thread's
active-span stack to note what was in flight at each step record (and
therefore at crash time).

Both gates (telemetry switch, profiler session) are evaluated at ``__enter__``
time, so a span created at import/decoration time tracks runtime toggles; a
fully-disabled span does nothing but two boolean checks.
"""
from __future__ import annotations

import functools
import threading
import time
from collections import deque
from typing import List, NamedTuple, Optional, Tuple

from jax.profiler import StepTraceAnnotation, TraceAnnotation

from . import metrics as _metrics

__all__ = ["span", "active_spans", "record", "records", "dropped", "Record",
           "SPAN_MS"]

SPAN_MS = _metrics.histogram(
    "mxtpu_span_ms", "Duration of instrumented spans, by span name.")

# The ring has to hold one whole benchmark run (set-up, the 20 s window, the
# 3 s traced tail, and the reference's compiles after them) at the shortest
# step of any cell PERF.md section 7.3 lists. Measured on the chip (cold
# traced runs, with the set-up spans: every parameter's gluon.set_data, the
# imports, and a jit.cache_load for each executable loaded): set-up leaves
# 3,900-5,740 records, most of them the jit.trace of every inner jit of the
# capture and of the step; a whole run leaves 15,348 in resnet50_v1.train_fed
# (the most), 14,339 in resnet50_v1.train and 8,006 in zaya1_8b.train, a
# step 4 records and a fed batch 4 more. At ~25 ms a step (ResNet-50 at
# batch 64) a run would leave ~6,000 + 920 x 8 + 8,000 = 21,400: the ring
# holds it 1.5 times, and the fullest run of today 2.1 times. dropped() says
# when it did not. At ~250 bytes a record it is 8 MB when full.
RING_RECORDS = 32768


class Record(NamedTuple):
    name: str
    t0: float                      # time.perf_counter seconds
    t1: float
    thread: int                    # threading.get_ident()
    parent: Optional[str]          # the span open around it on that thread
    unit: Optional[Tuple[str, int]]


_ring: deque = deque(maxlen=RING_RECORDS)
_ring_lock = threading.Lock()
_dropped = 0
_tls = threading.local()


def _stack() -> list:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


def active_spans() -> Tuple[str, ...]:
    """Names of spans currently open on THIS thread, outermost first."""
    return tuple(s.name for s in _stack())


def record(name: str, t0: float, t1: float, unit=None) -> None:
    """Append a finished region that was timed elsewhere (``jit_hooks``'s
    trace/compile events) under the span open on this thread."""
    global _dropped
    st = _stack()
    parent = st[-1] if st else None
    if unit is None and parent is not None:
        unit = parent.unit
    with _ring_lock:
        if len(_ring) == _ring.maxlen:
            _dropped += 1
        _ring.append(Record(name, t0, t1, threading.get_ident(),
                            parent.name if parent is not None else None, unit))


def records() -> List[Record]:
    """A copy of the ring, oldest first."""
    with _ring_lock:
        return list(_ring)


def dropped() -> int:
    """How many records the full ring has pushed out since the process
    started: a reader of the whole run sees all of it only while this is 0."""
    return _dropped


def _profiler_recording() -> bool:
    try:
        from .. import profiler
        return profiler.recording()
    except Exception:
        return False


class span:
    """Timed region: ``with span("kv_publish", key=k): ...`` or
    ``@span("evaluate")`` on a function (a fresh region per call).
    ``unit=("step", n)`` names the unit of work the span and the spans inside
    it belong to; a span that brings a ``"step"`` unit is a step root
    (``StepTraceAnnotation``). After exit ``t0``/``t1``/``ms`` hold its
    times, or None when telemetry was off."""

    __slots__ = ("name", "category", "labels", "unit", "t0", "t1", "_given",
                 "_us0", "_tel", "_prof", "_ann")

    def __init__(self, name: str, category: str = "span", unit=None,
                 **labels):
        self.name = name
        self.category = category
        self.labels = labels
        self.unit = self._given = unit
        self.t0 = self.t1 = None

    @property
    def ms(self) -> Optional[float]:
        return None if self.t1 is None else (self.t1 - self.t0) * 1000.0

    def __enter__(self):
        self._tel = _metrics.enabled()
        self._prof = _profiler_recording()
        if self._tel or self._prof:
            st = _stack()
            if self._given is None and st:
                self.unit = st[-1].unit
            st.append(self)
            if self._tel:
                if self._given is not None and self._given[0] == "step":
                    self._ann = StepTraceAnnotation(self.name,
                                                    step_num=self._given[1])
                else:
                    self._ann = TraceAnnotation(self.name)
                self._ann.__enter__()
            if self._prof:
                from .. import profiler
                self._us0 = profiler._prof.us()
            self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if not (self._tel or self._prof):
            return False
        t1 = time.perf_counter()
        dt = t1 - self.t0
        st = _stack()
        if st and st[-1] is self:
            st.pop()
        if self._tel:
            self.t1 = t1
            self._ann.__exit__(*exc)
            record(self.name, self.t0, t1, self.unit)
            SPAN_MS.observe(dt * 1000.0, span=self.name, **self.labels)
        if self._prof:
            from .. import profiler
            args = dict(self.labels) if self.labels else {}
            try:
                # merged-timeline cross-link: a span opened under
                # tracing.use(ctx) carries its trace_id into the
                # chrome-trace stream (never into metric labels — a
                # per-trace label would explode series cardinality)
                from . import tracing as _tracing
                tid = _tracing.current_trace_id()
                if tid:
                    args["trace_id"] = tid
            except Exception:
                pass
            profiler.record_event(self.name, self.category, self._us0,
                                  dt * 1e6, args or None)
        return False

    def __call__(self, fn):
        name, category, unit, labels = (self.name, self.category,
                                        self._given, self.labels)

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with span(name, category=category, unit=unit, **labels):
                return fn(*args, **kwargs)

        return wrapper
