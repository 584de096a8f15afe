"""Runtime span tracing: what is this rank doing *right now*, and
where did the wall-clock of a step go.

PR 1's kernel events fire at *trace time* (once per compiled
specialization) — they answer "what was compiled", not "what ran when".
Spans are the runtime half: host-side begin/end records around the
serving and tuning hot paths (prefill, decode steps, autotune trials,
bench iterations), cheap enough (~µs: two lock-guarded list ops per
span) to stay on in production.

Three consumers, one record:

- a per-rank **Chrome-trace-event JSON** export
  (``export_chrome_trace``) loadable in Perfetto / ``chrome://tracing``
  and mergeable across ranks on a shared clock (:mod:`.timeline`);
- the **XLA profiler**: every span also enters a
  ``jax.profiler.TraceAnnotation``, so the same names appear on a host
  plane of the XProf timeline when a ``jax.profiler`` trace is active;
- the **flight recorder / heartbeat**: the currently-open span stack is
  queryable (``open_spans``), so a SIGTERM dump or a stale-rank report
  can say what the rank was doing when it stopped.

One clock.  Inside a ``jax.profiler`` trace a span's
``TraceAnnotation`` is stamped by the profiler itself, so spans and
device operations share the trace's clock by construction — nothing
is converted.  Outside one the ring is the record: every span keeps
its raw ``time.perf_counter()`` start (``Span.t0``), and
:data:`MONOTONIC_OFFSET` is the one offset from that clock to
``time.monotonic()`` (the clock a serving harness hands the
scheduler), so a reader can cut the ring by the harness's window.

Cause.  A span records the span that was open on its thread when it
started (``Span.parent``, an ``id``): self time of a span is its
duration less its children's.  A *detached* span
(:meth:`SpanTracer.detached`) is a lifetime, not something a thread
is doing — a request from admission to retirement, closed in any
order: it is in the ring and in ``open_spans``, but it is never a
parent, and it does not enter a ``TraceAnnotation`` (the profiler's
per-thread stack expects enter and exit in stack order).

Cost discipline: with ``TDT_OBSERVABILITY=0`` the module-level
:func:`span` returns one shared no-op context manager — no allocation,
no lock, no clock read.  Enabled spans land in a bounded ring
(``TDT_TRACE_RING``, default 65536 finished spans: a serving run of a
few minutes — ~2000 scheduler steps of 9 spans — fits three times),
so a long-running server never grows without bound; what the ring
evicts is counted (``SpanTracer.dropped``,
``trace_dropped_spans_total``) so no reader takes a truncated window
for a whole one.
"""

from __future__ import annotations

import atexit
import functools
import gc
import itertools
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional

# spans mirror into XLA traces when a profiler is attached
from jax.profiler import TraceAnnotation as _TraceAnnotation

from triton_distributed_tpu.observability.metrics import (
    get_registry,
    observability_enabled,
)

#: Env knobs (scripts/launch.py --trace-dir plumbs the first one).
ENV_TRACE_DIR = "TDT_TRACE_DIR"
ENV_TRACE_RING = "TDT_TRACE_RING"
DEFAULT_RING = 65536

#: Unix-epoch base of ``time.perf_counter``, captured once per process:
#: span timestamps are ``_CLOCK_BASE + perf_counter()``, i.e. monotonic
#: *within* a rank but expressed on the wall clock *across* ranks — the
#: shared clock :mod:`.timeline` merges on (same-host ranks share it
#: exactly; cross-host skew is whatever NTP leaves, carried in the
#: export metadata so the merge can report it).
_CLOCK_BASE = time.time() - time.perf_counter()  # noqa: W001 (perf_counter epoch anchor, export metadata)

#: ``time.monotonic() - time.perf_counter()``, captured once: add it to
#: a span's ``t0`` to place the span on ``time.monotonic``'s clock.
MONOTONIC_OFFSET = time.monotonic() - time.perf_counter()  # noqa: W001 (the offset between two clocks, read once at import)


class Span:
    """One timed region.  Context manager; reentrant use is a bug
    (enter creates state), nest by creating new spans."""

    __slots__ = ("name", "attrs", "id", "parent", "t0", "ts", "dur",
                 "tid", "depth", "detached", "_tracer", "_ann")

    def __init__(self, tracer: "SpanTracer", name: str,
                 attrs: Optional[Dict[str, Any]] = None,
                 detached: bool = False):
        self._tracer = tracer
        self.name = name
        self.attrs = attrs or {}
        self.id = next(tracer._ids)
        self.parent = None     # id of the span that caused this one
        self.t0 = 0.0          # raw time.perf_counter() at enter
        self.ts = 0.0          # unix seconds at enter
        self.dur = None        # seconds; None while open
        self.tid = 0
        self.depth = 0
        self.detached = detached
        self._ann = None

    def __enter__(self) -> "Span":
        self.tid = threading.get_ident()
        self._tracer._push(self)
        if not self.detached:
            self._ann = _TraceAnnotation(self.name)
            self._ann.__enter__()
        self.t0 = time.perf_counter()
        self.ts = _CLOCK_BASE + self.t0
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        self.dur = t1 - self.t0
        if exc_type is not None:
            self.attrs["error"] = repr(exc_type.__name__)
        self._tracer._pop(self)
        return False

    def to_dict(self) -> dict:
        return {"name": self.name, "id": self.id,
                "parent": self.parent, "ts": self.ts, "dur": self.dur,
                "tid": self.tid, "depth": self.depth,
                "attrs": self.attrs}

    def chrome_event(self, rank: int, now: Optional[float] = None
                     ) -> dict:
        """Chrome "complete" (ph=X) event, µs timestamps.  An open span
        reports its duration so far and ``args.open=true``."""
        dur = self.dur
        args = dict(self.attrs, id=self.id, parent=self.parent)
        if dur is None:
            dur = max((now or time.time()) - self.ts, 0.0)  # noqa: W001 (default when no `now` injected)
            args["open"] = True
        return {"name": self.name, "ph": "X", "cat": "span",
                "ts": round(self.ts * 1e6, 3),
                "dur": round(dur * 1e6, 3),
                "pid": rank, "tid": self.tid, "args": args}


class _NullSpan:
    """Shared do-nothing span: the disabled path allocates nothing."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NULL_SPAN = _NullSpan()


class SpanTracer:
    """Thread-safe bounded ring of finished spans + per-thread stacks
    of open ones.  One process-global instance (:func:`get_tracer`)
    backs the module-level :func:`span` / :func:`traced`; tests may
    build private tracers."""

    def __init__(self, capacity: Optional[int] = None):
        if capacity is None:
            capacity = int(os.environ.get(ENV_TRACE_RING, DEFAULT_RING))
        import collections
        self._lock = threading.RLock()
        self._ring = collections.deque(maxlen=capacity)
        self._open: Dict[int, List[Span]] = {}
        #: Open detached spans by id: lifetimes, closed in any order.
        self._detached: Dict[int, Span] = {}
        self._last: Optional[Span] = None  # most recently *started*
        self._ids = itertools.count(1)
        #: Finished spans the full ring has evicted since the start
        #: (or the last `clear`).
        self.dropped = 0

    @property
    def capacity(self) -> int:
        return self._ring.maxlen

    def __len__(self) -> int:
        return len(self._ring)

    def span(self, name: str, **attrs) -> Span:
        if not observability_enabled():
            return NULL_SPAN
        return Span(self, name, attrs)

    def detached(self, name: str, **attrs) -> Span:
        """A lifetime span (a request from admission to retirement):
        entered and exited by hand, in any order relative to other
        spans.  Recorded like any span — ring, ``open_spans``, its
        ``parent`` the span that started it — but never a parent
        itself and never shown to the profiler."""
        if not observability_enabled():
            return NULL_SPAN
        return Span(self, name, attrs, detached=True)

    def record(self, name: str, t0: float, dur: float, **attrs) -> None:
        """A span that is over: the caller measured its start (on
        ``time.perf_counter``) and its duration itself, because most
        of its kind are not worth a span (`install_gc_hook`).  Its
        parent is the span open on this thread now; the profiler never
        saw it."""
        s = Span(self, name, attrs)
        s.tid = threading.get_ident()
        s.t0, s.ts, s.dur = t0, _CLOCK_BASE + t0, dur
        with self._lock:
            stack = self._open.get(s.tid)
            if stack:
                s.parent, s.depth = stack[-1].id, len(stack)
            self._finish(s)

    @property
    def monotonic_offset(self) -> float:
        """:data:`MONOTONIC_OFFSET`, for a reader that holds the
        tracer and does not import this module."""
        return MONOTONIC_OFFSET

    # -- Span plumbing ---------------------------------------------------

    def _push(self, s: Span) -> None:
        with self._lock:
            stack = self._open.get(s.tid)
            if stack:
                s.parent = stack[-1].id
                s.depth = len(stack)
            if s.detached:
                self._detached[s.id] = s
            elif stack is None:
                self._open[s.tid] = [s]
            else:
                stack.append(s)
            self._last = s

    def _pop(self, s: Span) -> None:
        with self._lock:
            if s.detached:
                self._detached.pop(s.id, None)
            else:
                stack = self._open.get(s.tid)
                if stack and stack[-1] is s:
                    stack.pop()
                elif stack and s in stack:
                    stack.remove(s)
                if not stack:
                    self._open.pop(s.tid, None)
            self._finish(s)

    def _finish(self, s: Span) -> None:
        """Into the ring (under the lock)."""
        if len(self._ring) == self._ring.maxlen:
            self.dropped += 1
            # Overflow must not be silent: a timeline merged from
            # this ring is missing the evicted span, and a doctor
            # report built on it should say so.
            get_registry().counter("trace_dropped_spans_total").inc()
        self._ring.append(s)

    # -- inspection ------------------------------------------------------

    def finished(self) -> List[Span]:
        with self._lock:
            return list(self._ring)

    def open_spans(self) -> List[Span]:
        """Currently-open spans across every thread, outermost first
        per thread — "what is this rank doing right now"."""
        with self._lock:
            return ([s for stack in self._open.values() for s in stack]
                    + list(self._detached.values()))

    def last_span(self) -> Optional[Span]:
        """The innermost open span, else the most recently started one
        — the heartbeat's "last seen doing"."""
        with self._lock:
            for stack in self._open.values():
                if stack:
                    return stack[-1]
            return self._last

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self._open.clear()
            self._detached.clear()
            self._last = None
            self.dropped = 0

    # -- Chrome-trace export ---------------------------------------------

    def chrome_trace(self, include_open: bool = True) -> dict:
        """The per-rank Chrome trace object (Perfetto /
        ``chrome://tracing`` "JSON object format")."""
        from triton_distributed_tpu.observability.metrics import (
            _process_count, _process_index)
        rank = _process_index()
        now = _CLOCK_BASE + time.perf_counter()
        with self._lock:
            spans = list(self._ring)
            if include_open:
                spans += [s for st in self._open.values() for s in st]
                spans += list(self._detached.values())
        events = [{"ph": "M", "name": "process_name", "pid": rank,
                   "args": {"name": f"rank {rank}"}},
                  {"ph": "M", "name": "process_sort_index", "pid": rank,
                   "args": {"sort_index": rank}}]
        events += [s.chrome_event(rank, now) for s in spans]
        return {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "metadata": {
                "schema": 1,
                "rank": rank,
                "world": _process_count(),
                "pid": os.getpid(),
                "clock": "unix-us",
                "clock_base_unix": _CLOCK_BASE,
                "export_unix_time": time.time(),  # noqa: W001 (export wall-stamp for humans)
            },
        }

    def default_path(self, directory: str) -> str:
        from triton_distributed_tpu.observability.metrics import (
            _process_index)
        return os.path.join(directory,
                            f"trace-rank-{_process_index()}.json")

    def export_chrome_trace(self, path: Optional[str] = None
                            ) -> Optional[str]:
        """Write the Chrome trace to ``path``, or to
        ``$TDT_TRACE_DIR/trace-rank-<N>.json``; returns the path
        written or None when there is nowhere to write."""
        if path is None:
            directory = os.environ.get(ENV_TRACE_DIR)
            if not directory:
                return None
            path = self.default_path(directory)
        os.makedirs(os.path.dirname(os.path.abspath(path)),
                    exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(self.chrome_trace(), f, default=str)
        os.replace(tmp, path)
        return path


_TRACER: Optional[SpanTracer] = None
# RLock: get_tracer() is reached from the flight recorder's signal
# handler (via the heartbeat payload); a plain Lock could deadlock a
# dying rank whose main thread was interrupted inside it.
_TRACER_LOCK = threading.RLock()


def get_tracer() -> SpanTracer:
    global _TRACER
    with _TRACER_LOCK:
        if _TRACER is None:
            _TRACER = SpanTracer()
        return _TRACER


def span(name: str, **attrs):
    """``with span("engine.prefill", batch=b): ...`` — the module-level
    entry point everything instruments through.  Disabled
    (``TDT_OBSERVABILITY=0``): returns the shared no-op span, zero
    allocation."""
    if not observability_enabled():
        return NULL_SPAN
    return Span(get_tracer(), name, attrs)


def traced(fn=None, *, name: Optional[str] = None):
    """Decorator form: ``@traced`` or ``@traced(name="engine.step")``.
    The span name defaults to the function's qualified name."""
    if fn is None:
        return functools.partial(traced, name=name)
    span_name = name or getattr(fn, "__qualname__", fn.__name__)

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        with span(span_name):
            return fn(*args, **kwargs)

    return wrapper


# -- pauses of Python's cyclic collector -----------------------------------

#: A collection shorter than this leaves nothing behind (there are
#: thousands; a pause that shows in a step is a hundred times longer).
GC_PAUSE_MIN_S = 1e-3

_GC_HOOKED = False


def install_gc_hook() -> None:
    """One `gc.callbacks` hook for the process (idempotent): a
    collection that took over `GC_PAUSE_MIN_S` is a `runtime.gc` span
    (``generation``, ``collected``) in the global tracer, under
    whatever span was open when it struck, and an observation of
    `runtime_gc_pause_ms` — so a long step's "self" names its cause.
    A short one costs two clock reads."""
    global _GC_HOOKED
    if _GC_HOOKED:
        return
    _GC_HOOKED = True
    began = [0.0]

    def on_gc(phase, info):
        if phase == "start":
            began[0] = time.perf_counter()
            return
        dur = time.perf_counter() - began[0]
        if dur < GC_PAUSE_MIN_S or not observability_enabled():
            return
        get_tracer().record("runtime.gc", began[0], dur,
                            generation=info.get("generation"),
                            collected=info.get("collected"))
        get_registry().histogram("runtime_gc_pause_ms").observe(dur * 1e3)

    gc.callbacks.append(on_gc)


# -- step tracking (heartbeat / timeline context) -------------------------

# Deliberately lock-free: a bare int store/load is atomic in CPython,
# and current_step() is called from the flight recorder's SIGTERM
# handler — a lock here could deadlock the dying rank if the signal
# landed inside set_step().
_STEP: Optional[int] = None


def set_step(step: int) -> None:
    """Record the current logical step (decode step, bench iteration)
    so heartbeats and flight dumps can say *where* a rank stalled."""
    global _STEP
    _STEP = int(step)


def current_step() -> Optional[int]:
    return _STEP


# -- launcher integration -------------------------------------------------

_EXPORT_ARMED = False


def maybe_install_trace_export() -> bool:
    """Arm an atexit Chrome-trace export iff ``TDT_TRACE_DIR`` names a
    directory (``scripts/launch.py --trace-dir`` plumbs it to every
    worker).  Called from ``parallel.mesh.initialize_distributed``;
    safe to call twice.  SIGTERM deaths do not run atexit — there the
    flight recorder's dump carries the open spans instead."""
    global _EXPORT_ARMED
    if not os.environ.get(ENV_TRACE_DIR):
        return False
    if _EXPORT_ARMED:
        return True
    _EXPORT_ARMED = True
    atexit.register(lambda: get_tracer().export_chrome_trace())
    return True
