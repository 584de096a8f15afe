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
  ``jax.profiler.TraceAnnotation``, so the same names appear on the
  XProf timeline when a ``jax.profiler`` trace is active;
- the **flight recorder / heartbeat**: the currently-open span stack is
  queryable (``open_spans``), so a SIGTERM dump or a stale-rank report
  can say what the rank was doing when it stopped.

Cost discipline: with ``TDT_OBSERVABILITY=0`` the module-level
:func:`span` returns one shared no-op context manager — no allocation,
no lock, no clock read.  Enabled spans land in a bounded ring
(``TDT_TRACE_RING``, default 16384 finished spans), so a long-running
server never grows without bound.
"""

from __future__ import annotations

import atexit
import functools
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional

# spans mirror into XLA traces when a profiler is attached
from jax.profiler import TraceAnnotation as _TraceAnnotation

from triton_distributed_tpu.observability.metrics import (
    observability_enabled,
)

#: Env knobs (scripts/launch.py --trace-dir plumbs the first one).
ENV_TRACE_DIR = "TDT_TRACE_DIR"
ENV_TRACE_RING = "TDT_TRACE_RING"
DEFAULT_RING = 16384

#: Unix-epoch base of ``time.perf_counter``, captured once per process:
#: span timestamps are ``_CLOCK_BASE + perf_counter()``, i.e. monotonic
#: *within* a rank but expressed on the wall clock *across* ranks — the
#: shared clock :mod:`.timeline` merges on (same-host ranks share it
#: exactly; cross-host skew is whatever NTP leaves, carried in the
#: export metadata so the merge can report it).
_CLOCK_BASE = time.time() - time.perf_counter()  # noqa: W001 (perf_counter epoch anchor, export metadata)

class Span:
    """One timed region.  Context manager; reentrant use is a bug
    (enter creates state), nest by creating new spans."""

    __slots__ = ("name", "attrs", "ts", "dur", "tid", "depth",
                 "_tracer", "_t0", "_ann")

    def __init__(self, tracer: "SpanTracer", name: str,
                 attrs: Optional[Dict[str, Any]] = None):
        self._tracer = tracer
        self.name = name
        self.attrs = attrs or {}
        self.ts = 0.0          # unix seconds at enter
        self.dur = None        # seconds; None while open
        self.tid = 0
        self.depth = 0
        self._ann = None

    def __enter__(self) -> "Span":
        self.tid = threading.get_ident()
        self.depth = self._tracer._push(self)
        self._ann = _TraceAnnotation(self.name)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        self.ts = _CLOCK_BASE + self._t0
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        t1 = time.perf_counter()
        self._ann.__exit__(exc_type, exc, tb)
        self.dur = t1 - self._t0
        if exc_type is not None:
            self.attrs["error"] = repr(exc_type.__name__)
        self._tracer._pop(self)
        return False

    def to_dict(self) -> dict:
        return {"name": self.name, "ts": self.ts, "dur": self.dur,
                "tid": self.tid, "depth": self.depth,
                "attrs": self.attrs}

    def chrome_event(self, rank: int, now: Optional[float] = None
                     ) -> dict:
        """Chrome "complete" (ph=X) event, µs timestamps.  An open span
        reports its duration so far and ``args.open=true``."""
        dur = self.dur
        args = dict(self.attrs)
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
        self._last: Optional[Span] = None  # most recently *started*

    @property
    def capacity(self) -> int:
        return self._ring.maxlen

    def __len__(self) -> int:
        return len(self._ring)

    def span(self, name: str, **attrs) -> Span:
        if not observability_enabled():
            return NULL_SPAN
        return Span(self, name, attrs)

    # -- Span plumbing ---------------------------------------------------

    def _push(self, s: Span) -> int:
        with self._lock:
            stack = self._open.setdefault(s.tid, [])
            stack.append(s)
            self._last = s
            return len(stack) - 1

    def _pop(self, s: Span) -> None:
        with self._lock:
            stack = self._open.get(s.tid)
            if stack and s in stack:
                stack.remove(s)
                if not stack:
                    del self._open[s.tid]
            if len(self._ring) == self._ring.maxlen:
                # Overflow must not be silent: a timeline merged from
                # this ring is missing the evicted span, and a doctor
                # report built on it should say so.
                from triton_distributed_tpu.observability.metrics \
                    import get_registry
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
            return [s for stack in self._open.values() for s in stack]

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
            self._last = None

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
