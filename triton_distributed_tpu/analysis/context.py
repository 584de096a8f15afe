"""Analysis context: shim the device language, replay the kernel body.

`AnalysisContext` monkeypatches — for the duration of a `with` block —
the primitives a kernel body touches, at the module objects every
kernel imports (`jax.lax`, `jax.experimental.pallas`,
`jax.experimental.pallas.tpu`):

- SPMD identity (`axis_index` / `axis_size`) resolves to the concrete
  rank currently being replayed, so `pl.when`-style branches take the
  branch *that rank* would take;
- structured control flow (`fori_loop`, `pl.when`) runs as plain
  Python over concrete trip counts;
- DMA and semaphore ops (`make_async_remote_copy`, `make_async_copy`,
  `semaphore_signal`, `semaphore_wait`, `get_barrier_semaphore`)
  record :class:`analysis.model.Op`s instead of touching hardware;
- `emit_pipeline` records reads of its inputs and writes of its
  outputs (the compute inside is irrelevant to the communication
  footprint); `run_scoped` materialises abstract scratch.

Because every `language.core` primitive bottoms out in these, the
whole device language is covered without the kernels knowing they are
being analyzed.  The replay runs the body once per (rank, grid step)
and assembles the per-rank traces in a :class:`Machine`.

Model assumptions (documented in docs/analysis.md):
- scratch/ref layout is SPMD-symmetric across ranks (the Pallas
  contract), so a semaphore name+index identifies the same physical
  semaphore on every chip;
- communication is data-independent, or the spec provides concrete
  ref `value`s for the scalars that steer it;
- loop bounds, ranks and chunk indices are concrete after rank
  substitution (true for every shipped kernel).
"""

from __future__ import annotations

import contextlib
import itertools
from typing import Callable, Optional, Sequence, Tuple

import numpy as np

from triton_distributed_tpu.analysis.model import (
    AbstractRef,
    AbstractSem,
    Machine,
)

__all__ = ["AnalysisContext", "record_traces"]


# The machine currently recording (shims look this up).  Replays are
# single-threaded; a plain module global keeps the shims trivial.
_CURRENT: Optional[Machine] = None


def _machine() -> Machine:
    if _CURRENT is None:
        raise RuntimeError("analysis shim called outside AnalysisContext")
    return _CURRENT


# ---------------------------------------------------------------------------
# Recorded copy descriptors
# ---------------------------------------------------------------------------

class _RecordedRemoteCopy:
    """Stand-in for the descriptor `pltpu.make_async_remote_copy`
    returns: `.start()` records the put; the wait methods record
    byte-drains of the copy's own semaphores (matching TPU DMA
    semantics: semaphores count delivered bytes)."""

    def __init__(self, src, dst, send_sem, recv_sem, device_id):
        self._src = src
        self._dst = dst
        self._send_sem = send_sem
        self._recv_sem = recv_sem
        self._device_id = device_id

    def start(self):
        _machine().record_put(self._src, self._dst, self._send_sem,
                              self._recv_sem, self._device_id)

    def wait_send(self):
        _machine().record_wait(self._send_sem, self._src.nbytes)

    def wait_recv(self):
        _machine().record_wait(self._recv_sem, self._dst.nbytes)

    def wait(self):
        self.wait_send()
        self.wait_recv()


class _RecordedLocalCopy:
    """Stand-in for `pltpu.make_async_copy`.  The `dl.wait_recv` /
    `dl.wait_send` idiom builds one of these over an *un-started* copy
    purely to drain `ref.nbytes` from a semaphore — so `.wait()`
    records the drain and `.start()` separately records the copy."""

    def __init__(self, src, dst, sem):
        self._src = src
        self._dst = dst
        self._sem = sem

    def start(self):
        _machine().record_copy_start(self._src, self._dst, self._sem)

    def wait(self):
        _machine().record_wait(self._sem, self._src.nbytes)


# ---------------------------------------------------------------------------
# Shims
# ---------------------------------------------------------------------------

def _shim_axis_index(axis):
    if isinstance(axis, (tuple, list)):
        flat = 0
        for a in axis:
            flat = flat * _machine().axis_size(a) + _machine().axis_index(a)
        return flat
    return _machine().axis_index(axis)


def _shim_axis_size(axis):
    if isinstance(axis, (tuple, list)):
        n = 1
        for a in axis:
            n *= _machine().axis_size(a)
        return n
    return _machine().axis_size(axis)


def _shim_fori_loop(lo, hi, body, init, unroll=None):
    del unroll
    val = init
    for i in range(int(lo), int(hi)):
        val = body(i, val)
    return val


def _shim_when(condition):
    def decorator(fn):
        if bool(condition):
            fn()
        return fn
    return decorator


def _shim_program_id(axis: int):
    gp = _machine().grid_point
    return gp[axis] if axis < len(gp) else 0


def _shim_num_programs(axis: int):
    g = _machine().grid
    return g[axis] if axis < len(g) else 1


def _shim_optimization_barrier(value):
    return value


def _shim_make_async_remote_copy(src_ref=None, dst_ref=None, send_sem=None,
                                 recv_sem=None, device_id=None,
                                 device_id_type=None, **kw):
    del device_id_type, kw
    return _RecordedRemoteCopy(src_ref, dst_ref, send_sem, recv_sem,
                               device_id)


def _shim_make_async_copy(src_ref, dst_ref, sem):
    return _RecordedLocalCopy(src_ref, dst_ref, sem)


def _shim_semaphore_signal(sem, inc=1, *, device_id=None,
                           device_id_type=None, **kw):
    del device_id_type, kw
    _machine().record_signal(sem, int(inc), device_id)


def _shim_semaphore_wait(sem, value=1):
    _machine().record_wait(sem, int(value))


def _shim_get_barrier_semaphore():
    # One global barrier semaphore per chip (what `collective_id`
    # selects); symmetric across ranks by name.
    return AbstractSem("__barrier__")


def _shim_emit_pipeline(inner, *, grid=None, in_specs=None, out_specs=None,
                        **kw):
    del inner, grid, kw
    n_in = len(in_specs) if in_specs is not None else 0
    specs = list(in_specs or ()) + list(out_specs or ())

    def run(*refs, **run_kw):
        del run_kw
        ins = refs[:n_in]
        outs = refs[n_in:]
        m = _machine()
        # The pipeline's VMEM working set: one (double-buffered) block
        # per spec — recorded for the resource sanitizer before the
        # comm footprint (reads/writes) below.
        for spec, r in zip(specs, refs):
            shape = getattr(spec, "block_shape", None)
            if shape is not None:
                m.record_resource(
                    "pipeline_block", shape,
                    getattr(r, "dtype", None) or np.float32)
        for r in ins:
            if isinstance(r, AbstractRef):
                m.record_read(r)
        for r in outs:
            if isinstance(r, AbstractRef):
                m.record_write(r)

    return run


def _scratch_to_abstract(machine: Machine, base: str, obj):
    """Map a `pl.run_scoped` scratch descriptor (pltpu.VMEM /
    SemaphoreType.DMA(shape) / SemaphoreType.REGULAR) to an abstract
    ref or semaphore."""
    name = machine.fresh_scoped_name(base)
    shape = tuple(getattr(obj, "shape", ()) or ())
    space = str(getattr(obj, "memory_space", ""))
    dtype = getattr(obj, "dtype", None)
    if ("semaphore" in space.lower()
            or "sem" in str(dtype).lower()
            or "SemaphoreType" in type(obj).__name__):
        return AbstractSem(name, shape)
    np_dtype = np.dtype(dtype) if dtype is not None else np.float32
    if "vmem" in space.lower() or not space:
        machine.record_resource("scratch", shape, np_dtype)
    return AbstractRef(machine, name, shape, np_dtype)


def _shim_run_scoped(fn, *args, **kwargs):
    m = _machine()
    a_args = [_scratch_to_abstract(m, f"arg{i}", t)
              for i, t in enumerate(args)]
    a_kw = {k: _scratch_to_abstract(m, k, t) for k, t in kwargs.items()}
    return fn(*a_args, **a_kw)


def _shim_delay(nanos):
    del nanos


# ---------------------------------------------------------------------------
# The context manager
# ---------------------------------------------------------------------------

class AnalysisContext(contextlib.AbstractContextManager):
    """Installs the recording shims for the duration of a replay."""

    def __init__(self, machine: Machine):
        self.machine = machine
        self._saved = []

    def _patch(self, obj, attr, repl):
        self._saved.append((obj, attr, getattr(obj, attr)))
        setattr(obj, attr, repl)

    def __enter__(self):
        global _CURRENT
        if _CURRENT is not None:
            raise RuntimeError("nested AnalysisContext is not supported")
        _CURRENT = self.machine

        import jax
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        lax = jax.lax
        self._patch(lax, "axis_index", _shim_axis_index)
        self._patch(lax, "axis_size", _shim_axis_size)
        self._patch(lax, "fori_loop", _shim_fori_loop)
        self._patch(lax, "optimization_barrier", _shim_optimization_barrier)

        self._patch(pl, "when", _shim_when)
        self._patch(pl, "program_id", _shim_program_id)
        self._patch(pl, "num_programs", _shim_num_programs)
        self._patch(pl, "run_scoped", _shim_run_scoped)
        self._patch(pl, "delay", _shim_delay)

        self._patch(pltpu, "make_async_remote_copy",
                    _shim_make_async_remote_copy)
        self._patch(pltpu, "make_async_copy", _shim_make_async_copy)
        self._patch(pltpu, "semaphore_signal", _shim_semaphore_signal)
        self._patch(pltpu, "semaphore_wait", _shim_semaphore_wait)
        self._patch(pltpu, "get_barrier_semaphore",
                    _shim_get_barrier_semaphore)
        self._patch(pltpu, "emit_pipeline", _shim_emit_pipeline)
        return self.machine

    def __exit__(self, *exc):
        global _CURRENT
        for obj, attr, orig in reversed(self._saved):
            setattr(obj, attr, orig)
        self._saved.clear()
        _CURRENT = None
        return False


# ---------------------------------------------------------------------------
# Replay driver
# ---------------------------------------------------------------------------

def record_traces(body: Callable, *, axis_sizes, refs: Sequence,
                  sems: Sequence, grid: Tuple[int, ...] = ()) -> Machine:
    """Replay `body(*refs, *sems)` once per (rank, grid step) on the
    abstract machine and return the machine with per-rank traces.

    `axis_sizes`: dict axis name -> world size (the mesh shape).
    `refs` / `sems`: RefSpec / SemSpec sequences (see registry).
    """
    axis_names = tuple(axis_sizes)
    sizes = tuple(int(axis_sizes[a]) for a in axis_names)
    machine = Machine(axis_names, sizes, grid)

    grid_points = (list(itertools.product(*[range(g) for g in grid]))
                   if grid else [()])

    with AnalysisContext(machine):
        for rank in machine.all_ranks():
            machine.set_rank(rank)
            coords = dict(zip(axis_names, rank))
            for gp in grid_points:
                machine.grid_point = gp
                # Scoped-scratch names must be SPMD-symmetric: every
                # rank allocates in the same deterministic order, so a
                # per-replay counter reset makes `run_scoped` scratch
                # (including DMA semaphores) line up across ranks —
                # the name-symmetry contract every cross-rank check
                # relies on.
                machine.reset_scoped_names()
                # RefSpec.value may be a callable(rank coords dict) for
                # rank-dependent scalars (e.g. a per-rank query offset).
                a_refs = [
                    AbstractRef(machine, s.name, s.shape, s.dtype,
                                value=(None if s.value is None
                                       else np.asarray(
                                           s.value(coords)
                                           if callable(s.value)
                                           else s.value)))
                    for s in refs
                ]
                a_sems = [AbstractSem(s.name, s.shape) for s in sems]
                body(*a_refs, *a_sems)
    return machine
