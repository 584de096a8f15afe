"""Kernel resource sanitizer: VMEM / tiling / block-index bounds.

The single-rank counterpart of the comm-graph sanitizer: instead of
replaying semaphore protocols, it replays every registered kernel's
`pallas_call` **geometry** — grid, BlockSpecs, scratch, scalar-prefetch
tables — and proves three resource properties with no TPU:

- **VMEM footprint** — dtype-aware bytes of every VMEM block (pipelined
  operands double-buffered, Pallas' steady state) plus scratch, checked
  against the call's `vmem_limit_bytes` (Mosaic's 16 MiB default when
  unset).  `vmem_overflow` findings are launch aborts caught in CI.
- **Tiling legality** — lane (last) dims must be 128-multiples unless
  they cover the whole operand dim; sublane dims must be multiples of
  the dtype's native rows (8 for 4-byte, 16 for 2-byte, 32 for int8 —
  the int8 scale-row rule from `quantized.py`).  → `tiling_illegal`.
- **Block-index bounds** — every BlockSpec index map is evaluated at
  every grid point with the *concrete* scalar-prefetch operands the
  call received, so indirection through index tables
  (`flash_attention`'s packed schedule) is checked against the real
  table values; blocks a kernel copies by hand out of an HBM operand
  are stated as `ManualBlocks` and bounded the same way
  (`flash_decode_paged`'s page gather, ``(ptab[b, j], 0, 0, 0)`` for
  every page below the row's length).
  The reserved NULL/trash page (`models.kv_cache.NULL_PAGE` = 0) is in
  bounds by construction — physical page 0 exists precisely so NULL
  entries land somewhere harmless — so a clean paged table analyzes
  clean and only a genuinely out-of-range entry is `oob_block_index`.

Two acquisition paths feed the same checks:

1. **Capture** (compute kernels): `capture_pallas_calls()` patches
   `pl.pallas_call` to *record* the call instead of compiling it; the
   kernel's host wrapper runs unmodified on CPU (no Mosaic, no
   interpret machinery), so the analyzed geometry is the literal
   `pallas_call` the kernel issues — zero spec drift.  Modules register
   builders with :func:`register_resource_kernel` next to their
   `pallas_call` sites, mirroring the comm registry.
2. **Replay** (comm kernels): the existing comm-graph replay records
   `run_scoped` VMEM scratch and `emit_pipeline` block shapes
   (`Machine.resource_replays`); :func:`check_replay_resources` folds
   them into the same footprint/tiling findings, so the full 50+
   (kernel, mesh) comm sweep gets resource coverage for free.

This module is also the **one shared footprint estimator** the kernel
guards call (`moe_reduce_rs`'s HBM-staging fallback, the GEMM-family
pre-flight checks, `flash_attention`'s prefetch-table cap), so guard
and analyzer can never disagree: both read `LANE`, `sublane_rows`,
`scratch_footprint_bytes` and `max_prefetch_steps` from here.

Dependency note: this module must stay importable from kernel modules
(they call the estimator at trace time), so it imports only the
stdlib + numpy at module level; jax/pallas are imported lazily inside
the capture machinery.
"""

from __future__ import annotations

import contextlib
import dataclasses
import itertools
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from triton_distributed_tpu.analysis.model import Finding, FindingKind

__all__ = [
    "CapturedCall",
    "LANE",
    "MOSAIC_DEFAULT_VMEM_LIMIT",
    "ManualBlocks",
    "PREFETCH_SMEM_LIMIT",
    "all_resource_kernels",
    "block_bytes",
    "capture_pallas_calls",
    "check_captured_call",
    "check_replay_resources",
    "check_vmem_fit",
    "max_prefetch_steps",
    "register_resource_kernel",
    "scratch_footprint_bytes",
    "sublane_rows",
    "sweep_resources",
]


# ---------------------------------------------------------------------------
# Shared estimator: the arithmetic guards and analyzer both use
# ---------------------------------------------------------------------------

#: Mosaic lane tiling unit: the last dim of any tiled block/slice.
LANE = 128

#: Mosaic's default scoped-VMEM ceiling when a `pallas_call` sets no
#: `vmem_limit_bytes` (kernels that need more pass
#: `utils.platform.SCOPED_VMEM_LIMIT` explicitly).
MOSAIC_DEFAULT_VMEM_LIMIT = 16 * 1024 * 1024

#: Budget for scalar-prefetch tables (they live in SMEM): the packed
#: flash-attention schedule's three int32 tables at its historical
#: 4096-step cap — 48 KiB.  `flash_attention` derives its step cap
#: from this via `max_prefetch_steps(3)`.
PREFETCH_SMEM_LIMIT = 48 * 1024


def sublane_rows(dtype) -> int:
    """Native Mosaic sublane multiple for ``dtype``: (8, 128) tiles
    for 4-byte, (16, 128) for 2-byte, (32, 128) for 1-byte elements.
    The single source for `matmul.round_up_rows`, the int8 block
    alignment in `quantized.py`, and the analyzer's tiling check."""
    itemsize = np.dtype(dtype).itemsize
    return {1: 32, 2: 16}.get(itemsize, 8)


def block_bytes(shape: Sequence[int], dtype) -> int:
    """Dtype-aware bytes of one block/scratch buffer."""
    return int(np.prod(tuple(shape) or (1,), dtype=np.int64)
               * np.dtype(dtype).itemsize)


def scratch_footprint_bytes(entries) -> int:
    """Total bytes of a scratch list: iterable of (shape, dtype)."""
    return sum(block_bytes(shape, dtype) for shape, dtype in entries)


def pipeline_footprint_bytes(block_entries, scratch_entries=(),
                             double_buffer: bool = True) -> int:
    """Working-set estimate of a software pipeline: every streamed
    block double-buffered (Pallas/`emit_pipeline` steady state) plus
    persistent scratch."""
    factor = 2 if double_buffer else 1
    return (factor * scratch_footprint_bytes(block_entries)
            + scratch_footprint_bytes(scratch_entries))


def max_prefetch_steps(num_tables: int, entry_bytes: int = 4) -> int:
    """How many grid steps fit the SMEM prefetch-table budget with
    ``num_tables`` per-step tables of ``entry_bytes`` entries."""
    return PREFETCH_SMEM_LIMIT // (num_tables * entry_bytes)


def check_vmem_fit(kernel: str, block_entries, scratch_entries=(),
                   limit: Optional[int] = None,
                   double_buffer: bool = True) -> int:
    """Pre-flight guard for kernel hosts: estimate the VMEM working
    set and raise a readable error (instead of a deep Mosaic abort)
    when it cannot fit.  Returns the estimate so callers can also
    branch on it (e.g. `moe_reduce_rs`'s HBM-staged fallback compares
    the same number against `COMM_VMEM_LIMIT`)."""
    from triton_distributed_tpu.utils.platform import SCOPED_VMEM_LIMIT

    limit = SCOPED_VMEM_LIMIT if limit is None else int(limit)
    est = pipeline_footprint_bytes(block_entries, scratch_entries,
                                   double_buffer=double_buffer)
    if est > limit:
        raise ValueError(
            f"{kernel}: estimated VMEM working set {est} bytes "
            f"(blocks x{2 if double_buffer else 1} + scratch) exceeds "
            f"the {limit}-byte limit — shrink the block config")
    return est


# ---------------------------------------------------------------------------
# pallas_call capture
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _SpecView:
    """One BlockSpec + the operand it maps, flattened for checking."""

    block_shape: Optional[Tuple[int, ...]]
    index_map: Optional[Callable]
    memory_space: str
    array_shape: Tuple[int, ...]
    dtype: np.dtype
    name: str
    #: ``index_map`` is a `ManualBlocks.indices`: a LIST of block
    #: indices a grid point (the operand itself is not in VMEM).
    manual: bool = False


@dataclasses.dataclass(frozen=True)
class ManualBlocks:
    """The blocks a kernel copies BY HAND (`make_async_copy`) out of
    an operand it leaves whole in HBM (`memory_space=pl.ANY`), stated
    for the bounds check the way a BlockSpec states them:
    ``indices(*grid_point, *prefetch)`` lists the block indices the
    kernel reads at that grid point, in units of ``block_shape``.
    `pallas_call` has no slot for it, so the host sets
    ``kernel.manual_blocks = {input position: ManualBlocks}`` on the
    kernel callable it passes (`flash_decode_paged`'s page gather)."""

    block_shape: Tuple[int, ...]
    indices: Callable


@dataclasses.dataclass
class CapturedCall:
    """Everything one recorded `pallas_call` exposes to the checks."""

    name: str
    grid: Tuple[int, ...]
    specs: List[_SpecView]              # in specs then out specs
    scratch: List[Tuple[Tuple[int, ...], np.dtype]]
    prefetch: List[np.ndarray]          # concrete scalar-prefetch values
    vmem_limit: Optional[int]


def _space_of(spec) -> str:
    space = getattr(spec, "memory_space", None)
    return str(space).lower() if space is not None else "vmem"


def _dtype_of(x) -> np.dtype:
    try:
        return np.dtype(x)
    except TypeError:
        return np.dtype(getattr(x, "dtype", np.float32))


def _spec_views(specs, operands, kind: str,
                manual=None) -> List[_SpecView]:
    views = []
    for i, (spec, op) in enumerate(zip(specs, operands)):
        by_hand = (manual or {}).get(i)
        if by_hand is not None:
            block_shape, index_map = (tuple(by_hand.block_shape),
                                      by_hand.indices)
        else:
            block_shape = (tuple(spec.block_shape)
                           if getattr(spec, "block_shape", None)
                           is not None else None)
            index_map = getattr(spec, "index_map", None)
        views.append(_SpecView(
            block_shape=block_shape,
            index_map=index_map,
            memory_space=_space_of(spec),
            array_shape=tuple(np.shape(op)),
            dtype=_dtype_of(getattr(op, "dtype", np.float32)),
            name=f"{kind}{i}",
            manual=by_hand is not None))
    return views


class _CapturedCompilerParams:
    """Recording stand-in for `pltpu.CompilerParams` (absent in older
    jax, where the kernels can only run after capture anyway)."""

    def __init__(self, **kw):
        self.kw = kw
        self.vmem_limit_bytes = kw.get("vmem_limit_bytes")
        self.dimension_semantics = kw.get("dimension_semantics")


_MISSING = object()


@contextlib.contextmanager
def capture_pallas_calls():
    """Patch `pl.pallas_call` (and `pltpu.CompilerParams`) so kernel
    hosts record their call geometry and return zeros instead of
    compiling.  Yields the list the records append to."""
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    records: List[CapturedCall] = []
    saved = [(pl, "pallas_call", pl.pallas_call),
             (pltpu, "CompilerParams",
              getattr(pltpu, "CompilerParams", _MISSING))]

    def patched(kernel, *, out_shape, grid_spec=None, grid=None,
                in_specs=None, out_specs=None, scratch_shapes=(),
                compiler_params=None, **kw):
        del kw
        gs_grid = tuple(getattr(grid_spec, "grid", None) or grid or ())
        gs_in = list(getattr(grid_spec, "in_specs", None)
                     or in_specs or [])
        gs_out = getattr(grid_spec, "out_specs", None) or out_specs
        gs_out = (list(gs_out) if isinstance(gs_out, (tuple, list))
                  else [gs_out] if gs_out is not None else [])
        gs_scratch = list(getattr(grid_spec, "scratch_shapes", None)
                          or scratch_shapes or [])
        n_pre = int(getattr(grid_spec, "num_scalar_prefetch", 0) or 0)
        vmem_limit = getattr(compiler_params, "vmem_limit_bytes", None)
        kname = getattr(getattr(kernel, "func", kernel), "__name__",
                        "pallas_kernel")

        def runner(*operands):
            outs = [o for o in jax.tree_util.tree_leaves(out_shape)]
            out_ops = [np.zeros(tuple(o.shape), o.dtype) for o in outs]
            views = (_spec_views(gs_in, operands[n_pre:], "in",
                                 getattr(kernel, "manual_blocks", None))
                     + _spec_views(gs_out, out_ops, "out"))
            scratch = []
            for s in gs_scratch:
                shape = tuple(getattr(s, "shape", ()) or ())
                space = str(getattr(s, "memory_space", "")).lower()
                if "sem" in space or "Semaphore" in type(s).__name__:
                    continue
                scratch.append((shape, _dtype_of(getattr(s, "dtype",
                                                         np.float32))))
            records.append(CapturedCall(
                name=kname, grid=gs_grid, specs=views, scratch=scratch,
                prefetch=[np.asarray(o) for o in operands[:n_pre]],
                vmem_limit=(int(vmem_limit) if vmem_limit else None)))
            tree = jax.tree_util.tree_structure(out_shape)
            return jax.tree_util.tree_unflatten(tree, out_ops)

        return runner

    pl.pallas_call = patched
    pltpu.CompilerParams = _CapturedCompilerParams
    try:
        yield records
    finally:
        for obj, attr, orig in saved:
            if orig is _MISSING:
                delattr(obj, attr)
            else:
                setattr(obj, attr, orig)


# ---------------------------------------------------------------------------
# Checks
# ---------------------------------------------------------------------------

def _check_tiling(shape: Tuple[int, ...], dtype,
                  full: Optional[Tuple[int, ...]], what: str,
                  kernel: Optional[str]) -> List[Finding]:
    """Lane/sublane legality of one block or scratch shape.

    Conservative (no false positives on shipped kernels): the lane dim
    is illegal when it exceeds one lane tile without being a multiple,
    or is a partial slice (neither a 128-multiple nor the operand's
    whole dim).  The sublane dim is illegal when it exceeds the
    dtype's native rows without being a multiple (and is not the whole
    operand dim — Mosaic pads whole-dim and sub-tile extents)."""
    findings = []
    if not shape:
        return findings
    last = int(shape[-1])
    full_last = int(full[-1]) if full else None
    if last % LANE != 0:
        partial = full_last is not None and last != full_last
        if last > LANE or partial:
            findings.append(Finding(
                FindingKind.TILING_ILLEGAL,
                f"{what}: lane (last) dim {last} is not a multiple of "
                f"{LANE}"
                + (f" and is a partial slice of {full_last}"
                   if partial else "")
                + " — Mosaic rejects the layout",
                ref=what, kernel=kernel))
    if len(shape) >= 2:
        rows = int(shape[-2])
        unit = sublane_rows(dtype)
        full_rows = int(full[-2]) if full and len(full) >= 2 else None
        if rows % unit != 0 and rows > unit and rows != full_rows:
            findings.append(Finding(
                FindingKind.TILING_ILLEGAL,
                f"{what}: sublane dim {rows} is not a multiple of the "
                f"{np.dtype(dtype).name} native tile rows ({unit}) — "
                f"forces relayouts or fails to compile",
                ref=what, kernel=kernel))
    return findings


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


#: Exhaustive grid-point cap for the bounds check; grids beyond it
#: are sampled deterministically (first N in row-major order + the
#: last point) — shipped kernels' representative shapes stay well
#: under it, so the sweep is exhaustive in practice.
MAX_BOUND_POINTS = 100_000


def _grid_points(grid: Tuple[int, ...]):
    total = int(np.prod(grid or (1,), dtype=np.int64))
    points = itertools.product(*[range(g) for g in grid]) if grid \
        else iter([()])
    if total <= MAX_BOUND_POINTS:
        yield from points
        return
    yield from itertools.islice(points, MAX_BOUND_POINTS)
    yield tuple(g - 1 for g in grid)


def check_captured_call(call: CapturedCall,
                        kernel: Optional[str] = None) -> List[Finding]:
    """All three resource checks over one captured `pallas_call`."""
    kernel = kernel or call.name
    findings: List[Finding] = []

    # -- tiling ---------------------------------------------------------
    for view in call.specs:
        if view.block_shape is None or "vmem" not in view.memory_space:
            continue
        findings.extend(_check_tiling(
            view.block_shape, view.dtype, view.array_shape,
            f"{call.name}.{view.name} block {view.block_shape}",
            kernel))
    for shape, dtype in call.scratch:
        findings.extend(_check_tiling(
            shape, dtype, None, f"{call.name} scratch {shape}", kernel))

    # -- block-index bounds (+ pipelined-operand detection) -------------
    varies = [False] * len(call.specs)
    oob_seen = set()
    for gp in _grid_points(call.grid):
        for si, view in enumerate(call.specs):
            if view.block_shape is None or view.index_map is None:
                continue
            try:
                idx = view.index_map(*gp, *call.prefetch)
            except Exception as e:  # map itself is broken
                key = (si, "error")
                if key not in oob_seen:
                    oob_seen.add(key)
                    findings.append(Finding(
                        FindingKind.OOB_BLOCK_INDEX,
                        f"{call.name}.{view.name}: index map failed at "
                        f"grid point {gp}: {type(e).__name__}: {e}",
                        ref=view.name, kernel=kernel))
                continue
            for idx in (idx if view.manual else [idx]):
                idx = tuple(int(i) for i in (
                    idx if isinstance(idx, (tuple, list)) else (idx,)))
                if not varies[si]:
                    first = getattr(view, "_first_idx", None)
                    if first is None:
                        view._first_idx = idx
                    elif idx != first:
                        varies[si] = True
                for d, (i, bs) in enumerate(zip(idx, view.block_shape)):
                    hi = _cdiv(int(view.array_shape[d]), int(bs)) - 1
                    if 0 <= i <= hi:
                        continue
                    key = (si, d)
                    if key in oob_seen:
                        continue
                    oob_seen.add(key)
                    via = (" (index fed by a scalar-prefetch table — a "
                           "stale/corrupt page-table entry reads foreign "
                           "memory)" if call.prefetch else "")
                    findings.append(Finding(
                        FindingKind.OOB_BLOCK_INDEX,
                        f"{call.name}.{view.name}: block index {i} "
                        f"along dim {d} at grid point {gp} is outside "
                        f"[0, {hi}] for operand shape "
                        f"{view.array_shape} with block "
                        f"{view.block_shape}{via}",
                        ref=view.name, kernel=kernel))

    # -- VMEM footprint -------------------------------------------------
    total = 0
    for si, view in enumerate(call.specs):
        if view.block_shape is None or "vmem" not in view.memory_space:
            continue
        factor = 2 if (varies[si] and call.grid) else 1
        total += factor * block_bytes(view.block_shape, view.dtype)
    total += scratch_footprint_bytes(call.scratch)
    limit = call.vmem_limit or MOSAIC_DEFAULT_VMEM_LIMIT
    if total > limit:
        findings.append(Finding(
            FindingKind.VMEM_OVERFLOW,
            f"{call.name}: estimated VMEM working set {total} bytes "
            f"(pipelined blocks double-buffered + scratch) exceeds "
            f"the {limit}-byte limit",
            kernel=kernel))

    # -- SMEM prefetch tables -------------------------------------------
    pre_bytes = sum(int(t.size) * int(t.dtype.itemsize)
                    for t in call.prefetch)
    if pre_bytes > PREFETCH_SMEM_LIMIT:
        findings.append(Finding(
            FindingKind.SMEM_OVERFLOW,
            f"{call.name}: scalar-prefetch operands total {pre_bytes} "
            f"bytes, over the {PREFETCH_SMEM_LIMIT}-byte SMEM table "
            f"budget",
            kernel=kernel))
    return findings


def check_replay_resources(machine,
                           kernel: Optional[str] = None,
                           limit: Optional[int] = None) -> List[Finding]:
    """Resource findings from a comm-graph replay: per-(rank, grid
    step) peak of `run_scoped` VMEM scratch plus double-buffered
    `emit_pipeline` blocks, and tiling legality of each allocation."""
    from triton_distributed_tpu.utils.platform import COMM_VMEM_LIMIT

    limit = COMM_VMEM_LIMIT if limit is None else int(limit)
    findings: List[Finding] = []
    tiling_seen = set()
    worst = 0
    for replay in machine.resource_replays:
        total = 0
        for kind, shape, dtype in replay:
            factor = 2 if kind == "pipeline_block" else 1
            total += factor * block_bytes(shape, dtype)
            key = (kind, shape, np.dtype(dtype))
            if key not in tiling_seen:
                tiling_seen.add(key)
                findings.extend(_check_tiling(
                    shape, dtype, None, f"{kind} {shape}", kernel))
        worst = max(worst, total)
    if worst > limit:
        findings.append(Finding(
            FindingKind.VMEM_OVERFLOW,
            f"replayed VMEM working set peaks at {worst} bytes "
            f"(scoped scratch + double-buffered pipeline blocks), "
            f"over the {limit}-byte limit",
            kernel=kernel))
    return findings


# ---------------------------------------------------------------------------
# Registry + sweep
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class _ResourceEntry:
    name: str
    builder: Callable  # builder() -> List[CapturedCall]


_RESOURCE_REGISTRY: Dict[str, _ResourceEntry] = {}


def register_resource_kernel(name: str):
    """Decorator: register ``builder() -> List[CapturedCall]`` — the
    builder invokes the kernel host at representative shapes under
    `capture_pallas_calls` and returns the records.  Lives next to
    the `pallas_call` site, like the comm hooks."""

    def decorator(builder):
        if name in _RESOURCE_REGISTRY:
            raise ValueError(
                f"resource kernel {name!r} registered twice")
        _RESOURCE_REGISTRY[name] = _ResourceEntry(name, builder)
        return builder

    return decorator


def _load_resource_modules():
    """Import every module carrying resource hooks (the comm modules
    via the comm registry's loader, plus the pure-compute kernels)."""
    import importlib

    from triton_distributed_tpu.analysis.registry import (
        _load_kernel_modules)

    _load_kernel_modules()
    for mod in ("flash_attention", "matmul", "grouped_gemm",
                "quantized", "mla_decode"):
        importlib.import_module(
            f"triton_distributed_tpu.kernels.{mod}")


def all_resource_kernels() -> List[str]:
    _load_resource_modules()
    return sorted(_RESOURCE_REGISTRY)


def sweep_resources(names: Optional[Sequence[str]] = None,
                    mesh: Optional[Dict[str, int]] = None):
    """Resource-analyze the full kernel surface; yields
    (name, axis_sizes, findings).

    Comm-registered kernels are replayed on the abstract machine (their
    `run_scoped`/`emit_pipeline` footprint); capture-registered compute
    kernels run their builders.  `names`/`mesh` filter like the comm
    sweep (mesh only applies to comm entries; compute entries are
    single-chip and report an empty mesh)."""
    from triton_distributed_tpu.analysis.context import record_traces
    from triton_distributed_tpu.analysis.registry import (
        all_kernels, iter_specs)

    _load_resource_modules()
    comm_names = None
    if names:
        known = set(all_kernels())
        comm_names = [n for n in names if n in known]
    comm_iter = (iter_specs(comm_names, mesh)
                 if comm_names is None or comm_names else ())
    for name, axis_sizes, spec in comm_iter:
        machine = record_traces(spec.body, axis_sizes=spec.axis_sizes,
                                refs=spec.refs, sems=spec.sems,
                                grid=spec.grid)
        yield name, axis_sizes, check_replay_resources(machine,
                                                       kernel=name)
    import fnmatch
    for name in sorted(_RESOURCE_REGISTRY):
        if names and not any(fnmatch.fnmatch(name, pat) or name == pat
                             for pat in names):
            continue
        entry = _RESOURCE_REGISTRY[name]
        findings: List[Finding] = []
        for call in entry.builder():
            findings.extend(check_captured_call(call, kernel=name))
        yield name, {}, findings
