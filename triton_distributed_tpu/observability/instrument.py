"""Kernel-facing instrumentation helpers.

These are the hooks the collective/fused entry points call once per
traced specialization (see :mod:`.events` for the trace-time emission
model).  Each helper derives the per-rank ICI payload bytes and the
analytic perf-model estimate for the method actually chosen, so every
event carries an expectation the audit can later hold a measurement
against.
"""

from __future__ import annotations

from typing import Optional

from triton_distributed_tpu.observability.events import emit_kernel_event
from triton_distributed_tpu.observability.metrics import (
    observability_enabled,
)


def _itemsize(dtype) -> int:
    import jax.numpy as jnp
    return jnp.dtype(dtype).itemsize


def estimate_collective_us(op: str, payload_bytes: int, world: int,
                           method: Optional[str] = None,
                           sizes=None) -> Optional[float]:
    """Analytic estimate for a standalone collective.

    ``payload_bytes`` is the per-rank unit the perf model is
    parameterised by: the local shard for AG, the per-rank chunk for
    RS, the full input for AR.  ``sizes`` (torus axis sizes) selects
    the multi-lane torus model.
    """
    if world <= 1:
        return None
    from triton_distributed_tpu.kernels import comm_perf_model as cpm

    if sizes is not None and len(sizes) > 1:
        if op.startswith("all_reduce"):
            # AR over the torus = RS + AG on 1/world chunks.
            return 2 * cpm.estimate_torus_ag_time_us(
                max(payload_bytes // world, 1), sizes)
        return cpm.estimate_torus_ag_time_us(payload_bytes, sizes)
    if op.startswith(("all_gather", "reduce_scatter")):
        if method in ("push_all", "scatter_reduce"):
            return cpm.estimate_one_shot_time_us(payload_bytes, world)
        return cpm.estimate_all_gather_time_us(payload_bytes, world)
    if op.startswith("all_reduce"):
        if method == "one_shot":
            return cpm.estimate_one_shot_time_us(payload_bytes, world)
        if method == "two_shot":
            return cpm.estimate_two_shot_time_us(payload_bytes, world)
        if method == "chain":
            return cpm.estimate_chain_allreduce_time_us(payload_bytes,
                                                        world)
        return cpm.estimate_all_reduce_time_us(payload_bytes, world)
    return None


def collective_bytes_per_rank(op: str, payload_bytes: int, world: int,
                              method: Optional[str] = None) -> int:
    """ICI bytes *sent per rank*.  Ring AG/RS and one-shot push both
    ship (world-1) payload units; AR methods vary."""
    if world <= 1:
        return 0
    if op.startswith("all_reduce"):
        if method == "one_shot":
            return (world - 1) * payload_bytes
        if method == "chain":
            return 2 * payload_bytes
        # ring / torus / two_shot / xla: RS + AG on 1/world chunks.
        return 2 * (world - 1) * (payload_bytes // world)
    return (world - 1) * payload_bytes


#: Default hop pattern per method (the method *is* the schedule); the
#: emit sites override where the method name underdetermines routing
#: (torus lanes, hierarchical phases).  See observability/links.py for
#: the link-traversal semantics of each pattern.
_METHOD_HOPS = {
    "ring": "ring",
    "bidir_ring": "bidir_ring",
    "chain": "chain",
    "push_all": "all_pairs",
    "one_shot": "all_pairs",
    "two_shot": "all_pairs",
    "scatter_reduce": "all_pairs",
    "ll": "all_pairs",
    # XLA's collective on a torus runs a ring schedule; attributing it
    # as one keeps the link counters comparable across methods.
    "xla": "ring",
    "fused": "ring",
}


def hops_for_method(method) -> str:
    """Hop-pattern annotation for a method name (conservative "ring"
    for anything unknown so bytes are never dropped)."""
    return _METHOD_HOPS.get(
        method.value if hasattr(method, "value") else method, "ring")


def record_collective(op: str, *, axis, world: int, method, shape,
                      dtype, payload_bytes: int, sizes=None,
                      hops=None, axes=None, **extra):
    """Emit the launch-metadata event for a standalone collective.

    ``hops``: the kernel's hop-pattern annotation (defaults from the
    method); ``axes``/``sizes``: torus axis names and sizes for
    multi-axis events, so link attribution can rebuild the topology.
    """
    if not observability_enabled():
        return None
    method_s = method.value if hasattr(method, "value") else method
    if world > 1:
        extra["hops"] = hops or hops_for_method(method_s)
        if axes is not None and sizes is not None:
            extra["axes"] = [str(a) for a in axes]
            extra["sizes"] = [int(s) for s in sizes]
    return emit_kernel_event(
        op, kind="collective", method=method_s, axis=str(axis),
        world=world, shape=shape, dtype=dtype,
        bytes_moved=collective_bytes_per_rank(op, payload_bytes, world,
                                              method_s),
        estimate_us=estimate_collective_us(op, payload_bytes, world,
                                           method_s, sizes=sizes),
        payload_bytes=int(payload_bytes), **extra)


def estimate_overlap_gemm_us(op: str, m: int, n: int, k: int,
                             world: int, dtype,
                             method: Optional[str] = None
                             ) -> Optional[float]:
    """Analytic estimate for the fused overlap GEMMs.

    ``m`` is the per-rank row count (the AG shard for ag_gemm, the
    output chunk for gemm_rs).  Mirrors `choose_ll_or_fused`'s cost
    decomposition so the audit judges the kernel against the same
    model the method auto-selection used.
    """
    from triton_distributed_tpu.kernels import comm_perf_model as cpm
    from triton_distributed_tpu.kernels.gemm_perf_model import (
        estimate_gemm_time_us)

    if world <= 1:
        return estimate_gemm_time_us(m, n, k, dtype)
    is_ag = op.startswith("ag_gemm")
    chunk_bytes = m * (k if is_ag else n) * _itemsize(dtype)
    if method == "ll":
        if is_ag:
            return (cpm.estimate_one_shot_time_us(chunk_bytes, world)
                    + estimate_gemm_time_us(world * m, n, k, dtype))
        return (estimate_gemm_time_us(world * m, n, k, dtype)
                + cpm.estimate_one_shot_time_us(chunk_bytes, world))
    # fused ring (and the XLA composition, whose sequential AG+GEMM
    # the overlapped estimate lower-bounds).
    step_comm = (cpm.estimate_all_gather_time_us(chunk_bytes, world)
                 / max(world - 1, 1))
    t_overlap = world * max(estimate_gemm_time_us(m, n, k, dtype),
                            step_comm)
    if method == "xla":
        return (cpm.estimate_all_gather_time_us(chunk_bytes, world)
                + world * estimate_gemm_time_us(m, n, k, dtype))
    return t_overlap


#: (op, method) -> the name of the schedule a fused-GEMM program was
#: BUILT with, carried as ``extra["schedule"]`` beside ``method`` in
#: its launch event (table in `docs/observability.md`).
OVERLAP_GEMM_SCHEDULES = {
    ("ag_gemm", "ll"): "weights_ahead_of_gather",
    ("gemm_rs", "ll"): "scatter_behind_stream",
    ("ag_gemm", "fused"): "ring",
    ("gemm_rs", "fused"): "ring",
    ("ag_gemm", "xla"): "collective_then_matmul",
    ("gemm_rs", "xla"): "collective_then_matmul",
}


def record_overlap_gemm(op: str, *, axis, world: int, method, m: int,
                        n: int, k: int, dtype, config=None, hops=None,
                        **extra):
    """Emit the launch-metadata event for ag_gemm / gemm_rs (and the
    MoE fused epilogue, which passes its own flops/bytes via extra)."""
    if not observability_enabled():
        return None
    method_s = method.value if hasattr(method, "value") else method
    if (op, method_s) in OVERLAP_GEMM_SCHEDULES:
        extra["schedule"] = OVERLAP_GEMM_SCHEDULES[op, method_s]
    chunk_bytes = (m * (k if op.startswith("ag_gemm") else n)
                   * _itemsize(dtype))
    if world > 1:
        extra["hops"] = hops or hops_for_method(method_s)
    return emit_kernel_event(
        op, kind="fused_gemm", method=method_s, axis=str(axis),
        world=world, shape=(m, n, k), dtype=dtype,
        bytes_moved=(world - 1) * chunk_bytes if world > 1 else 0,
        flops=2 * world * m * n * k,
        estimate_us=estimate_overlap_gemm_us(op, m, n, k, world, dtype,
                                             method_s),
        config=config, payload_bytes=int(chunk_bytes), **extra)


def estimate_compute_us(flops: int, dtype, efficiency: float = 0.6
                        ) -> float:
    """Bare MXU-roofline time for ``flops`` (coarse: no memory term),
    for ops without an (m, n, k) shape (grouped/MoE pipelines)."""
    from triton_distributed_tpu.kernels.gemm_perf_model import (
        get_max_mxu_tflops)
    return flops / (get_max_mxu_tflops(dtype) * 1e12 * efficiency) * 1e6
