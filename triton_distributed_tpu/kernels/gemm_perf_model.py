"""Analytic GEMM performance model (roofline).

Reference: `python/triton_dist/kernels/nvidia/gemm_perf_model.py` (247
LoC) — `get_max_tensorcore_tflops:61`, `get_tflops_approx:126`, used to
balance communication vs compute resources.

TPU: per-generation MXU peak and HBM bandwidth; `estimate_gemm_time_us`
is the max of the compute and memory rooflines.  Overlap kernels use it
to decide whether a chunk's matmul hides a chunk's DMA (the decision
the reference makes by partitioning SMs between comm and compute).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    bf16_tflops: float
    int8_tops: float
    hbm_gbps: float


#: Keyed by the exact string `jax.Device.device_kind` returns (v5e
#: reports "TPU v5 lite", v5p "TPU v5", v6e "TPU v6 lite").  v5e from
#: Google Cloud's "TPU v5e" page: 197 TFLOP/s bf16, 393 TOP/s int8,
#: 819 GB/s HBM.
_V5E = ChipSpec(bf16_tflops=197.0, int8_tops=393.0, hbm_gbps=819.0)
_CHIP_TABLE = {
    "TPU v4": ChipSpec(bf16_tflops=275.0, int8_tops=275.0,
                       hbm_gbps=1228.0),
    "TPU v5 lite": _V5E,
    "TPU v5": ChipSpec(bf16_tflops=459.0, int8_tops=918.0,
                       hbm_gbps=2765.0),
    "TPU v6 lite": ChipSpec(bf16_tflops=918.0, int8_tops=1836.0,
                            hbm_gbps=1640.0),
    # The CPU backend runs the kernels in TPU interpret mode, which
    # simulates a v5 (`utils.platform._enable_cpu_simulation_shims`):
    # method selection there must pick what it would pick on the v5e.
    "cpu": _V5E,
}


def lookup_device_kind(table: dict, device=None):
    """``table[device.device_kind]`` — an unknown device is an error,
    never a default: a wrong peak silently skews every auto-select."""
    device = device or jax.devices()[0]
    kind = device.device_kind
    if kind not in table:
        raise KeyError(
            f"no perf-model entry for device_kind {kind!r} "
            f"(known: {sorted(table)}) — add its published peaks")
    return table[kind]


def get_chip_spec(device=None) -> ChipSpec:
    return lookup_device_kind(_CHIP_TABLE, device)


def get_max_mxu_tflops(dtype=jnp.bfloat16, device=None) -> float:
    spec = get_chip_spec(device)
    if jnp.dtype(dtype).itemsize == 1:
        return spec.int8_tops
    return spec.bf16_tflops


def estimate_gemm_time_us(m: int, n: int, k: int, dtype=jnp.bfloat16,
                          efficiency: float = 0.6, device=None) -> float:
    """max(compute, memory) roofline with an efficiency derate."""
    spec = get_chip_spec(device)
    itemsize = jnp.dtype(dtype).itemsize
    flops = 2.0 * m * n * k
    t_compute = flops / (get_max_mxu_tflops(dtype, device) * 1e12
                         * efficiency)
    nbytes = (m * k + k * n + m * n) * itemsize
    t_mem = nbytes / (spec.hbm_gbps * 1e9)
    return max(t_compute, t_mem) * 1e6


def gemm_is_compute_bound(m: int, n: int, k: int,
                          dtype=jnp.bfloat16, device=None) -> bool:
    spec = get_chip_spec(device)
    itemsize = jnp.dtype(dtype).itemsize
    intensity = (2.0 * m * n * k) / ((m * k + k * n + m * n) * itemsize)
    ridge = get_max_mxu_tflops(dtype, device) * 1e12 / (
        spec.hbm_gbps * 1e9)
    return intensity >= ridge
