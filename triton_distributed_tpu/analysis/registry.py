"""Registration of shipped kernels with the comm-graph sanitizer.

Each comm kernel module registers one builder per kernel variant it
ships (a *registration hook*): the builder receives a mesh shape
(dict axis -> size) and returns a :class:`KernelSpec` describing the
kernel body and its ref/semaphore layout — the same information the
module's `pl.pallas_call` site encodes in `out_shape`/`scratch_shapes`.
The CLI (`python -m triton_distributed_tpu.analysis`) sweeps every
registered kernel across its representative mesh shapes and fails on
any finding; tier-1 runs that sweep (`tests/test_analysis.py`).

Keeping the hook next to the `pallas_call` site is deliberate: when a
kernel's scratch layout changes, the spec that the sanitizer replays
is one screen away, and a drifted spec fails the sweep loudly (a
missing semaphore shows up as an unknown-name wait, a wrong shape as a
ledger imbalance).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

__all__ = [
    "KernelSpec",
    "RefSpec",
    "SemSpec",
    "all_kernels",
    "get_kernel",
    "iter_specs",
    "register_comm_kernel",
    "single_axis",
]


def single_axis(axis_sizes: Dict[str, int]) -> Tuple[str, int]:
    """(axis, world) of a single-axis mesh; ValueError otherwise (so a
    multi-axis `--mesh` override skips single-axis kernels)."""
    if len(axis_sizes) != 1:
        raise ValueError(f"single-axis kernel, got mesh {axis_sizes}")
    (axis, world), = axis_sizes.items()
    return axis, int(world)


@dataclasses.dataclass(frozen=True)
class RefSpec:
    """One HBM ref (input, output or comm buffer) of the kernel.

    `value`: optional concrete contents; reads under analysis return
    it (zeros otherwise).  Provide it for scalars that steer the
    communication pattern (e.g. a broadcast root in SMEM).
    """

    name: str
    shape: Tuple[int, ...]
    dtype: object = np.float32
    value: Optional[object] = None


@dataclasses.dataclass(frozen=True)
class SemSpec:
    """One semaphore scratch (scalar or shaped array)."""

    name: str
    shape: Tuple[int, ...] = ()


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """Everything the sanitizer needs to replay one kernel variant."""

    name: str
    body: Callable            # body(*refs, *sems)
    axis_sizes: Dict[str, int]
    refs: Sequence[RefSpec]
    sems: Sequence[SemSpec]
    grid: Tuple[int, ...] = ()


@dataclasses.dataclass(frozen=True)
class _Entry:
    name: str
    builder: Callable         # builder(axis_sizes: dict) -> KernelSpec
    meshes: Tuple[Dict[str, int], ...]


_REGISTRY: Dict[str, _Entry] = {}


def register_comm_kernel(name: str, meshes: Sequence[Dict[str, int]]):
    """Decorator: register `builder(axis_sizes) -> KernelSpec` under
    `name`, to be swept at each mesh shape in `meshes`."""
    meshes = tuple(dict(m) for m in meshes)

    def decorator(builder):
        if name in _REGISTRY:
            raise ValueError(f"analysis kernel {name!r} registered twice")
        _REGISTRY[name] = _Entry(name, builder, meshes)
        return builder

    return decorator


def _load_kernel_modules():
    """Import every kernels module so registration hooks run."""
    import importlib

    for mod in (
        "allgather",
        "allgather_gemm",
        "allgather_group_gemm",
        "allreduce",
        "common_ops",
        "flash_decode",
        "gemm_reduce_scatter",
        "hierarchical",
        "low_latency_all_to_all",
        "low_latency_allgather",
        "moe_reduce_rs",
        "reduce_scatter",
        "sp_ag_attention",
        "torus",
    ):
        importlib.import_module(f"triton_distributed_tpu.kernels.{mod}")


def all_kernels() -> List[str]:
    _load_kernel_modules()
    return sorted(_REGISTRY)


def get_kernel(name: str) -> _Entry:
    _load_kernel_modules()
    return _REGISTRY[name]


def iter_specs(names: Optional[Sequence[str]] = None,
               mesh: Optional[Dict[str, int]] = None):
    """Yield (kernel name, axis_sizes, KernelSpec) over the sweep.

    `names`: restrict to these kernels (default: all registered).
    `mesh`: replace each kernel's representative meshes with this one
    (skipping kernels whose builder rejects it by raising ValueError).

    ValueError is tolerated ONLY under a `mesh` override: a kernel's
    own representative meshes must always build — a builder error
    there propagates, so a regression cannot silently shrink the
    tier-1 sweep (the "broken import shrinking the suite" failure
    mode the gate exists to prevent).
    """
    _load_kernel_modules()
    for name in (names or sorted(_REGISTRY)):
        entry = _REGISTRY[name]
        if mesh is not None:
            try:
                spec = entry.builder(dict(mesh))
            except ValueError:
                continue  # mesh shape not applicable to this kernel
            yield name, dict(mesh), spec
        else:
            for axis_sizes in entry.meshes:
                yield name, dict(axis_sizes), entry.builder(
                    dict(axis_sizes))
