"""Backend detection and Pallas interpret-mode policy.

Kernels in this framework run in two modes:
- compiled (Mosaic) on real TPU devices;
- TPU interpret mode (`pltpu.InterpretParams`) everywhere else, which
  faithfully simulates VMEM/HBM spaces, DMA and cross-device semaphores
  on CPU — this is how the SPMD test harness exercises 8-device meshes
  on one host (SURVEY.md §4: the reference has no mock backends and
  tests only on real multi-GPU; on TPU we can do better).
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
from jax.experimental.pallas import tpu as pltpu

#: Persistent compile cache of a checkout that is given none from
#: outside: a FIXED path (the path is part of the cache key, so a
#: directory named by pid, time or `tempfile` never hits).
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR`` decides when it is set (JAX reads it
    itself — nothing is set here); otherwise the cache lives at
    `DEFAULT_COMPILE_CACHE_DIR`.  The layer loops in `models/qwen.py`
    are unrolled Python, so a server's start-up compiles depth ×
    (prefill buckets + decode + insert programs): every program is
    worth keeping, including the sub-second ones JAX skips by default.
    """
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          DEFAULT_COMPILE_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir


@functools.lru_cache(maxsize=None)
def backend_platform() -> str:
    return jax.default_backend()


def is_tpu() -> bool:
    return backend_platform() == "tpu"


def is_cpu() -> bool:
    return backend_platform() == "cpu"


def device_record() -> dict:
    """The device a result was taken on, as JAX reports it.  Every
    entry point that exists to measure prints this with each JSON
    line: a number without its device cannot be told from a CPU
    fallback's."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


@functools.lru_cache(maxsize=None)
def _enable_cpu_simulation_shims() -> None:
    """Make `pltpu.emit_pipeline` usable under interpret mode on CPU.

    The Mosaic software-pipeline helper asks the runtime for the TPU
    generation to pick DMA tilings even when interpreted; answer "v5"
    when simulating.  Test-harness shim only — never active on TPU.
    """
    from jax._src.pallas.mosaic import pipeline as _pipeline

    _orig = _pipeline._get_tpu_generation

    def _get_gen():
        try:
            return _orig()
        except ValueError:
            return 5

    _pipeline._get_tpu_generation = _get_gen

    # Deadlock fix for multi-device interpret simulation: stock
    # `io_callback_impl` does `device_put(args, cpu_device0)` for every
    # interpreter callback.  When device 0's execution thread is blocked
    # inside a kernel (e.g. a semaphore wait), a transfer onto device 0
    # queued by another device's callback can never complete → deadlock
    # (timing-dependent; bites any collective kernel).  The interpreter
    # callbacks are pure-host numpy code, so feed them host arrays
    # directly instead.
    import numpy as _np

    from jax._src import callback as _cb

    def _io_callback_impl_host(*args, result_avals, callback, sharding,
                               ordered):
        del result_avals, sharding, ordered
        np_args = tuple(_np.asarray(a) for a in args)
        import jax.tree_util as _tu

        return _tu.tree_map(_np.asarray, callback(*np_args))

    _cb.io_callback_impl = _io_callback_impl_host


#: Scoped-VMEM ceiling for Pallas kernels (Mosaic defaults to 16 MiB;
#: the traffic-minimising GEMM configs want big f32 accumulators,
#: and v5e/v5p have 128 MiB of VMEM).  Shared by matmul and the
#: fused comm kernels so a retune stays consistent.
SCOPED_VMEM_LIMIT = 100 * 1024 * 1024
COMM_VMEM_LIMIT = SCOPED_VMEM_LIMIT


def comm_compiler_params(collective_id: Optional[int], world_size: int):
    """CompilerParams for communication kernels.  Mosaic requires
    `collective_id` to be absent when the compiled kernel contains no
    cross-device barrier/collective — which is the case when
    world_size == 1 and all remote-DMA loops trace away."""
    if world_size <= 1 or collective_id is None:
        return pltpu.CompilerParams(has_side_effects=True,
                                    vmem_limit_bytes=COMM_VMEM_LIMIT)
    return pltpu.CompilerParams(has_side_effects=True,
                                collective_id=collective_id,
                                vmem_limit_bytes=COMM_VMEM_LIMIT)


def default_interpret(interpret: Optional[bool] = None):
    """Resolve an `interpret=` argument for pl.pallas_call.

    Returns False on TPU (compile with Mosaic), an InterpretParams
    instance elsewhere.  Pass an explicit bool/InterpretParams to
    override.
    """
    if interpret is None:
        interpret = not is_tpu()
    if interpret is False:
        return False
    _enable_cpu_simulation_shims()
    if interpret is True:
        return pltpu.InterpretParams()
    return interpret
