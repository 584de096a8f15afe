"""triton_distributed_tpu — a TPU-native framework for compute–communication
overlapping kernels.

A from-scratch re-design (NOT a port) of the capabilities of ByteDance's
Triton-distributed (reference: /root/reference) in idiomatic JAX/XLA/Pallas:

- device-visible one-sided communication + signal/wait primitives
  (NVSHMEM's role, played here by Pallas async remote DMA + semaphores over
  ICI; XLA collectives over DCN) — :mod:`triton_distributed_tpu.language`
- a library of overlap kernels: AllGather-GEMM, GEMM-ReduceScatter,
  AllReduce, low-latency AllGather, low-latency MoE AllToAll (EP
  dispatch/combine), grouped-GEMM MoE overlap, sequence-parallel
  allgather-attention, distributed flash-decode —
  :mod:`triton_distributed_tpu.kernels`
- tensor-parallel model layers (MLP/Attention), EP and SP layers —
  :mod:`triton_distributed_tpu.layers`
- a Qwen3-style inference engine with fully-compiled decode —
  :mod:`triton_distributed_tpu.models`
- a distributed contextual autotuner, AOT export tooling, SPMD test and
  benchmark harness — :mod:`triton_distributed_tpu.autotuner`,
  :mod:`triton_distributed_tpu.tools`

Parity map against the reference lives in SURVEY.md at the repo root.
"""

__version__ = "0.1.0"

from triton_distributed_tpu.utils.platform import configure_compile_cache

# The one place the compile cache is placed: every entry point
# (server, benches, chip_smoke.py, tests_tpu) imports the package.
configure_compile_cache()

from triton_distributed_tpu.parallel.mesh import (  # noqa: F401
    MeshContext,
    get_mesh_context,
    initialize_distributed,
    make_mesh,
)
from triton_distributed_tpu.utils.debug import dist_print  # noqa: F401
from triton_distributed_tpu.utils.testing import (  # noqa: F401
    assert_allclose,
    perf_func,
)
