"""Kernels, across chips: device time one decode step spends in the
kernels that move data between chips — the Pallas AG-GEMM / GEMM-RS
kernels (`ag_gemm_*`, `gemm_rs_*`: transfer and matmul fused, so this
is their TOTAL time, not the exposed part) and XLA's own collectives
— summed over the trace's rows on the busiest chip, over the traced
decode steps.  The few prefills of the window run the same kernels
and are in the sum."""

from cellbench import span_reader

PREFIXES = ("ag_gemm_", "gemm_rs_", "all-reduce", "all-gather",
            "reduce-scatter", "collective-permute")


def read(run):
    return span_reader.device_ms_per_decode_step(
        run, "comm_gemm_ms", PREFIXES)
