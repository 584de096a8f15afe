"""Kernels: device time one decode step spends in the WINDOW layers'
paged decode attention — the trace's rows named `swa_decode_paged`
summed (all window layers), over the traced decode steps.  (The full
layers' rows, `flash_decode_paged`, are `decode_attention_ms`.)"""

from cellbench import span_reader

KERNELS = ("swa_decode_paged",)


def read(run):
    return span_reader.device_ms_per_decode_step(
        run, "swa_decode_attention_ms", KERNELS)
