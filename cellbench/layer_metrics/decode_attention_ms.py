"""Kernels: device time one decode step spends in the paged decode
attention kernel — the trace's rows named `flash_decode_paged` summed
(all layers), over the traced decode steps, on the busiest chip."""

from cellbench import span_reader


def read(run):
    return span_reader.device_ms_per_decode_step(
        run, "decode_attention_ms", ("flash_decode_paged",))
