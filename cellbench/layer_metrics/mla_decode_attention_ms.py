"""Kernels: device time one decode step spends in the latent decode
attention kernel — the trace's rows named `mla_decode_paged` summed
(all layers), over the traced decode steps."""

from cellbench import span_reader


def read(run):
    return span_reader.device_ms_per_decode_step(
        run, "mla_decode_attention_ms", ("mla_decode_paged",))
