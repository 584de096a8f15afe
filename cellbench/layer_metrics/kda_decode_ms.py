"""Linear attention: device time one decode step spends in the
delta-rule state kernel — the trace's rows named `kda_decode_step`
summed (all delta-rule layers), over the traced decode steps."""

from cellbench import span_reader


def read(run):
    return span_reader.device_ms_per_decode_step(
        run, "kda_decode_ms", ("kda_decode_step",))
