"""State-space layers: device time one decode step spends in the
Mamba-2 state kernel — the trace's rows named `mamba2_decode_step`
summed (all state-space layers), over the traced decode steps."""

from cellbench import span_reader


def read(run):
    return span_reader.device_ms_per_decode_step(
        run, "ssm_decode_ms", ("mamba2_decode_step",))
