"""Expert layer: device time one decode step spends in the routed
experts' two grouped GEMMs — the trace's rows named
`moe_decode_gate_up` and `moe_decode_down` summed (all sparse layers),
over the traced decode steps.  (Prefill runs the same kernels under
`moe_prefill_*`, so its rows are not in this sum.)  The router, the
packing and the shared expert are XLA operations and are not in it."""

from cellbench import span_reader

KERNELS = ("moe_decode_gate_up", "moe_decode_down")


def read(run):
    return span_reader.device_ms_per_decode_step(run, "moe_ffn_ms",
                                                 KERNELS)
