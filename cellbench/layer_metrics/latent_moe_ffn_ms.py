"""Expert layer: device time one decode step spends in the latent
experts' two grouped GEMMs — the trace's rows named
`moe_decode_relu2_up` and `moe_decode_relu2_down` summed (all expert
layers), over the traced decode steps.  (Prefill runs the same kernels
under `moe_prefill_relu2_*`, so its rows are not in this sum.)  The
router, the packing, the latent's two projections and the shared expert
are XLA operations and are not in it."""

from cellbench import span_reader

KERNELS = ("moe_decode_relu2_up", "moe_decode_relu2_down")


def read(run):
    return span_reader.device_ms_per_decode_step(
        run, "latent_moe_ffn_ms", KERNELS)
