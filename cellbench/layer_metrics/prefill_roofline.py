"""Kernels / roofline, COMPUTE bound: the least time the chip could
take for the prompts admitted in the traced window — projections,
causal attention and the head's last position at each TRUE prompt
length (`model_math.prefill_flops`; padding to the bucket is not
useful work), over the chip's bf16 peak — as a share of the device
time the prefill programs took."""


def read(run):
    if run.trace is None:
        return None
    durs = run.module("prefill")
    admitted = run.admitted_in_trace()
    if not durs or not admitted:
        return None
    dims = run.spec.config
    chips = run.system.world
    # every admission is one prefill event; the window's edges can cut
    # one off either list, so compare means
    flops = sum(run.math.prefill_flops(dims, len(r.item.prompt), chips)
                for r in admitted) / len(admitted)
    least = flops / run.peaks["bf16_flops_per_s"]
    return 100.0 * least / (sum(durs) / len(durs))
