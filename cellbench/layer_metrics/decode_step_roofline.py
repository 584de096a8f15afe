"""Kernels / roofline, BANDWIDTH bound: the least time the chip could
take for the traced decode steps — each chip's shard of the layer and
head weights once a step plus the KV of the live context positions
(`model_math.decode_step_bytes`), over the chip's HBM bandwidth — as a
share of the device time those steps took."""


def read(run):
    if run.trace is None:
        return None
    durs = run.module("decode")
    steps = [s for s in run.traced_steps() if s[1] > 0]
    if not durs or not steps:
        return None
    dims = run.spec.config
    chips = run.system.world
    live = sum(s[4] for s in steps) / len(steps)
    least = (run.math.decode_step_bytes(dims, live, chips)
             / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (sum(durs) / len(durs))
