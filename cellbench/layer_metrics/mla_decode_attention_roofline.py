"""Kernels / roofline, BANDWIDTH bound: the least time the chip could
take to read the latent rows of the live context positions of a traced
decode step — 576 numbers a token a layer, not the pool's lane padding
— over its HBM bandwidth, as a share of the device time
`mla_decode_paged` took in such a step."""

from cellbench import model_math_glm4_moe_lite as math
from cellbench.layer_metrics import mla_decode_attention_ms


def read(run):
    ms = mla_decode_attention_ms.read(run)
    steps = [s for s in run.traced_steps() if s[1] > 0] if ms else []
    if not steps:
        return None
    live = sum(s[4] for s in steps) / len(steps)
    least = (math.latent_bytes(run.spec.config, live)
             / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (ms * 1e-3)
