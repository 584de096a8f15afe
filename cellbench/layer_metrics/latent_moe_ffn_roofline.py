"""Kernels / roofline, BANDWIDTH bound: the least time the chip could
take to read the latent experts a traced decode step HIT (the program's
own count, `serving.moe`; an expert is two matrices of `moe_latent_size`
x `moe_intermediate_size`), over its HBM bandwidth, as a share of the
device time the two grouped GEMMs took in such a step
(`latent_moe_ffn_ms`).  The router, the latent's projections and the
shared expert are XLA operations outside that time, so their bytes are
not in the numerator either (they are in `ssm_decode_step_roofline`)."""

from cellbench import model_math_nemotron_h as math
from cellbench import moe_spans
from cellbench.layer_metrics import latent_moe_ffn_ms


def read(run):
    ms = latent_moe_ffn_ms.read(run)
    if ms is None:
        return None
    rows = moe_spans.counted(run, "latent_moe_ffn_roofline", traced=True)
    if rows is None:
        return None
    least = (math.expert_bytes(run.spec.config,
                               moe_spans.mean(rows, "experts_hit"))
             / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (ms * 1e-3)
