"""Kernels / roofline, BANDWIDTH bound: the least time the chip could
take to read the routed experts a traced decode step HIT (the
program's own count, `serving.moe`), over its HBM bandwidth, as a
share of the device time the two grouped GEMMs took in such a step
(`moe_ffn_ms`).  The router and the shared expert are XLA operations
outside that time, so their bytes are not in the numerator either
(they are in `sparse_decode_step_roofline`)."""

from cellbench import model_math_glm4_moe_lite as math
from cellbench import moe_spans
from cellbench.layer_metrics import moe_ffn_ms


def read(run):
    ms = moe_ffn_ms.read(run)
    if ms is None:
        return None
    rows = moe_spans.counted(run, "moe_ffn_roofline", traced=True)
    if rows is None:
        return None
    least = (math.expert_bytes(run.spec.config,
                               moe_spans.mean(rows, "experts_hit"))
             / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (ms * 1e-3)
