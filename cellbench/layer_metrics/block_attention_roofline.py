"""Kernels / roofline, BANDWIDTH bound: the least time the chip could
take to read the K/V that the rows of a traced block pass attend — the
live context positions (each row reads its committed prefix and its
own block: at least what it has been delivered) x 14 336 B
(`model_math_sdar_moe.attention_bytes`) — over its HBM bandwidth, as a
share of the device time `flash_decode_paged` took in such a pass at
8 x block query rows a KV head."""

from cellbench import model_math_sdar_moe as math
from cellbench.layer_metrics import decode_attention_ms


def read(run):
    ms = decode_attention_ms.read(run)
    steps = [s for s in run.traced_steps() if s[1] > 0] if ms else []
    if not steps:
        return None
    live = sum(s[4] for s in steps) / len(steps)
    least = (math.attention_bytes(run.spec.config, live)
             / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (ms * 1e-3)
