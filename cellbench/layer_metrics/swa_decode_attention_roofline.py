"""Kernels / roofline, BANDWIDTH bound: the least time the chip could
take to read what the window layers' decode kernels must — K and V of
each live row's last `sliding_window` tokens in every window layer
(`window_tokens_live` of the program's `serving.window` spans: tokens
inside the window, not the pages they lie in) — over its HBM bandwidth,
as a share of `swa_decode_attention_ms`."""

from cellbench import model_math_cohere2_moe as math
from cellbench import window_spans
from cellbench.layer_metrics import swa_decode_attention_ms


def read(run):
    ms = swa_decode_attention_ms.read(run)
    if ms is None:
        return None
    rows = window_spans.counted(run, "swa_decode_attention_roofline",
                                traced=True)
    if rows is None:
        return None
    least = (math.window_kv_bytes(
        run.spec.config, window_spans.mean(rows, "window_tokens_live"))
        / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (ms * 1e-3)
