"""Kernels / roofline, BANDWIDTH bound (a read-modify-write of the
state: 6 operations a number moved): the least time the chip could take
to read and write the recurrent state of the rows LIVE in a traced
decode step (`live_slots` of `serving.state`) and their convolution
inputs (`model_math_nemotron_h.ssm_decode_bytes`), over its HBM
bandwidth, as a share of the device time `mamba2_decode_step` took in
such a step.  The convolution and the projections are XLA operations
outside that time; their bytes are small beside the state's (1%) and
stay in the numerator as the issue defines it."""

from cellbench import model_math_nemotron_h as math
from cellbench.layer_metrics import ssm_decode_ms, state_pool_live_peak


def read(run):
    ms = ssm_decode_ms.read(run)
    if ms is None:
        return None
    rows = state_pool_live_peak.counted(run, "ssm_decode_roofline",
                                        traced=True)
    if rows is None:
        return None
    live = sum(r["live_slots"] for r in rows) / len(rows)
    least = (math.ssm_decode_bytes(run.spec.config, live)
             / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (ms * 1e-3)
