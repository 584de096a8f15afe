"""End-to-end PREFILL throughput: Qwen3-0.6B-shaped model, full
serving stack, fused-Pallas layers vs plain-XLA layers at long
sequence lengths — the one serving phase that had no end-to-end
number (VERDICT r4 next #6).

Prefill is one ~10 ms+ dispatch at these shapes, so per-call slope
timing (`measure_ops`, chained calls, ABBA interleave) is adequate;
the figure of merit is prefill tokens/s.

Reference analogue: the e2e prefill recipes in `docs/e2e.md:30-123`.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))  # repo root

import argparse
import json
import statistics

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from triton_distributed_tpu.models import ModelConfig
from triton_distributed_tpu.models.qwen import Qwen3
from triton_distributed_tpu.utils.benchmarking import measure_ops
from triton_distributed_tpu.utils.platform import device_record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--seqs", type=int, nargs="*", default=[2048, 4096])
    ap.add_argument("--repeats", type=int, default=4)
    ap.add_argument("--layers", type=int, default=0)
    args = ap.parse_args()

    mesh = Mesh(np.array(jax.devices()), ("tp",))
    for s in args.seqs:
        cfg = ModelConfig.qwen3_0_6b()
        if args.layers:
            cfg.num_layers = args.layers
        cfg.max_seq_len = s + 8
        b = args.batch
        ids = jax.random.randint(jax.random.key(0), (b, s), 0,
                                 cfg.vocab_size)

        runners = []
        for mode in ("fused", "xla"):
            model = Qwen3(cfg, mesh, mode=mode)
            params = model.init_params(jax.random.key(1))
            prefill = jax.jit(model.make_prefill_fn())
            cache = model.create_cache(b, max_seq=cfg.max_seq_len)

            def run(ids_, params=params, prefill=prefill, cache=cache):
                logits, _ = prefill(params, ids_, cache)
                return logits

            runners.append(run)

        fused, xla = runners

        # chain the next call's ids on this call's logits (argmax of
        # one row keeps the mix cost negligible at these latencies)
        def chain(a, logits):
            nxt = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
            return ((a[0] + nxt - nxt),)

        ops = [fused, xla, fused]                    # ABBA bracket
        _, slopes = measure_ops(ops, (ids,), chain,
                                n1=3, repeats=args.repeats,
                                return_slopes=True)
        fused_pairs = [(x + y) / 2 for x, y in zip(slopes[0],
                                                   slopes[-1])]
        t_fused = statistics.median(slopes[0] + slopes[-1])
        ratios = sorted(t / f for t, f in zip(slopes[1], fused_pairs))
        pinned = b == 1 and not args.layers
        print(json.dumps({
            "bench": "e2e_prefill", "device": device_record(),
            "B": b, "S": s,
            "layers": cfg.num_layers,
            "regime": (f"pinned-B1-L{cfg.num_layers}-S{s}" if pinned
                       else "custom"),
            "ms": round(t_fused * 1e3, 2),
            "prefill_tokens_per_s": round(b * s / t_fused, 0),
            "vs_xla": round(statistics.median(ratios), 3),
            "vs_xla_range": [round(ratios[0], 3), round(ratios[-1], 3)],
            # Unlike decode, prefill modes differ even at world=1: the
            # xla mode runs dense S² attention, the fused mode our
            # Pallas flash — so the ratio is real (and grows with S).
            "note": "xla_mode_uses_dense_attention",
        }), flush=True)


if __name__ == "__main__":
    main()
