"""Driver benchmark: prints ONE JSON line.

Measures the flagship AG-GEMM op at the reference's headline hidden
size (7168, BASELINE.md) on the available chip(s), with the
`contextual_autotune` tuner selecting the method (XLA vs fused Pallas)
and MXU block config — the production path, not a hardcoded config.

Timing methodology: each sample dispatches N dependence-chained calls
with a single trailing fetch, and the per-call latency is the slope
between N1 and N2 samples: t = (T(N2) - T(N1)) / (N2 - N1), which
removes every fixed per-sample cost (dispatch ramp, the fetch) exactly.
ROADMAP D11 replaces this harness with plain windows ended by
`block_until_ready` (chip_smoke.py checks that it blocks) plus kernel
times from the profiler trace.

The JSON line carries the device it ran on.
"""

import functools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from triton_distributed_tpu.utils.platform import device_record

M_TOTAL, K, N_TOTAL = 4096, 7168, 7168


def make_chain(k):
    """Feed an op's (M, N) output back into its (M, k) input — the
    dependence chain used by both the tuner and the final A/B."""
    return jax.jit(
        lambda x, out: (out[:, :k] * jnp.bfloat16(1e-3)
                        + x * jnp.bfloat16(0.5)).astype(jnp.bfloat16))


def measure_pair(fs, a, b, k, n1=20, n2=220, repeats=8):
    """Per-call latency of each jitted `f(a, b) -> (M, N)` in `fs` by
    two-point fit, with the ops' samples interleaved in time so slow
    drift (chip clocks, host load) hits all ops equally.  Calls are
    dependence-chained through the output so the device queue can't
    collapse them.

    The fetch cost fluctuates by tens of ms, so (a) the call-count gap
    is large enough that the slope denominator (~n2-n1 calls of device
    work) swamps it, and (b) the slope is computed *per repeat* from
    the adjacent (n1, n2) pair — minutes-scale drift then cancels
    within each repeat — and the median of the per-repeat slopes is
    returned (median-of-slopes, not slope-of-medians: the latter mixes
    samples taken far apart in time).

    Returns (median_slopes, per_repeat_slopes).  For A/B ratios use
    per-repeat pairing (`ratio_vs_last`): ratios of slopes measured
    adjacently in time are far more drift-robust than the ratio of two
    medians — a ~10% drift across the run otherwise lands entirely in
    one op's median."""
    import statistics

    chain = make_chain(k)

    def total(f, n_calls):
        t0 = time.perf_counter()
        x = a
        for _ in range(n_calls):
            x = chain(x, f(x, b))
        np.asarray(x[0, 0])  # fence: forces full queue drain
        return time.perf_counter() - t0

    for f in fs:
        total(f, 2)  # warm every jit
    slopes = [[] for _ in fs]
    for _ in range(repeats):
        for sl, f in zip(slopes, fs):
            t1 = total(f, n1)
            t2 = total(f, n2)
            sl.append(max((t2 - t1) / (n2 - n1), 1e-9))
    return [statistics.median(sl) for sl in slopes], slopes


def ratio_vs_last(per_repeat):
    """Median of per-repeat (last_op / op) slope ratios, one list per
    op (the last op is the baseline)."""
    import statistics
    base = per_repeat[-1]
    return [statistics.median(b / t for b, t in zip(base, sl))
            for sl in per_repeat[:-1]]


def _regime_prefill(mesh, world):
    """Autotuned fused AG-GEMM at the reference's headline shape."""
    from triton_distributed_tpu.autotuner import ContextualAutotuner
    from triton_distributed_tpu.kernels.allgather_gemm import (
        AllGatherGEMMContext,
        ag_gemm,
        ag_gemm_nonoverlap,
    )
    from triton_distributed_tpu.kernels.matmul import (
        MatmulConfig,
        matmul_config_space,
    )
    from triton_distributed_tpu.ops import shard_map_op

    m_loc = M_TOTAL // world
    n_loc = N_TOTAL // world
    a = jax.random.normal(jax.random.key(0), (M_TOTAL, K)).astype(jnp.bfloat16)
    b = jax.random.normal(jax.random.key(1), (K, N_TOTAL)).astype(jnp.bfloat16)
    specs = dict(in_specs=(P("tp", None), P(None, "tp")),
                 out_specs=P(None, "tp"))
    jit_cache = {}

    def fused_for(config):
        f = jit_cache.get(config)
        if f is None:
            method, mcfg = config
            ctx = AllGatherGEMMContext(
                axis="tp", world_size=world, method=method,
                gemm=mcfg or MatmulConfig())
            f = jax.jit(shard_map_op(
                functools.partial(ag_gemm, ctx=ctx), mesh, **specs))
            jit_cache[config] = f
        return f

    baseline = jax.jit(shard_map_op(
        functools.partial(ag_gemm_nonoverlap, axis="tp"), mesh, **specs))

    # Autotune the production op's MXU block config (the reference's
    # contextual_autotune over triton.Config spaces); the fused-vs-XLA
    # method A/B happens below with drift-robust interleaved sampling.
    # The fused kernel's inner GEMM runs per-chunk at m = m_loc (at
    # world == 1 m_loc is the full M), so resolve the space there.
    candidates = [("fused", c)
                  for c in matmul_config_space(m_loc, n_loc, K)]

    def op(a, b, *, config):
        return fused_for(config)(a, b)

    tune_chain = make_chain(K)

    # iters=40 -> samples of 40 vs 240 chained calls: ~0.6 s of device
    # work per sample, large enough to swamp the fetch-cost jitter;
    # chaining keeps only one output buffer live.  The disk cache
    # (keyed by device kind + shapes, invalidated when the candidate
    # list changes) skips re-tuning on repeat runs; the final A/B
    # below still measures the finalists fresh every run.
    tuner = ContextualAutotuner(op, candidates, iters=40,
                                chain=lambda out, x, w: (tune_chain(x, out), w),
                                cache_path=".autotune_cache.json")
    tuner(a, b)  # populates cache + ranking
    ranking = next(iter(tuner.cache.values())).ranking
    finalists = [cfg for _, cfg in ranking[:2]]

    # Final A/B with drift-robust interleaved sampling over the top-2
    # tuner finalists (their margin is within tuner noise) + baseline.
    times, per_repeat = measure_pair(
        [fused_for(c) for c in finalists] + [baseline], a, b, K)
    ratios = ratio_vs_last(per_repeat)
    t_fused, ratio, best = max(
        zip(times[:-1], ratios, finalists), key=lambda p: p[1])
    flops = 2 * M_TOTAL * K * N_TOTAL
    detail = (f"autotuned {best[1].block_m}x{best[1].block_n}x"
              f"{best[1].block_k}, {flops / t_fused / 1e12:.1f} TFLOP/s")
    return t_fused, ratio, detail


def _regime_decode_ll(mesh, world, m=16):
    """The serving hot path at decode rows: low-latency ag_gemm (one
    Pallas kernel, B streamed once) vs the XLA composition.

    A ~100 µs op is below the host's per-dispatch floor (each chained
    call is 2 dispatches), so per-call timing reads the floor, not the
    op.  Chain iterations INSIDE one jitted scan instead
    (`measure_ops_scanned`), ABBA-interleaved."""
    from triton_distributed_tpu.kernels.allgather_gemm import (
        AllGatherGEMMContext,
        ag_gemm,
        ag_gemm_nonoverlap,
    )
    from triton_distributed_tpu.ops import shard_map_op
    from triton_distributed_tpu.utils.benchmarking import (
        feedback_mix,
        measure_ops_scanned,
    )

    a = jax.random.normal(jax.random.key(2), (m, K)).astype(jnp.bfloat16)
    b = jax.random.normal(jax.random.key(3), (K, N_TOTAL)).astype(jnp.bfloat16)
    specs = dict(in_specs=(P("tp", None), P(None, "tp")),
                 out_specs=P(None, "tp"))
    ctx = AllGatherGEMMContext(axis="tp", world_size=world, method="ll")
    ll = shard_map_op(functools.partial(ag_gemm, ctx=ctx), mesh, **specs)
    baseline = shard_map_op(
        functools.partial(ag_gemm_nonoverlap, axis="tp"), mesh, **specs)
    mix = lambda args, out: (feedback_mix(args[0], out), args[1])
    import statistics
    # ABBA within each repeat so first-order drift cancels; pair the
    # slopes per repeat (adjacent in time), never ratio two medians.
    # The two ops tie by construction at world=1 (both stream B once,
    # no comm) — 32 inner iterations x 8 repeats tightens the paired
    # ratio to ~±0.5% so the min-headline doesn't wobble on noise.
    _, slopes = measure_ops_scanned(
        [ll, baseline, baseline, ll], (a, b), mix,
        n_inner=32, repeats=8, return_slopes=True)
    pair_ratios = [(b1 + b2) / (l1 + l2)
                   for l1, b1, b2, l2 in zip(*slopes)]
    ratio = statistics.median(pair_ratios)
    t_ll = statistics.median(slopes[0] + slopes[3])
    # At world=1 both ops stream B exactly once with no comm — a tie
    # by construction; the measured ratio (±1%) bounds harness noise.
    # The ll path's win (one-shot AG overlapped into the single-pass
    # GEMM) exists only at world > 1.
    tie = " (ties by construction at world=1)" if world <= 1 else ""
    return t_ll, ratio, f"M={m} ll path{tie}"


def _regime_flash_decode(mesh, world, s=8192):
    """Serving decode attention: our flash_decode kernel vs its
    STRONGEST available baselines — JAX's public Pallas
    paged-attention decode kernel and the dense XLA GQA decode —
    taking the per-repeat MIN of the two as the denominator.  Unlike
    decode_ll this regime has a real numerator at world=1 (the kernel
    either beats the strongest public decode kernel or it doesn't), so
    it carries signal in the min-headline (VERDICT r3 next #5)."""
    import statistics

    from jax.experimental.pallas.ops.tpu.paged_attention import (
        paged_attention)

    from triton_distributed_tpu.kernels.flash_decode import flash_decode
    from triton_distributed_tpu.utils.benchmarking import (
        feedback_mix,
        measure_ops_scanned,
    )

    b, h, hkv, d = 8, 32, 8, 128
    q = (jax.random.normal(jax.random.key(6), (b, h, d)) / 4
         ).astype(jnp.bfloat16)
    kc = (jax.random.normal(jax.random.key(7), (b, hkv, s, d)) / 4
          ).astype(jnp.bfloat16)
    vc = (jax.random.normal(jax.random.key(8), (b, hkv, s, d)) / 4
          ).astype(jnp.bfloat16)
    kv_len = jnp.full((b,), s, jnp.int32)

    page_size = 256
    pages_per_seq = s // page_size
    k_pages = kc.transpose(1, 0, 2, 3).reshape(
        hkv, b * pages_per_seq, page_size, d)
    v_pages = vc.transpose(1, 0, 2, 3).reshape(
        hkv, b * pages_per_seq, page_size, d)
    page_indices = jnp.arange(b * pages_per_seq, dtype=jnp.int32
                              ).reshape(b, pages_per_seq)
    scale = d ** -0.5

    def ours(q_, kc_, vc_, kv_len_, *_):
        return flash_decode(q_, kc_, vc_, kv_len_)[0]

    def paged(q_, kc_, vc_, kv_len_, k_pages_, v_pages_, pidx_):
        return paged_attention(q_ * scale, k_pages_, v_pages_,
                               kv_len_, pidx_,
                               pages_per_compute_block=4)

    def xla_decode(q_, kc_, vc_, kv_len_, *_):
        g = h // hkv
        qg = q_.reshape(b, hkv, g, d).astype(jnp.float32)
        sc = jnp.einsum("bkgd,bksd->bkgs", qg,
                        kc_.astype(jnp.float32)) * scale
        mask = jnp.arange(s)[None, :] < kv_len_[:, None]
        sc = jnp.where(mask[:, None, None, :], sc, -1e30)
        p = jax.nn.softmax(sc, axis=-1)
        out = jnp.einsum("bkgs,bksd->bkgd", p, vc_.astype(jnp.float32))
        return out.reshape(b, h, d).astype(q_.dtype)

    mix = lambda args, out: (feedback_mix(args[0], out),) + args[1:]
    # ABBA: ours brackets the baselines within each repeat so drift
    # cancels in the per-repeat pairing.
    _, slopes = measure_ops_scanned(
        [ours, paged, xla_decode, ours],
        (q, kc, vc, kv_len, k_pages, v_pages, page_indices), mix,
        n_inner=16, repeats=8, return_slopes=True)
    pair_ratios = [min(tp, tx) / ((o1 + o2) / 2)
                   for o1, tp, tx, o2 in zip(*slopes)]
    ratio = statistics.median(pair_ratios)
    t_ours = statistics.median(slopes[0] + slopes[3])
    kv_gbps = 2 * b * hkv * s * d * 2 / t_ours / 1e9
    return (t_ours, ratio,
            f"S={s} vs min(paged, xla) ({kv_gbps:.0f} GB/s KV)")


def _regime_moe(mesh, world):
    """MoE epilogue: `moe_reduce_rs_fused` (ragged-packed grouped
    down-GEMM with the topk-weighted combine folded into the epilogue)
    vs the XLA composition a user would otherwise run (grouped einsum
    + gather combine), at the weight-streaming-bound decode shape
    `bench_moe` profiles.  VERDICT r5 flagged this path at 0.52–0.69×
    XLA — putting it in the headline min makes the gate SEE the
    weakest regime instead of averaging it away: the headline can no
    longer improve while MoE stays below 1.0."""
    import statistics

    from triton_distributed_tpu.kernels import moe_utils
    from triton_distributed_tpu.kernels.moe_reduce_rs import (
        MoEReduceRSContext,
        moe_reduce_rs_fused,
    )
    from triton_distributed_tpu.ops import shard_map_op
    from triton_distributed_tpu.utils.benchmarking import (
        feedback_mix,
        measure_ops_scanned,
    )

    e, cap, mc, k, n, topk = 64, 128, 2048, 2048, 1408, 2
    key = jax.random.key(9)
    buckets = (jax.random.normal(key, (1, e, cap, k)) / 8
               ).astype(jnp.bfloat16)
    wdown = (jax.random.normal(jax.random.fold_in(key, 1), (e, k, n))
             / 8).astype(jnp.bfloat16)
    ids = jax.random.randint(jax.random.fold_in(key, 2), (mc, topk),
                             0, e)
    tw = jax.nn.softmax(jax.random.normal(
        jax.random.fold_in(key, 3), (mc, topk)), axis=-1)
    plan = moe_utils.plan_chunks(ids, tw, 1, e, cap,
                                 dtype=jnp.bfloat16)
    cmatb = plan.combine_blocks

    ctx = MoEReduceRSContext(axis="tp", world_size=world,
                             num_experts=e, topk=topk)

    def fused(bk, w_, cm):
        return shard_map_op(
            lambda b_, ww, c_: moe_reduce_rs_fused(
                b_, ww, plan._replace(combine_blocks=c_), ctx),
            mesh, in_specs=(P(), P(), P()), out_specs=P())(bk, w_, cm)

    def xla(bk, w_, cm):
        part = jnp.einsum("eck,ekn->ecn", bk[0], w_,
                          preferred_element_type=jnp.float32
                          ).astype(bk.dtype)
        return moe_utils.combine_tokens(part, ids,
                                        plan.slot_of_pair[0], tw)

    def mix(a, out):
        return (feedback_mix(a[0], out[None, None]), a[1], a[2])

    # ABBA: ours brackets the baseline within each repeat so drift
    # cancels in the per-repeat pairing (same harness as
    # flash_decode / decode_ll).
    _, slopes = measure_ops_scanned(
        [fused, xla, fused], (buckets, wdown, cmatb), mix,
        n_inner=16, repeats=8, return_slopes=True)
    pair_ratios = [x / ((f1 + f2) / 2)
                   for f1, x, f2 in zip(*slopes)]
    ratio = statistics.median(pair_ratios)
    t_fused = statistics.median(slopes[0] + slopes[2])
    flops = 2 * e * cap * k * n + 2 * e * mc * cap * n
    return (t_fused, ratio,
            f"E={e} cap={cap} vs XLA "
            f"({flops / t_fused / 1e12:.1f} TFLOP/s)")


def _regime_w8a8(mesh, world):
    """Quantized inference (beyond-reference capability): int8 fused
    AG-GEMM vs the bf16 XLA composition a user would otherwise run."""
    from triton_distributed_tpu.kernels.allgather_gemm import (
        AllGatherGEMMContext,
        ag_gemm_nonoverlap,
        ag_gemm_w8a8,
    )
    from triton_distributed_tpu.kernels.quantized import quantize_sym
    from triton_distributed_tpu.ops import shard_map_op

    a = jax.random.normal(jax.random.key(4), (M_TOTAL, K)).astype(jnp.bfloat16)
    b = jax.random.normal(jax.random.key(5), (K, N_TOTAL)).astype(jnp.bfloat16)
    b_q, b_s = quantize_sym(b, axis=0)
    ctx = AllGatherGEMMContext(axis="tp", world_size=world)

    q_op = jax.jit(shard_map_op(
        lambda aa, bq, bs: ag_gemm_w8a8(aa, bq, bs, ctx), mesh,
        in_specs=(P("tp", None), P(None, "tp"), P("tp")),
        out_specs=P(None, "tp")))
    baseline = jax.jit(shard_map_op(
        functools.partial(ag_gemm_nonoverlap, axis="tp"), mesh,
        in_specs=(P("tp", None), P(None, "tp")), out_specs=P(None, "tp")))

    # The quantized weights ride as RUNTIME ARGUMENTS of the jitted
    # q_op (the outer adapter is plain Python): a jitted closure over
    # b_q would embed ~50 MB as compile-time constants.
    times, per_repeat = measure_pair(
        [lambda x, w: q_op(x, b_q, b_s), baseline], a, b, K)
    ratio = ratio_vs_last(per_repeat)[0]
    tops = 2 * M_TOTAL * K * N_TOTAL / times[0] / 1e12
    return times[0], ratio, f"{tops:.0f} TOPS int8 vs bf16 XLA"


def record_regimes(regimes, noise_bound, world):
    """Route the regime measurements through the metrics registry so
    the BENCH line, the flight recorder and a metrics export all carry
    the same numbers; attach perf-model estimates where one exists and
    run the audit over them.  TDT_METRICS_EXPORT=<path> additionally
    writes the full registry snapshot."""
    import os

    from triton_distributed_tpu.observability import (
        audit_events, emit_kernel_event, estimate_overlap_gemm_us,
        get_registry, observability_enabled)

    if not observability_enabled():
        return
    gemm_est = {
        "prefill_fused": ("ag_gemm", M_TOTAL // world, "fused"),
        "decode_ll": ("ag_gemm", max(16 // world, 1), "ll"),
    }
    events = []
    for name, (t, ratio, detail) in dict(
            regimes, decode_ll=noise_bound).items():
        est = None
        if name in gemm_est:
            op, m_loc, method = gemm_est[name]
            est = estimate_overlap_gemm_us(
                op, m_loc, N_TOTAL // world, K, world, jnp.bfloat16,
                method)
        ev = emit_kernel_event(
            f"bench_{name}", kind="bench", world=world,
            measured_us=t * 1e6, estimate_us=est,
            vs_baseline=round(ratio, 3), detail=detail)
        if ev is not None:
            events.append(ev)
        get_registry().gauge("bench_vs_baseline", regime=name).set(ratio)
    audit_events(events)
    export = os.environ.get("TDT_METRICS_EXPORT")
    if export:
        get_registry().export(export)


def main():
    devices = jax.devices()
    world = len(devices)
    mesh = Mesh(np.array(devices), ("tp",))

    # Headline = MINIMUM vs_baseline across the SIGNAL regimes, so a
    # lucky draw in one regime can't carry the round.  decode_ll ties
    # by construction at world=1 (VERDICT r3 weak #3): it is reported
    # as the harness noise bound but does NOT gate the min — every
    # regime in the min has a real numerator (prefill vs XLA overlap
    # composition, flash_decode vs the strongest public decode
    # kernels, w8a8 vs the bf16 composition, moe_reduce_rs_fused vs
    # the XLA epilogue composition — the known-weak regime the min
    # now surfaces instead of hiding).
    # Runtime spans bracket each regime so a --trace-dir run (or an
    # attached jax.profiler) shows where the bench wall time went.
    from triton_distributed_tpu.observability import span
    regimes = {}
    for name, fn in [("prefill_fused", _regime_prefill),
                     ("flash_decode", _regime_flash_decode),
                     ("w8a8", _regime_w8a8),
                     # MoE in the min: the gate must SEE the weakest
                     # regime (VERDICT r5's moe_reduce_rs debt), not
                     # average it away behind the strong ones.
                     ("moe", _regime_moe)]:
        with span("bench.regime", regime=name, world=world):
            regimes[name] = fn(mesh, world)
    with span("bench.regime", regime="decode_ll", world=world):
        noise_bound = _regime_decode_ll(mesh, world)
    record_regimes(regimes, noise_bound, world)
    worst = min(regimes, key=lambda r: regimes[r][1])
    t_worst, r_worst, _ = regimes[worst]
    detail = "; ".join(f"{name}={r:.3f} ({d})"
                       for name, (t, r, d) in regimes.items())
    detail += (f"; noise_bound:decode_ll={noise_bound[1]:.3f} "
               f"({noise_bound[2]})")
    print(json.dumps({
        "metric": f"min vs_baseline over regimes [{detail}] "
                  f"(M={M_TOTAL} K={K} N={N_TOTAL}, "
                  f"{world} chip{'s' if world > 1 else ''}); "
                  f"worst={worst}",
        "value": round(t_worst * 1e6, 1),
        "unit": "us",
        "vs_baseline": round(r_worst, 3),
        "device": device_record(),
    }))


if __name__ == "__main__":
    main()
