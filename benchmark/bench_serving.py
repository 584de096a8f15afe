"""Serving benchmark: continuous batching vs serial `Engine.serve`.

Synthetic arrivals from a SEEDED schedule (exponential interarrivals,
bucket-length prompts, per-request sampling seeds — no wall-clock
randomness: the same seed always produces the same offered trace).
Two drivers consume the identical trace:

- **serial**: one `Engine.serve` call per request in arrival order,
  KV cache reused across calls (the caller-provided-cache path).  A
  request waits for the whole previous request, and — like the
  reference engine — serve decodes all ``max_new`` steps whether or
  not the stream already hit EOS;
- **continuous**: `serving.ContinuousBatchingScheduler` — requests
  join the running decode batch mid-flight via bucketed prefill +
  slot insert, and RETIRE at EOS, freeing the slot for the next
  joiner.

The workload samples at temperature 1 over a small vocabulary, so
streams hit the EOS id after naturally varying lengths (mean well
under ``max_new``).  Throughput counts USEFUL tokens — up to and
including the first EOS — for both modes; the serial engine still
pays wall-clock for the full ``max_new`` (it cannot early-exit; that
is exactly the waste continuous batching removes).  The offered trace
(arrivals, prompts, seeds) is identical for both modes, but the
REALIZED continuations differ: the serial engine samples its first
token from the prefill logits with the unsplit key, while the
scheduler's per-slot chain splits first, so the two modes draw
different same-distribution streams (useful-token totals land within
~2% — both are reported on the rows; throughput is per-token
normalized, so the comparison is fair, just not token-identical).

Per load the two modes run in ABBA order (serial, continuous,
continuous, serial) with throughput taken over summed makespans:
shared-host CPU throttling drifts on the scale of minutes (observed
2-4x on this container class), and a sequential per-mode sweep folds
that drift straight into the ratio — the same lesson
`bench_e2e_decode` learned.  ``speedup_vs_serial`` is therefore the
robust, machine-portable headline; the absolute TTFT/TBT microsecond
rows are snapshots of one machine state (regenerate the committed
baseline on YOUR machine before gating absolute values:
``python benchmark/bench_serving.py > benchmark/results/serving.json``).

Per (mode, load) it emits TTFT and TBT rows through ``bench_record``
(`samples_us` → registry histograms + p50_us/p99_us on the line), so
`scripts/check_bench_regression.py` gates serving tails alongside the
kernel benches.  The TBT row also carries aggregate
``tokens_per_s``; continuous rows carry ``speedup_vs_serial`` and
``continuous_beats_serial`` (the acceptance check: with staggered
arrivals, continuous must sustain strictly higher useful-token
throughput).

Default model is the CPU-runnable toy (`serving.toy.ToyModel`) so this
bench runs anywhere; ``--model qwen`` swaps in the shard_map Qwen3
engine on real hardware.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))  # repo root

import argparse
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from triton_distributed_tpu.utils.platform import device_record


def make_schedule(seed: int, n: int, load: float, buckets, vocab: int):
    """Deterministic offered trace: (arrival_s, prompt, seed) per
    request.  Prompt lengths are drawn FROM the bucket set so the
    serial engine compiles one program per (bucket, gen_len) — the
    same compile budget the bucketed scheduler has."""
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(1.0 / load, n))
    lens = rng.choice(buckets, n)
    prompts = [list(rng.integers(1, vocab, int(s))) for s in lens]
    return [(float(a), p, int(rng.integers(0, 2 ** 31)))
            for a, p in zip(arrivals, prompts)]


def make_shared_prefix_schedule(seed: int, n: int, load: float,
                                sys_len: int, vocab: int,
                                suffix_lo: int = 2,
                                suffix_hi: int = 4):
    """Shared-system-prompt trace: every request is the SAME
    ``sys_len``-token system prompt plus a short per-request suffix —
    the workload radix prefix caching exists for.  Deterministic like
    `make_schedule`."""
    rng = np.random.default_rng(seed)
    sysp = list(rng.integers(1, vocab, sys_len))
    arrivals = np.cumsum(rng.exponential(1.0 / load, n))
    prompts = [sysp + list(rng.integers(
        1, vocab, int(rng.integers(suffix_lo, suffix_hi + 1))))
        for _ in range(n)]
    return [(float(a), p, int(rng.integers(0, 2 ** 31)))
            for a, p in zip(arrivals, prompts)]


def measure_peak_concurrency(model, params, args, buckets, layout,
                             budget_bytes, n=64):
    """Admitted-concurrency sweep: short requests, everyone eligible
    at once, SAME KV byte budget for both layouts.  Slot admission
    prices every request at max-context, so its peak is
    budget/bytes_per_slot; page admission prices actual pages."""
    from triton_distributed_tpu.serving import (
        ContinuousBatchingScheduler, Request, SchedulerConfig)

    sched = ContinuousBatchingScheduler(
        model, params,
        SchedulerConfig(num_slots=n, max_queue=n + 8,
                        prefill_buckets=buckets,
                        kv_layout=layout, page_size=args.page_size,
                        kv_budget_bytes=budget_bytes),
        clock=time.perf_counter)
    reqs = [Request(prompt=[1 + (i % (args.vocab - 2)), 2, 3, 4],
                    max_new_tokens=4, arrival_time=0.0)
            for i in range(n)]
    for r in reqs:
        ok = sched.submit(r)
        assert ok, r.reject_reason
    peak = 0
    while sched.has_work():
        sched.step()
        peak = max(peak, sched.slots.active_slots)
    assert len(sched.finished) == n
    return peak


def useful_len(tokens, eos: int) -> int:
    """Tokens up to and including the first EOS (all, if none)."""
    for i, t in enumerate(tokens):
        if t == eos:
            return i + 1
    return len(tokens)


class SerialDriver:
    """Arrival-order `Engine.serve` calls, cache reused across calls.
    Virtual queueing (service starts at max(prev finish, arrival)),
    real measured service times.  No early exit: serve always decodes
    ``max_new`` steps."""

    def __init__(self, model, params, args, buckets):
        from triton_distributed_tpu.models.engine import Engine

        self.model, self.params, self.args = model, params, args
        self.eng = Engine(model, temperature=args.temperature,
                          scan_decode=True)
        self.cache = model.create_cache(1)
        # Warm every (bucket, gen) program out of the measurement, and
        # time prefill+first-token per bucket (serve(gen_len=1) IS
        # exactly that) for the TTFT attribution.
        self.t_first = {}
        for b in buckets:
            ids = jnp.asarray(np.arange(b) % (args.vocab - 1) + 1,
                              jnp.int32)[None]
            _, self.cache = self.eng.serve(self.params, ids, 1,
                                           cache=self.cache)
            _, self.cache = self.eng.serve(self.params, ids,
                                           args.max_new,
                                           cache=self.cache)
            t0 = time.perf_counter()
            _, self.cache = self.eng.serve(self.params, ids, 1,
                                           cache=self.cache)
            self.t_first[b] = time.perf_counter() - t0

    def measure(self, schedule):
        args = self.args
        max_new = args.max_new
        clock = 0.0
        ttft_s, tbt_s = [], []
        busy0 = None
        useful = 0
        for arrival, prompt, seed in schedule:
            ids = jnp.asarray(prompt, jnp.int32)[None]
            start = max(clock, arrival)
            t0 = time.perf_counter()
            toks, self.cache = self.eng.serve(
                self.params, ids, max_new,
                key=jax.random.key(seed), cache=self.cache)
            toks = np.asarray(toks)[0]
            service = time.perf_counter() - t0
            if busy0 is None:
                busy0 = arrival
            clock = start + service
            useful += useful_len(toks, args.eos)
            b = len(prompt)
            ttft_s.append(start - arrival + self.t_first[b])
            tbt_s.extend([max(service - self.t_first[b], 0.0)
                          / max(max_new - 1, 1)] * max(max_new - 1, 1))
        return {"makespan_s": clock - busy0, "useful_tokens": useful,
                "ttft_s": ttft_s, "tbt_s": tbt_s}


class ContinuousDriver:
    def __init__(self, model, params, args, buckets, layout="slots",
                 prefix_cache=True, cfg_overrides=None):
        from triton_distributed_tpu.serving import (
            ContinuousBatchingScheduler, Request, SchedulerConfig)

        self.Request = Request
        self.args = args
        self.layout = layout
        cfg_kw = dict(num_slots=args.slots,
                      max_queue=args.n_requests + 8,
                      prefill_buckets=buckets,
                      temperature=args.temperature,
                      steps_per_sync=args.steps_per_sync,
                      kv_layout=layout,
                      page_size=args.page_size,
                      prefix_cache=prefix_cache)
        cfg_kw.update(cfg_overrides or {})
        # One clock everywhere: arrivals, TBT callbacks and the
        # scheduler's own timestamps all read perf_counter, so the
        # derived TTFT/makespan never mix clock epochs.
        self.sched = ContinuousBatchingScheduler(
            model, params, SchedulerConfig(**cfg_kw),
            clock=time.perf_counter)
        # Warm the per-bucket prefill/insert programs and the masked
        # step out of the measurement (prompt ids kept inside the
        # vocab, same construction as SerialDriver's warm-up).  A
        # speculative engine additionally needs a verify round to
        # compile: repetitive warm prompts guarantee the n-gram
        # drafter proposes (a draft model proposes regardless), and
        # the longer warm budget leaves it draft headroom.
        spec = bool(cfg_kw.get("spec_k"))
        # Spec warm streams must OUTLIVE a full verify round (max_new
        # > k+1), or the continuing-row reconcile program compiles
        # mid-measure — the warm asserts below catch a silent miss.
        warm_new = 2 * cfg_kw.get("spec_k", 0) + 4 if spec else 2
        warm = [Request(prompt=(list(np.arange(b) % 4 + 1) if spec
                                else list(np.arange(b)
                                          % (args.vocab - 1) + 1)),
                        max_new_tokens=warm_new)
                for b in buckets]
        self.sched.run(warm)
        if spec:
            assert self.sched._spec_proposed > 0, (
                "speculative warm-up never took a verify dispatch — "
                "the spec program would compile mid-measure")
            # The PLAIN masked step is the spec engine's fallback
            # (no proposals / near-horizon) — a max_new=1 request can
            # never speculate (no draft budget), so this compiles it.
            self.sched.run([Request(prompt=[1, 2, 3, 4],
                                    max_new_tokens=1)])
            # The warm workload is synthetic: its proposals must
            # neither pre-trip nor pre-feed the accept-collapse
            # throttle — measured traffic decides.
            self.sched._spec_proposed = 0
            self.sched._spec_accepted = 0
            self.sched._spec_throttled = False
        self.sched.finished.clear()
        if layout == "paged":
            # The run(warm) admissions may have taken the SUFFIX path
            # for the larger buckets (the warm prompts share prefixes
            # with each other through the radix cache), leaving the
            # full-prefill and suffix programs of some buckets
            # uncompiled — warm every per-bucket program DIRECTLY so
            # no radix-dependent admission path pays a first-compile
            # mid-measure.
            import jax
            import jax.numpy as jnp
            for b in buckets:
                ids = jnp.ones((1, b), jnp.int32)
                _, row = self.sched._prefill(params, ids,
                                             self.sched._row_cache(b))
                jax.block_until_ready(row.ks[0])
                if self.sched._prefill_suffix is not None:
                    self.sched._prefill_suffix(
                        params, ids, jnp.int32(args.page_size),
                        self.sched._row_cache(b))

    def _radix_stats(self):
        radix = getattr(self.sched.slots, "radix", None)
        if radix is None:
            return (0, 0)
        return (radix.hit_tokens, radix.miss_tokens)

    def measure(self, schedule, eos=None):
        args = self.args
        eos_ids = (args.eos,) if eos is None else tuple(eos)
        last_token_t = {}
        tbt_s = []

        def on_token(req, tok, _last=last_token_t, _tbt=tbt_s):
            now = time.perf_counter()
            if req.request_id in _last:
                _tbt.append(now - _last[req.request_id])
            _last[req.request_id] = now

        h0, m0 = self._radix_stats()
        t0 = time.perf_counter()
        reqs = [self.Request(prompt=p, max_new_tokens=args.max_new,
                             seed=s, eos_token_ids=eos_ids,
                             arrival_time=t0 + a, on_token=on_token)
                for a, p, s in schedule]
        done = list(self.sched.run(reqs))   # copy: run() returns the
        self.sched.finished.clear()         # live finished list
        assert len(done) == len(schedule), (len(done), len(schedule))
        first_arrival = min(r.t_arrival for r in done)
        last_finish = max(r.t_finish for r in done)
        useful = sum(len(r.generated) for r in done)
        h1, m1 = self._radix_stats()
        out = {"makespan_s": last_finish - first_arrival,
               "useful_tokens": useful,
               "ttft_s": [r.ttft for r in done], "tbt_s": tbt_s,
               # token streams in SCHEDULE order (deterministic per
               # (prompt, seed)): the spec section asserts exactness
               # against the plain engine's
               "streams": [list(r.generated) for r in reqs]}
        if (self.layout == "paged"
                and getattr(self.sched.slots, "radix", None) is not None):
            hit, miss = h1 - h0, m1 - m0
            out["prefix_hit_rate"] = (hit / (hit + miss)
                                      if hit + miss else 0.0)
        if self.sched.config.spec_k:
            # keyed off the CONFIG, not the live drafter: a throttled
            # engine releases its drafter mid-measure, and the row
            # must still report the outcome that led there
            prop = sum(r.spec_proposed for r in done)
            acc = sum(r.spec_accepted for r in done)
            out["spec_proposed"] = prop
            out["spec_accepted"] = acc
            out["spec_accept_rate"] = acc / prop if prop else 0.0
        return out

    def accept_hist(self):
        """Snapshot of the per-round accept-length histogram
        (``serving_spec_accept_tokens``): (count, sum, buckets).  The
        caller deltas two snapshots to get one trace's histogram."""
        from triton_distributed_tpu.observability import get_registry
        h = get_registry().snapshot().get("histograms", {}).get(
            "serving_spec_accept_tokens")
        if not h:
            return 0, 0.0, {}
        return h["count"], h["sum"], dict(h["buckets"])


def pool_runs(runs):
    """Combine a mode's ABBA repeats: samples pooled, throughput from
    summed makespans (tokens are schedule-deterministic, identical
    across repeats)."""
    out = {
        "tokens_per_s": (sum(r["useful_tokens"] for r in runs)
                         / sum(r["makespan_s"] for r in runs)),
        "useful_tokens": runs[0]["useful_tokens"],
        "ttft_s": [t for r in runs for t in r["ttft_s"]],
        "tbt_s": [t for r in runs for t in r["tbt_s"]],
    }
    if any("prefix_hit_rate" in r for r in runs):
        out["prefix_hit_rate"] = statistics.mean(
            r.get("prefix_hit_rate", 0.0) for r in runs)
    if "streams" in runs[0]:
        out["streams"] = runs[0]["streams"]
    if any("spec_proposed" in r for r in runs):
        prop = sum(r.get("spec_proposed", 0) for r in runs)
        acc = sum(r.get("spec_accepted", 0) for r in runs)
        out["spec_proposed"] = prop
        out["spec_accepted"] = acc
        out["spec_accept_rate"] = acc / prop if prop else 0.0
    return out


def emit(mode, load, args, res, extra=None, trace=None,
         steps_per_sync=None, slots=None):
    from triton_distributed_tpu.observability import bench_record

    base = {"bench": "serving", "model": args.model, "mode": mode,
            "slots": (slots if slots is not None
                      else args.slots if mode != "serial" else 1),
            "n_requests": args.n_requests, "max_new": args.max_new,
            "load_rps": load}
    if mode != "serial":
        base["steps_per_sync"] = (args.steps_per_sync
                                  if steps_per_sync is None
                                  else steps_per_sync)
    if args.model == "qwen":
        # the real model's rows are device numbers: say which device
        base["device"] = device_record()
    if trace is not None:
        # identity dimension: shared-prefix rows never match the
        # default-trace rows in the regression gate
        base["trace"] = trace
    for metric, samples in (("ttft", res["ttft_s"]),
                            ("tbt", res["tbt_s"])):
        us = [s * 1e6 for s in samples]
        rec = dict(base, metric=metric, us=round(statistics.mean(us), 1),
                   samples_us=[round(u, 1) for u in us])
        if metric == "tbt":
            rec["tokens_per_s"] = round(res["tokens_per_s"], 1)
            rec["useful_tokens"] = res["useful_tokens"]
            if "prefix_hit_rate" in res:
                rec["prefix_hit_rate"] = round(res["prefix_hit_rate"],
                                               4)
            rec.update(extra or {})
        bench_record(rec)


def measure_record_overhead(model, params, args, buckets):
    """Paired record-off / record-on cluster runs on the identical
    trace: the price of arming `ClusterConfig.record_dir` (see
    `observability/replay.py`) must stay in the noise (gated <= 5%
    by `check_bench_regression.replay_checks`), and the artifact the
    ON runs wrote must actually replay EXACT — an overhead number
    for a recorder whose recordings don't re-execute gates nothing.

    Mirrored off/on/on/off/off/on order (same drift-cancelling
    lesson as the serial-vs-continuous pairing), min-of-3 wall time
    per mode: recording cost is host-side Python (row buffering +
    one atomic flush), so min-of-N isolates it from scheduler
    jitter."""
    import shutil
    import tempfile

    from triton_distributed_tpu.observability.replay import (
        replay_run)
    from triton_distributed_tpu.serving import (
        ClusterConfig, SchedulerConfig, ServingCluster)

    trace = [dict(prompt=[1 + (i % 7), 2, 3 + (i % 5)],
                  max_new_tokens=4 + (i % 3), seed=i,
                  arrival_time=0.002 * i)
             for i in range(min(args.n_requests, 24))]
    sc = SchedulerConfig(num_slots=4, prefill_buckets=buckets,
                         temperature=0.8, top_k=8)

    def run(record_dir):
        cfg = ClusterConfig(n_replicas=2, n_prefill_workers=1,
                            scheduler=sc, record_dir=record_dir)
        t0 = time.perf_counter()
        cluster = ServingCluster(model, params, cfg)
        for t in trace:
            cluster.submit(**t)
        done = cluster.drain()
        wall = time.perf_counter() - t0
        assert len(done) == len(trace)
        return wall

    walls = {"off": [], "on": []}
    dirs = []
    for mode in ("off", "on", "on", "off", "off", "on"):
        if mode == "on":
            d = tempfile.mkdtemp(prefix="tdt-bench-replay-")
            dirs.append(d)
            walls[mode].append(run(d))
        else:
            walls[mode].append(run(""))
    off, on = min(walls["off"]), min(walls["on"])
    report = replay_run(dirs[-1], model=model, params=params)
    exact = report["status"] == "EXACT"
    for d in dirs:
        shutil.rmtree(d, ignore_errors=True)

    from triton_distributed_tpu.observability import bench_record
    bench_record({
        "bench": "serving", "model": args.model,
        "metric": "replay_record", "n_requests": len(trace),
        "record_off_s": round(off, 6), "record_on_s": round(on, 6),
        "recording_overhead": round(on / off - 1.0, 4),
        "recording_overhead_le_5pct": on <= off * 1.05,
        "replay_exact": exact})


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", choices=("toy", "qwen"), default="toy")
    ap.add_argument("--slots", type=int, default=24)
    ap.add_argument("--n-requests", type=int, default=96)
    ap.add_argument("--max-new", type=int, default=96)
    ap.add_argument("--loads", default="400,800",
                    help="offered loads to sweep, requests/second; "
                         "defaults saturate the serial engine (~200 "
                         "rps on a 2-core CPU) — at sub-saturating "
                         "load every correct system's throughput "
                         "equals the offered load")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--buckets", default="8,16,32")
    ap.add_argument("--temperature", type=float, default=1.0)
    ap.add_argument("--steps-per-sync", type=int, default=12,
                    help="decode steps per host sync (multi-step "
                         "scheduling; EOS checked per block)")
    ap.add_argument("--vocab", type=int, default=31)
    ap.add_argument("--eos", type=int, default=3,
                    help="EOS id: streams end when sampling hits it")
    ap.add_argument("--page-size", type=int, default=16,
                    help="KV page size for the paged engine rows")
    ap.add_argument("--spec-k", type=int, default=8,
                    help="draft tokens per verify round for the "
                         "speculative rows")
    ap.add_argument("--spec-slots", type=int, default=4,
                    help="engine slots for the speculative pairing "
                         "(the LOW-concurrency latency regime "
                         "speculation targets: at saturating batch, "
                         "plain batching already fills the machine "
                         "and trading extra draft/verify compute for "
                         "tokens-per-dispatch rightly loses)")
    ap.add_argument("--sys-len", type=int, default=48,
                    help="shared system-prompt length for the "
                         "shared-prefix trace")
    args = ap.parse_args()

    buckets = tuple(int(b) for b in args.buckets.split(","))
    # the shared-prefix trace needs a bucket covering sys_len + suffix
    eng_buckets = tuple(sorted(set(buckets) | {
        1 << (args.sys_len + 8 - 1).bit_length()}))
    if args.model == "toy":
        from triton_distributed_tpu.serving import ToyConfig, ToyModel
        max_seq = max(eng_buckets) + args.max_new + 8
        max_seq += (-max_seq) % args.page_size   # page-aligned
        model = ToyModel(ToyConfig(
            vocab_size=args.vocab, hidden=32, max_seq_len=max_seq))
        params = model.init_params(jax.random.key(args.seed))
    else:
        from jax.sharding import Mesh

        from triton_distributed_tpu.models import ModelConfig
        from triton_distributed_tpu.models.qwen import Qwen3
        cfg = ModelConfig.qwen3_0_6b()
        cfg.max_seq_len = max(buckets) + args.max_new + 8
        model = Qwen3(cfg, Mesh(np.array(jax.devices()), ("tp",)))
        params = model.init_params(jax.random.key(args.seed))

    # Drivers (and their compiled programs) are built ONCE; per load
    # the modes are measured in mirrored (ABCCBA) order so slow
    # machine drift (shared-host CPU throttling, minutes-scale — same
    # lesson as bench_e2e_decode) cancels out of the paired speedups
    # instead of biasing whichever mode ran last.
    serial_drv = SerialDriver(model, params, args, eng_buckets)
    cont_drv = ContinuousDriver(model, params, args, eng_buckets)
    # Default-trace paged driver runs WITHOUT the radix cache: the
    # deterministic schedule repeats identical prompts across repeats
    # and load points, so a persistent prefix cache would warm across
    # runs and the "paged" rows would measure cache hits the offered
    # workload doesn't contain.  The prefix cache gets its own driver
    # and its own trace below.
    paged_drv = ContinuousDriver(model, params, args, eng_buckets,
                                 layout="paged", prefix_cache=False)
    paged_prefix_drv = ContinuousDriver(model, params, args,
                                        eng_buckets, layout="paged")
    for load in (float(x) for x in args.loads.split(",")):
        schedule = make_schedule(args.seed, args.n_requests, load,
                                 buckets, args.vocab)
        runs = {"serial": [], "continuous": [], "paged": []}
        for mode in ("serial", "continuous", "paged",
                     "paged", "continuous", "serial"):
            drv = {"serial": serial_drv, "continuous": cont_drv,
                   "paged": paged_drv}[mode]
            runs[mode].append(drv.measure(schedule))
        serial = pool_runs(runs["serial"])
        cont = pool_runs(runs["continuous"])
        paged = pool_runs(runs["paged"])
        speedup = cont["tokens_per_s"] / serial["tokens_per_s"]
        # The two same-mode repeats measure the same deterministic
        # workload seconds apart: a >1.5x makespan spread between them
        # means a host-throttling cliff landed mid-cycle (ABBA cancels
        # only smooth drift) — tag the row so a glitchy run reads as a
        # glitchy run (same policy as bench_e2e_decode's discards).
        spread = max(
            max(r["makespan_s"] for r in rs)
            / min(r["makespan_s"] for r in rs)
            for rs in runs.values())
        drift = ({"machine_drift_suspected": True,
                  "makespan_spread": round(spread, 2)}
                 if spread > 1.5 else {})
        emit("serial", load, args, serial)
        emit("continuous", load, args, cont, extra={
            "speedup_vs_serial": round(speedup, 3),
            "continuous_beats_serial":
                cont["tokens_per_s"] > serial["tokens_per_s"],
            **drift})
        emit("paged", load, args, paged, extra={
            "speedup_vs_serial": round(
                paged["tokens_per_s"] / serial["tokens_per_s"], 3),
            "speedup_vs_slots": round(
                paged["tokens_per_s"] / cont["tokens_per_s"], 3),
            **drift})

    # Shared-system-prompt trace: the radix prefix cache's workload.
    # Paged vs slot engines in mirrored order; the paged rows carry
    # the prefix hit rate (acceptance: > 0.9 — only the first arrival
    # and the tiny per-request suffixes miss).
    load = float(args.loads.split(",")[0])
    schedule = make_shared_prefix_schedule(
        args.seed, args.n_requests, load, args.sys_len, args.vocab)
    runs = {"continuous": [], "paged": []}
    for mode in ("continuous", "paged", "paged", "continuous"):
        drv = cont_drv if mode == "continuous" else paged_prefix_drv
        runs[mode].append(drv.measure(schedule))
    cont = pool_runs(runs["continuous"])
    paged = pool_runs(runs["paged"])
    emit("continuous", load, args, cont, trace="shared_prefix")
    emit("paged", load, args, paged, trace="shared_prefix", extra={
        "speedup_vs_slots": round(
            paged["tokens_per_s"] / cont["tokens_per_s"], 3),
        "prefix_hit_gt_90": paged.get("prefix_hit_rate", 0) > 0.9,
        "ttft_vs_slots": round(
            statistics.mean(paged["ttft_s"])
            / max(statistics.mean(cont["ttft_s"]), 1e-9), 3)})

    # Speculative decoding: paired spec-vs-plain GREEDY engines on the
    # identical trace, ABBA-interleaved like the serial-vs-continuous
    # pairing.  The plain comparator syncs per token (steps_per_sync=1
    # — the same EOS-check granularity speculation keeps: a verify
    # round commits <= k+1 tokens and checks EOS every round; block
    # mode trades that latency away, an orthogonal knob).  Greedy so
    # the exactness row is meaningful — every driver must produce
    # token-for-token identical streams (`spec_exact`, asserted here
    # AND gated by check_bench_regression).  Two draft sources: the
    # model-free n-gram drafter and a draft model (the toy drafts for
    # itself here — on real hardware a tiny Qwen3 config,
    # `ModelConfig.draft_of`, fills this slot; accept rate is then a
    # property of the model pair, not of the machinery measured).
    from triton_distributed_tpu.serving import BatchedDraftModelDrafter
    load = float(args.loads.split(",")[0])
    schedule = make_schedule(args.seed, args.n_requests, load,
                             buckets, args.vocab)
    greedy = dict(temperature=0.0, steps_per_sync=1,
                  num_slots=args.spec_slots)
    # The draft drafter is BATCHED (one masked rollout dispatch
    # proposes for every slot — the per-request variant would pay
    # `slots` sequential draft dispatches per round); the factory
    # form gives it the scheduler's slot space.
    draft_factory = lambda sched: BatchedDraftModelDrafter(  # noqa: E731
        model, params, num_slots=sched.config.num_slots,
        max_seq=sched.max_seq, prefill_buckets=eng_buckets)
    spec_drivers = {
        "plain": ContinuousDriver(
            model, params, args, eng_buckets, cfg_overrides=greedy),
        "spec_ngram": ContinuousDriver(
            model, params, args, eng_buckets,
            cfg_overrides=dict(greedy, spec_k=args.spec_k)),
        "spec_draft": ContinuousDriver(
            model, params, args, eng_buckets,
            cfg_overrides=dict(greedy, spec_k=args.spec_k,
                               spec_drafter=draft_factory)),
    }
    # Arm the accept-collapse throttle AFTER warm-up (a throttled
    # engine releases its drafter for good — the synthetic warm
    # workload must not be what pulls that trigger): measured
    # traffic decides, and the committed row asserts it fired.
    spec_drivers["spec_ngram"].sched.config.spec_min_accept = 0.3
    runs = {m: [] for m in spec_drivers}
    hists = {m: [0, 0.0, {}] for m in spec_drivers}
    for mode in ("plain", "spec_ngram", "spec_draft",
                 "spec_draft", "spec_ngram", "plain"):
        drv = spec_drivers[mode]
        c0, s0, b0 = drv.accept_hist()
        # eos=(): speculation is a DECODE-length optimization, and
        # the greedy toy hits the sampled-workload EOS id within a
        # few tokens — the spec trace decodes full max_new streams
        # (the long-generation regime the technique exists for).
        runs[mode].append(drv.measure(schedule, eos=()))
        c1, s1, b1 = drv.accept_hist()
        hists[mode][0] += c1 - c0
        hists[mode][1] += s1 - s0
        for kk, v in b1.items():
            hists[mode][2][kk] = (hists[mode][2].get(kk, 0)
                                  + v - b0.get(kk, 0))
    pooled = {m: pool_runs(rs) for m, rs in runs.items()}
    plain = pooled["plain"]
    emit("plain", load, args, plain, trace="spec_greedy",
         steps_per_sync=1, slots=args.spec_slots)
    for mode in ("spec_ngram", "spec_draft"):
        res = pooled[mode]
        exact = res["streams"] == plain["streams"]
        assert exact, f"{mode} diverged from plain greedy streams"
        speedup = res["tokens_per_s"] / plain["tokens_per_s"]
        rounds, acc_sum, buckets = hists[mode]
        extra = {
            "spec_k": args.spec_k,
            "spec_accept_rate": round(res["spec_accept_rate"], 4),
            "spec_proposed": res["spec_proposed"],
            "spec_accepted": res["spec_accepted"],
            "spec_rounds": rounds,
            # registry histograms bucket by ceil(log2(v)) with a
            # large-negative sentinel for v <= 0: decode the keys to
            # power-of-two UPPER BOUNDS before publishing ("0" =
            # zero-accept rounds, "4" = accept length in (2, 4])
            "accept_len_hist": {
                k: v for k, v in sorted(
                    ((("0" if int(kk) < 0 else str(2 ** int(kk))), c)
                     for kk, c in buckets.items() if c),
                    key=lambda kv: int(kv[0]))},
            # Acceptance-weighted tokens per verify dispatch (1 +
            # mean accept length): the tokens-per-model-step
            # multiplier a memory-bound accelerator realizes.
            "spec_tokens_per_step": round(
                1.0 + acc_sum / rounds, 4) if rounds else None,
            "speedup_vs_plain": round(speedup, 3),
            "spec_exact": exact}
        if mode == "spec_draft":
            # The never-worse gate rides the draft pairing: its
            # accept rate is a property of the measured machinery
            # (the toy drafts for itself — greedy self-agreement is
            # total), so a loss is a scheduling/dispatch regression.
            extra["spec_beats_plain"] = speedup > 1.0
        else:
            # The n-gram drafter's accept rate is a property of the
            # WORKLOAD (the toy's greedy streams are near-
            # unpredictable); what the row asserts instead is the
            # accept-collapse throttle: drafting must have shut
            # itself off (spec_min_accept=0.3) and the wall cost of
            # having probed must stay small.
            extra["spec_throttled"] = bool(
                spec_drivers[mode].sched._spec_throttled)
        emit(mode, load, args, res, trace="spec_greedy",
             steps_per_sync=1, slots=args.spec_slots, extra=extra)

    # Page-vs-slot admitted-concurrency sweep on the SAME KV budget
    # (the tentpole's capacity claim: >= 4x on short requests).
    from triton_distributed_tpu.observability import bench_record
    budget = 4 * model.create_cache(1).bytes_per_slot()
    peaks = {}
    for layout in ("slots", "paged"):
        peaks[layout] = measure_peak_concurrency(
            model, params, args, eng_buckets, layout, budget)
    bench_record({"bench": "serving", "model": args.model,
                  "metric": "concurrency", "budget_slots": 4,
                  "max_concurrent_slots": peaks["slots"],
                  "max_concurrent_paged": peaks["paged"],
                  "concurrency_vs_slots": round(
                      peaks["paged"] / max(peaks["slots"], 1), 2),
                  "paged_4x_concurrency":
                      peaks["paged"] >= 4 * peaks["slots"]})

    # Record & replay: the recording-overhead pairing (<= 5% gate)
    # plus the replay-exactness bit on the artifact it wrote.
    measure_record_overhead(model, params, args, eng_buckets)


if __name__ == "__main__":
    main()
