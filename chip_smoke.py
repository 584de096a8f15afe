#!/usr/bin/env python3
"""Standing proof that the serving path starts on the chip.

    python3 chip_smoke.py            # TPU required; exits nonzero without
    python3 chip_smoke.py --rehearse # ModelConfig.tiny, CPU allowed,
                                     # marked "rehearsal": true, never a pass

Drives the main path once through the entry points a user calls
(`ContinuousBatchingScheduler` paged and slots, `Engine.serve`) with
`ModelConfig.qwen3_8b()` at its published widths (bf16, random weights
from ``--seed``, ``mode="fused"``), checks fused-vs-xla logits, and
establishes two facts later PRs lean on: whether `block_until_ready`
blocks, and whether `jax.profiler` yields a TPU plane.

One chip belongs to one process, so this file is two things:

- the PARENT (default) never initialises a JAX backend.  It runs the
  phases in one child process (``--pass cold``), then the same phases
  in a second FRESH process (``--pass warm``) that must find every
  program in the persistent compile cache, and prints the summary and,
  as the last line of a real run, the result
  ``{"ok": ..., "device": {"platform", "kind", "count"}}``.
- the CHILD (``--pass``) is the one process that drives every phase,
  each under a watchdog: a hang in a semaphore wait is a named failure
  and a nonzero exit, not a spent budget.

On one chip the depth is cut (printed under ``reduced``); with four or
more devices it runs all 36 layers on a ``tp`` mesh over four of them and
requires at least one Pallas ring/``ll`` GEMM kernel to have executed.
No width is ever cut.  Nothing here is a speed claim: ``"claim": null``.
"""

from __future__ import annotations

import argparse
import faulthandler
import glob
import json
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time
import traceback

#: Exit codes (0 = pass; 2/3 are left to the chip tool).
EXIT_FAILED, EXIT_NO_TPU, EXIT_HUNG = 1, 4, 5

#: Backstop for both passes together, should a child wedge past its
#: own per-phase watchdog: the run is to end inside 1200 s.
TOTAL_TIMEOUT_S = 1150


def result_line(ok: bool, device: dict) -> str:
    """The last line of a real run's standard output: exactly these
    keys, the device as JAX reported it in the child."""
    return json.dumps({"ok": bool(ok), "device": {
        "platform": str(device["platform"]), "kind": str(device["kind"]),
        "count": int(device["count"])}})


def logit_tolerance(num_layers: int):
    """Fused-vs-xla logit tolerance, as (RMS, worst single logit), both
    relative to the RMS of the reference logits.

    Both modes compute in bf16 with f32 accumulation and differ only in
    summation order and in where an activation is rounded to bf16
    (flash vs dense softmax; ring-chunked vs whole GEMMs and the order
    of their reduction).  Each layer whose two modes round differently
    moves the residual stream by about one bf16 ulp (2^-8), the layers'
    moves are independent, and a random-weight network passes a relative
    perturbation on unchanged — so they add in quadrature over L layers.
    The bound is four of them: 4 * 2^-8 * sqrt(L) = 2^-6 * sqrt(L).
    Measured on the v5e at L=12: 2.3-2.5% against a bound of 5.4%.  An
    8-bit float path has an ulp of 2^-3 or 2^-4, sixteen or more times
    bf16's, and lands an order of magnitude above the bound; so does a
    dropped term.  The worst of the ~152k logits is held to 6x the RMS
    bound (measured 4.5-4.9x the RMS error; a Gaussian tail of that
    many samples reaches ~4.5 sigma)."""
    rms = 2.0 ** -6 * num_layers ** 0.5
    return rms, 6 * rms


# ---------------------------------------------------------------------------
# Parent: orchestrates two fresh child processes, never touches a device
# ---------------------------------------------------------------------------

def parent(args) -> int:
    tmp = tempfile.mkdtemp(prefix="chip_smoke_")
    summaries = {}
    deadline = time.monotonic() + TOTAL_TIMEOUT_S
    try:
        for which in ("cold", "warm"):
            summary = os.path.join(tmp, f"{which}.json")
            cmd = [sys.executable, os.path.abspath(__file__),
                   "--pass", which, "--summary", summary,
                   "--seed", str(args.seed)]
            if args.rehearse:
                cmd.append("--rehearse")
            print(f"== chip_smoke: {which} pass "
                  f"({'first' if which == 'cold' else 'second fresh'} "
                  f"process, same phases) ==", flush=True)
            # Own process group, so a wedged child and anything it
            # started are stopped together.
            proc = subprocess.Popen(cmd, start_new_session=True)
            try:
                rc = proc.wait(
                    timeout=max(1.0, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                _kill_group(proc)
                print(f"chip_smoke: {which} pass ran past the "
                      f"{TOTAL_TIMEOUT_S}s allowed the whole run — "
                      f"killed", flush=True)
                return EXIT_HUNG
            finally:
                _kill_group(proc)
            if rc != 0:
                print(f"chip_smoke: {which} pass failed (exit {rc})",
                      flush=True)
                return rc
            with open(summary) as f:
                summaries[which] = json.load(f)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    cold, warm = summaries["cold"], summaries["warm"]
    phases_ok = bool(cold["ok"] and warm["ok"])
    result = {
        # A rehearsal is never a pass, whatever its phases did.
        "ok": phases_ok and not args.rehearse,
        "device": cold["device"],
        "rehearsal": bool(args.rehearse),
        "model": cold["model"],
        "reduced": cold["reduced"],
        "versions": cold["versions"],
        "compile_cache_dir": cold["compile_cache_dir"],
        "block_until_ready_blocks": cold["block_until_ready_blocks"],
        "profiler_tpu_plane": cold["profiler_tpu_plane"],
        "phases": {p["phase"]: {
            "ok": p["ok"] and w["ok"],
            "cold_wall_s": p["wall_s"], "warm_wall_s": w["wall_s"],
            "cold_compile_s": p["compile_s"],
            "warm_compile_s": w["compile_s"],
            "warm_cache_hits": w["cache_hits"],
            "warm_cache_misses": w["cache_misses"],
            "peak_hbm_bytes": p["peak_hbm_bytes"],
        } for p, w in zip(cold["phases"], warm["phases"])},
        "cold_pass_s": cold["total_s"], "warm_pass_s": warm["total_s"],
        "claim": None,
    }
    print(json.dumps(result), flush=True)
    if not args.rehearse:
        # A rehearsal prints no result: it is never a pass.
        print(result_line(phases_ok, cold["device"]), flush=True)
    return 0 if phases_ok else EXIT_FAILED


def _kill_group(proc) -> None:
    import signal
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except (ProcessLookupError, PermissionError):
        pass


# ---------------------------------------------------------------------------
# Child: the one process that drives every phase
# ---------------------------------------------------------------------------

class CompileCounters:
    """Persistent-cache and compile-time accounting from `jax.monitoring`
    (the events JAX itself records around every compilation)."""

    def __init__(self):
        import jax
        self.requests = self.hits = 0
        self.compile_s = self.trace_lower_s = 0.0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(
            self._duration)

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def _duration(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs
        elif event in ("/jax/core/compile/jaxpr_trace_duration",
                       "/jax/core/compile/jaxpr_to_mlir_module_duration"):
            self.trace_lower_s += secs

    def since(self, then=None):
        """Counts now, or their growth since an earlier reading."""
        now = {"trace_lower_s": self.trace_lower_s,
               "compile_s": self.compile_s,
               "cache_requests": self.requests, "cache_hits": self.hits}
        if then is None:
            return now
        d = {k: round(now[k] - then[k], 3) for k in now}
        d["cache_misses"] = d["cache_requests"] - d["cache_hits"]
        return d


class Child:
    def __init__(self, args):
        self.args = args
        self.rehearse = args.rehearse
        self.warm = args.which == "warm"
        self.phases = []
        self.paged_streams = []
        self.engine = None
        self.facts = {"block_until_ready_blocks": None,
                      "profiler_tpu_plane": None}

    # -- set-up: header first, backend asserted before anything else ----

    def start(self) -> int:
        import importlib.metadata as md

        import jax

        import triton_distributed_tpu  # noqa: F401  (places the cache)
        from triton_distributed_tpu.utils.platform import (
            default_interpret, device_record)

        self.jax = jax
        backend = jax.default_backend()
        self.device = device_record()
        self.versions = {
            "python": sys.version.split()[0],
            "jax": jax.__version__,
            "jaxlib": md.version("jaxlib"),
            "libtpu": md.version("libtpu"),
        }
        self.cache_dir = jax.config.jax_compilation_cache_dir
        print(json.dumps({
            "pass": self.args.which, "backend": backend,
            "device": self.device, "versions": self.versions,
            "compile_cache_dir": self.cache_dir,
            "compile_cache_dir_from_env": bool(
                os.environ.get("JAX_COMPILATION_CACHE_DIR")),
            "rehearsal": self.rehearse}), flush=True)
        if backend != "tpu" and not self.rehearse:
            print(f"chip_smoke: no TPU — jax.default_backend() is "
                  f"{backend!r} ({self.device['kind']}); this run needs "
                  f"the accelerator (use --rehearse to debug on the "
                  f"CPU)", flush=True)
            return EXIT_NO_TPU
        if not self.rehearse and default_interpret(None) is not False:
            print("chip_smoke: Pallas kernels would run interpreted on "
                  "this backend", flush=True)
            return EXIT_NO_TPU
        self.counters = CompileCounters()
        self.tp = 4 if self.device["count"] >= 4 else 1
        self.plan()
        return 0

    def plan(self):
        """Sizes.  Real: Qwen3-8B widths, nothing cut but depth on one
        chip.  Rehearsal: `ModelConfig.tiny`, everything scaled down."""
        from triton_distributed_tpu.models.config import ModelConfig
        if self.rehearse:
            self.cfg = ModelConfig.tiny(num_layers=1)
            self.reduced = {"rehearsal": "ModelConfig.tiny, 1 layer"}
            self.slots = 4
            self.max_seq = 128
            self.prompt_lens = (12, 30, 60)       # buckets 16 / 32 / 64
            self.max_new = 3
            self.engine_shape = (4, 16, 7)
            self.watchdog_s = 600
        else:
            self.cfg = ModelConfig.qwen3_8b()
            full = self.cfg.num_layers
            if self.tp == 1:
                # 12 of 36 layers = 2.4 GB + embed/head 2.5 GB ... in
                # bf16 ~7.1 GB of weights, leaving over half of the
                # 16 GB for KV, activations and the xla-mode scores.
                self.cfg.num_layers = 12
            self.reduced = ({"num_layers": [full, self.cfg.num_layers]}
                            if self.cfg.num_layers != full else {})
            self.slots = 8 if self.tp == 1 else 16
            self.max_seq = 4096
            self.prompt_lens = (120, 500, 1500)   # 128 / 512 / 2048
            self.max_new = 32
            self.engine_shape = (8, 512, 32)
            # Several times the slowest cold phase measured (33 s on
            # one chip), and a small share of the 1200 s limit.
            self.watchdog_s = 480
        self.model_desc = {
            "config": "tiny" if self.rehearse else "qwen3_8b",
            "hidden": self.cfg.hidden_size,
            "heads": [self.cfg.num_heads, self.cfg.num_kv_heads,
                      self.cfg.head_dim],
            "ffn": self.cfg.intermediate_size,
            "vocab": self.cfg.vocab_size, "layers": self.cfg.num_layers,
            "dtype": self.cfg.dtype, "mode": "fused", "tp": self.tp}

    # -- phase harness ---------------------------------------------------

    def run_phase(self, name, fn) -> bool:
        jax = self.jax
        c0 = self.counters.since()
        t0 = time.perf_counter()
        fired = threading.Event()

        def on_timeout():
            fired.set()
            print(json.dumps({"phase": name, "ok": False,
                              "error": f"HUNG: no progress after "
                                       f"{self.watchdog_s}s"}),
                  flush=True)
            faulthandler.dump_traceback(file=sys.stderr)
            os._exit(EXIT_HUNG)

        dog = threading.Timer(self.watchdog_s, on_timeout)
        dog.daemon = True
        dog.start()
        detail, err = {}, None
        try:
            detail = fn() or {}
        except Exception as e:  # phase boundary: record, fail the run
            traceback.print_exc()
            err = f"{type(e).__name__}: {e}"[:2000]
        finally:
            dog.cancel()
        stats = [d.memory_stats() or {} for d in jax.devices()]
        rec = {
            "phase": name, "ok": err is None,
            "wall_s": round(time.perf_counter() - t0, 3),
            **self.counters.since(c0),
            "peak_hbm_bytes": max(
                (s.get("peak_bytes_in_use", 0) for s in stats),
                default=0),
            **detail}
        if err is not None:
            rec["error"] = err
        self.phases.append(rec)
        print(json.dumps(rec), flush=True)
        return err is None

    def bytes_in_use(self, what):
        """Per-device HBM in use on the mesh; at tp > 1 no device may
        hold more than 1.5x the least-loaded one."""
        per_dev = [(d.memory_stats() or {}).get("bytes_in_use")
                   for d in self.mesh.devices.flat]
        if all(per_dev) and max(per_dev) > 1.5 * min(per_dev):
            raise RuntimeError(
                f"{what} not spread: bytes_in_use {per_dev}")
        return per_dev

    # -- phases ------------------------------------------------------------

    def phase_block(self):
        """Does `block_until_ready` wait for the device?  Enqueue a
        chain of matmuls whose least possible device time is known from
        the chip's peak, and look at where the host waits."""
        jax = self.jax
        import jax.numpy as jnp
        import numpy as np

        n, reps = (512, 8) if self.rehearse else (8192, 64)

        def chain(x):
            def body(_, a):
                y = jnp.dot(a, a, preferred_element_type=jnp.float32)
                return (y * (1.0 / n)).astype(a.dtype)
            return jax.lax.fori_loop(0, reps, body, x)

        @jax.jit
        def chain_probe(x):
            y = chain(x)
            return y, y[:1, :1]        # 2 bytes to fetch, same program

        x = jnp.ones((n, n), jnp.bfloat16)
        np.asarray(chain_probe(x)[1])              # compile + warm
        t0 = time.perf_counter()
        y, corner = chain_probe(x)
        t_dispatch = time.perf_counter() - t0
        jax.block_until_ready(y)
        t_block = time.perf_counter() - t0
        t1 = time.perf_counter()
        np.asarray(corner)
        t_fetch = time.perf_counter() - t1
        # Least device time: 2 n^3 flops per matmul at the bf16 peak of
        # the v5e (197 TFLOP/s); a CPU rehearsal has no such floor.
        floor_s = 0.0 if self.rehearse else reps * 2 * n ** 3 / 197e12
        blocks = bool(t_block >= floor_s and t_fetch < 0.25 * t_block)
        self.facts["block_until_ready_blocks"] = blocks
        detail = {"dispatch_s": round(t_dispatch, 5),
                  "until_ready_s": round(t_block, 5),
                  "fetch_after_ready_s": round(t_fetch, 5),
                  "device_floor_s": round(floor_s, 5),
                  "block_until_ready_blocks": blocks}
        if not blocks and not self.rehearse:
            raise RuntimeError(
                f"block_until_ready returned before the device was "
                f"done: {detail}")
        return detail

    def phase_load(self):
        jax = self.jax
        import numpy as np
        from jax.sharding import Mesh

        from triton_distributed_tpu.models.qwen import Qwen3

        devs = jax.devices()[:self.tp]
        self.mesh = Mesh(np.array(devs), ("tp",))
        self.model = Qwen3(self.cfg, self.mesh, mode="fused")
        self.model_xla = Qwen3(self.cfg, self.mesh, mode="xla")
        self.params = self.model.init_params(
            jax.random.key(self.args.seed))
        jax.block_until_ready(self.params)
        nbytes = sum(x.nbytes for x in jax.tree.leaves(self.params))
        per_dev = self.bytes_in_use("weights")
        print(f"load: {nbytes / 1e9:.2f} GB of weights, bytes_in_use "
              f"per device {per_dev}", flush=True)
        return {"weight_bytes": nbytes, "bytes_in_use": per_dev}

    def _requests(self):
        """~8 seeded requests around the three prompt sizes."""
        import numpy as np

        from triton_distributed_tpu.serving import Request
        rng = np.random.default_rng(self.args.seed)
        reqs = []
        for i in range(8):
            base = self.prompt_lens[i % 3]
            n = int(base * rng.uniform(0.85, 1.0))
            prompt = rng.integers(0, self.cfg.vocab_size, n).tolist()
            reqs.append(Request(prompt, self.max_new, seed=i))
        return reqs

    def _serve(self, layout):
        from triton_distributed_tpu.serving import (
            ContinuousBatchingScheduler, FinishReason, SchedulerConfig)
        sched = ContinuousBatchingScheduler(
            self.model, self.params,
            SchedulerConfig(num_slots=self.slots, max_seq=self.max_seq,
                            kv_layout=layout))
        per_dev = self.bytes_in_use("weights + cache")
        reqs = self._requests()
        rejected = [r.request_id for r in reqs if not sched.submit(r)]
        done = sched.drain()
        bad = [r.to_dict() for r in reqs
               if r.finish_reason != FinishReason.LENGTH
               or len(r.generated) != self.max_new
               or not all(0 <= t < self.cfg.vocab_size
                          for t in r.generated)]
        if rejected or bad or len(done) != len(reqs):
            raise RuntimeError(
                f"{layout}: rejected={rejected} unfinished/bad={bad} "
                f"done={len(done)}/{len(reqs)}")
        return {"requests": len(reqs), "finished": len(done),
                "tokens": sum(len(r.generated) for r in reqs),
                "buckets": sorted({r.bucket for r in reqs}),
                "bytes_in_use": per_dev,
                "streams": [r.generated for r in reqs]}

    def phase_paged(self):
        out = self._serve("paged")
        self.paged_streams = out.pop("streams")
        return out

    def phase_slots(self):
        out = self._serve("slots")
        streams = out.pop("streams")
        # A count, not a check: with random weights the argmax flips on
        # rounding, and the two layouts split the KV differently.
        same = sum(a == b for a, b in zip(streams, self.paged_streams))
        out["streams_equal_to_paged"] = f"{same}/{len(streams)}"
        return out

    def phase_engine(self):
        jax = self.jax
        import numpy as np

        from triton_distributed_tpu.models.engine import Engine
        b, s, gen = self.engine_shape
        ids = jax.random.randint(jax.random.key(self.args.seed + 1),
                                 (b, s), 0, self.cfg.vocab_size)
        self.engine = Engine(self.model)
        toks = np.asarray(self.engine.serve(self.params, ids, gen))
        if toks.shape != (b, gen) or not (
                (toks >= 0) & (toks < self.cfg.vocab_size)).all():
            raise RuntimeError(f"Engine.serve returned {toks.shape}")
        return {"batch": b, "prompt": s, "new_tokens": gen,
                "scan_decode": True}

    def phase_logits(self):
        """Correctness, outside any timing: same params, fused vs xla.
        Last-position prefill logits per bucket; then one dense and one
        paged decode step on the SERVING path's own artefacts (bucket
        prefill -> insert at offset s-1 -> decode), each against the
        xla-mode decode step AND against the xla-mode full forward —
        which at world 1 contains no Pallas kernel at all."""
        jax = self.jax
        import jax.numpy as jnp
        import numpy as np

        from triton_distributed_tpu.serving.engine_batched import (
            make_insert_fn, make_paged_insert_fn, pick_bucket)
        from triton_distributed_tpu.serving.scheduler import (
            DEFAULT_PREFILL_BUCKETS)

        fused, xla, params = self.model, self.model_xla, self.params
        pre_f = jax.jit(fused.make_prefill_fn())
        pre_x = jax.jit(xla.make_prefill_fn())
        checks = {}
        tol_rms, tol_max = logit_tolerance(self.cfg.num_layers)

        def compare(name, got, ref):
            got = np.asarray(got, np.float32)
            ref = np.asarray(ref, np.float32)
            if got.shape != ref.shape or not np.isfinite(got).all():
                raise RuntimeError(f"{name}: shape {got.shape} vs "
                                   f"{ref.shape} or non-finite logits")
            rms = float(np.sqrt(np.mean(ref ** 2)))
            rel_rms = float(np.sqrt(np.mean((got - ref) ** 2))) / rms
            rel_max = float(np.abs(got - ref).max()) / rms
            checks[name] = {"rel_rms": round(rel_rms, 5),
                            "rel_max": round(rel_max, 5)}
            if rel_rms > tol_rms or rel_max > tol_max:
                raise RuntimeError(
                    f"{name}: rel_rms={rel_rms:.4f} (tol {tol_rms:.4f}) "
                    f"rel_max={rel_max:.4f} (tol {tol_max:.4f}) — "
                    f"{checks}")

        rows = {}
        for i, n in enumerate(self.prompt_lens):
            bucket = pick_bucket(n, DEFAULT_PREFILL_BUCKETS)
            ids = jax.random.randint(
                jax.random.key(self.args.seed + 10 + i), (1, bucket), 0,
                self.cfg.vocab_size)
            row_in = fused.create_cache(1, max_seq=bucket)
            lf, row = pre_f(params, ids, row_in)
            lx, _ = pre_x(params, ids, row_in)
            compare(f"prefill_{bucket}", lf, lx)
            rows[bucket] = (ids, row, lx)

        # The middle bucket's full-length prompt through the serving
        # path: its last token re-enters at offset s-1 and the decode
        # step must reproduce the full forward's last-position logits.
        bucket = sorted(rows)[1]
        ids, row, ref = rows[bucket]
        tokens = jnp.zeros((self.slots,), jnp.int32).at[0].set(
            ids[0, -1])
        keys = jnp.zeros((self.slots, 2), jnp.uint32)
        key = jax.random.PRNGKey(0)

        dense = fused.create_cache(self.slots, max_seq=self.max_seq)
        dense, _ = make_insert_fn()(dense, keys, row, key, jnp.int32(0),
                                    jnp.int32(bucket - 1))
        dec_f = jax.jit(fused.make_decode_fn())
        dec_x = jax.jit(xla.make_decode_fn())
        lf = dec_f(params, tokens, dense)[0][:1]
        compare("decode_dense_vs_xla_step", lf,
                dec_x(params, tokens, dense)[0][:1])
        compare("decode_dense_vs_xla_forward", lf, ref)
        del dense

        ps = 16
        t = self.max_seq // ps
        n_pages = 1 + self.slots * t
        pool = fused.create_paged_cache(self.slots, n_pages, ps, t)
        rng = np.random.default_rng(self.args.seed)
        page_ids = rng.permutation(np.arange(1, n_pages))[
            :bucket // ps].astype(np.int32)        # a scattered pool
        pool, _ = make_paged_insert_fn()(
            pool, jnp.zeros((self.slots, 2), jnp.uint32), row, key,
            jnp.int32(0), jnp.asarray(page_ids), jnp.int32(bucket - 1))
        table = np.zeros((self.slots, t), np.int32)
        table[0, :len(page_ids)] = page_ids
        pool = pool.with_page_table(table)
        pdec_f = jax.jit(fused.make_paged_decode_fn(page_size=ps))
        pdec_x = jax.jit(xla.make_paged_decode_fn(page_size=ps))
        lf = pdec_f(params, tokens, pool)[0][:1]
        compare("decode_paged_vs_xla_step", lf,
                pdec_x(params, tokens, pool)[0][:1])
        compare("decode_paged_vs_xla_forward", lf, ref)
        return {"tolerance": {"rel_rms": round(tol_rms, 5),
                              "rel_max": round(tol_max, 5)},
                "checks": checks}

    def phase_profile(self):
        """Four traced steady-state decode steps through the engine's
        own hook; the trace must hold a device plane with events."""
        jax = self.jax
        b, s, _ = self.engine_shape
        ids = jax.random.randint(jax.random.key(self.args.seed + 2),
                                 (b, s), 0, self.cfg.vocab_size)
        tmp = tempfile.mkdtemp(prefix="chip_smoke_prof_")
        cwd = os.getcwd()
        try:
            # Engine.serve traces into ./prof (`group_profile`'s
            # default): run it from a scratch directory.
            os.chdir(tmp)
            # first token + 2 warm-up steps + 4 traced steps: no rollout
            self.engine.serve(self.params, ids, 7, profile_decode_steps=4)
        finally:
            os.chdir(cwd)
        try:
            found = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                              recursive=True)
            if not found:
                raise RuntimeError("jax.profiler wrote no .xplane.pb")
            data = jax.profiler.ProfileData.from_file(found[0])
            want = "/host:" if self.rehearse else "/device:TPU:"
            planes = {}
            for plane in data.planes:
                events = (e for line in plane.lines for e in line.events)
                planes[plane.name] = (
                    sum(1 for _ in events) if not self.rehearse
                    else int(any(True for _ in events)))
            hit = {k: v for k, v in planes.items()
                   if k.startswith(want) and v > 0}
            # A steady window holds no compilation (a count, reported).
            compiles = sum(
                1 for plane in data.planes if plane.name == "/host:CPU"
                for line in plane.lines
                if not line.name.startswith("python")
                for e in line.events if e.name == "PJRT_Client_Compile")
            self.facts["profiler_tpu_plane"] = bool(
                hit) and not self.rehearse
            keep = os.path.join(cwd, "chiprun_out", "chip_smoke")
            if os.path.isdir(os.path.join(cwd, "chiprun_out")):
                os.makedirs(keep, exist_ok=True)
                shutil.copy(found[0], os.path.join(
                    keep, f"decode_steps_tp{self.tp}.xplane.pb"))
            if not hit:
                raise RuntimeError(
                    f"trace has no {want}* plane with events: {planes}")
            return {"xplane_bytes": os.path.getsize(found[0]),
                    "planes": planes, "compiles_in_window": compiles}
        finally:
            shutil.rmtree(tmp, ignore_errors=True)

    def phase_methods(self, events):
        """Which method ag_gemm / gemm_rs resolved to at prefill rows
        and at decode rows.  At tp > 1 a pass that ran only XLA
        collectives proves nothing: at least one must be a Pallas ring
        ("fused") or one-shot ("ll") kernel."""
        seen = {}
        for e in events:
            if e.kind == "fused_gemm":
                # shape = (rows per rank, n, k)
                regime = ("decode" if e.shape[0] == self.slots // self.tp
                          else "prefill")
                seen.setdefault(f"{e.op}@{regime}", set()).add(e.method)
        seen = {k: sorted(v) for k, v in sorted(seen.items())}
        pallas = any(m in ("fused", "ll") for v in seen.values()
                     for m in v)
        if self.tp > 1 and not pallas:
            raise RuntimeError(f"no Pallas overlap kernel ran: {seen}")
        return {"overlap_gemm_methods": seen, "pallas_overlap": pallas}

    # -- driver ------------------------------------------------------------

    def run(self) -> int:
        rc = self.start()
        if rc:
            return rc
        from triton_distributed_tpu.observability.events import (
            capture_events)
        t0 = time.perf_counter()
        ok = self.run_phase("block_until_ready", self.phase_block)
        with capture_events() as events:
            loaded = self.run_phase("load", self.phase_load)
            ok &= loaded
            if loaded:
                # Four real chips cost four times the minutes: there,
                # only what one chip cannot show.
                names = (["paged", "logits"]
                         if self.tp > 1 and not self.rehearse else
                         ["paged", "slots", "engine", "logits", "profile"])
                for name in names:
                    if name == "profile" and self.engine is None:
                        continue          # its engine phase failed
                    ok &= self.run_phase(
                        name, getattr(self, f"phase_{name}"))
                ok &= self.run_phase(
                    "methods", lambda: self.phase_methods(events))
        misses = sum(p["cache_misses"] for p in self.phases)
        hits = sum(p["cache_hits"] for p in self.phases)
        if self.warm:
            # A second fresh process finds every program in the cache.
            # (Interpret-mode kernels of a CPU rehearsal carry host
            # callbacks, which JAX never persists.)
            cache_ok = hits > 0 and (misses == 0 or self.rehearse)
            print(json.dumps({"phase": "compile_cache", "ok": cache_ok,
                              "hits": hits, "misses": misses,
                              "dir": self.cache_dir}), flush=True)
            ok &= cache_ok
        summary = {
            "ok": bool(ok), "pass": self.args.which,
            "device": self.device, "versions": self.versions,
            "model": self.model_desc, "reduced": self.reduced,
            "compile_cache_dir": self.cache_dir,
            "phases": self.phases,
            "total_s": round(time.perf_counter() - t0, 3),
            **self.facts}
        with open(self.args.summary, "w") as f:
            json.dump(summary, f)
        return 0 if ok else EXIT_FAILED


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="weights, prompts and page scatter")
    ap.add_argument("--rehearse", action="store_true",
                    help="ModelConfig.tiny, CPU allowed; output marked "
                         "'rehearsal': true, never a pass")
    ap.add_argument("--pass", dest="which", choices=("cold", "warm"),
                    help=argparse.SUPPRESS)       # child mode
    ap.add_argument("--summary", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.which:
        faulthandler.enable()
        return Child(args).run()
    return parent(args)


if __name__ == "__main__":
    sys.exit(main())
