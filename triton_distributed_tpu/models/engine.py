"""Serving engine: prefill + fully-compiled decode loop.

Reference: `python/triton_dist/models/engine.py` (187 LoC) —
`Engine.serve` (`:113-188`): torch prefill, backend switch, CUDA-graph
captured decode (`_init_cuda_graph:75-105`), sampling, profiling hook.

TPU: the decode step is one jitted program with the KV cache donated
(buffer reuse in place of CUDA-graph memory reuse); `lax.scan` rolls
`gen_len` steps into a single compiled loop, so steady-state decode has
zero Python/dispatch overhead — the XLA equivalent of graph replay.
"""

from __future__ import annotations

import time
from typing import Optional

import jax
import jax.numpy as jnp

from triton_distributed_tpu.models.qwen import Qwen3
from triton_distributed_tpu.models.utils import sample_token
from triton_distributed_tpu.utils.profiling import group_profile


class Engine:
    def __init__(self, model: Qwen3, temperature: float = 0.0,
                 top_k: int = 0, top_p: float = 1.0,
                 scan_decode: bool = True):
        self.model = model
        self.temperature = temperature
        self.top_k = top_k
        self.top_p = top_p
        self.scan_decode = scan_decode
        self._prefill = jax.jit(model.make_prefill_fn())
        decode_fn = model.make_decode_fn()

        # The step/rollout composition is shared with the
        # continuous-batching runtime (serving.engine_batched): Engine
        # is the thin static-batch client of the same code.  Imported
        # lazily — serving.engine_batched imports models submodules.
        from triton_distributed_tpu.serving.engine_batched import (
            make_rollout_fn, make_step_fn)

        step = make_step_fn(decode_fn, temperature, top_k=top_k,
                            top_p=top_p)
        # donate cache so XLA updates it in place across steps
        self._step = jax.jit(step, donate_argnums=(2,))
        self._rollout = jax.jit(make_rollout_fn(step),
                                static_argnums=(4,), donate_argnums=(2,))
        #: Shapes served so far: the first call per shape pays jit
        #: trace+compile (tens of seconds on TPU) and must not land in
        #: the steady-state latency histograms.
        self._served_shapes = set()

    def prefill(self, params, input_ids, cache):
        return self._prefill(params, input_ids, cache)

    def serve(self, params, input_ids, gen_len: int,
              key: Optional[jax.Array] = None, profile: bool = False,
              profile_decode_steps: int = 0, cache=None):
        """input_ids: (B, S) — S and B must tile the tp axis (pad
        upstream).  Returns generated tokens (B, gen_len).

        ``profile_decode_steps``: trace only that many steady-state
        decode steps (the reference Engine captures 64 decode steps to
        `trace_static.json`, `models/engine.py:151-177`); implies the
        per-step loop for the traced prefix.

        ``cache``: caller-provided KV cache to reuse instead of
        allocating (and zeroing) a fresh one per call — its offset is
        reset, stale KV beyond the new offset is never attended.  When
        given, serve returns ``(tokens, cache)``; the cache is donated
        through the decode jits, so the caller MUST rebind to the
        returned one (the passed-in buffer is consumed).  This is what
        lets a serving loop issue back-to-back serves without
        re-zeroing HBM.
        """
        key = key if key is not None else jax.random.key(0)
        b, s = input_ids.shape
        caller_cache = cache is not None
        if caller_cache:
            assert int(cache.offset.shape[0]) == b, (
                f"cache batch {cache.offset.shape[0]} != input batch {b}")
            # Undersized caches fail loudly: decode's KV writes clamp
            # at max_seq-1, which would silently corrupt the last row.
            cache_seq = int(cache.ks[0].shape[2])
            assert s + gen_len <= cache_seq + 1, (
                f"cache max_seq={cache_seq} cannot hold prompt {s} + "
                f"gen_len {gen_len}")
            cache = cache.set_offset(0)
        else:
            cache = self.model.create_cache(b)

        # Serving metrics (opt-out with the rest of observability):
        # prefill tokens/s, steady-state decode ms/step, KV occupancy.
        # The only extra device sync is ONE block after prefill — serve
        # already blocks at the end, so steady-state decode pays
        # nothing.  Runtime spans (observability.tracing) bracket the
        # same phases for the cross-rank timeline; the scan path is one
        # dispatch, so it gets ONE span, not per-step spans (per-step
        # host timing does not exist there by design).
        from triton_distributed_tpu.observability import (
            observability_enabled, set_step, span)
        obs = observability_enabled()
        t_serve0 = time.perf_counter()

        with span("engine.serve", batch=b, prompt_len=s,
                  gen_len=gen_len), \
                group_profile("engine_serve", do_prof=profile):
            with span("engine.prefill", batch=b, prompt_len=s):
                logits, cache = self.prefill(params, input_ids, cache)
                if obs:
                    jax.block_until_ready(logits)
                    t_prefill = time.perf_counter() - t_serve0
            first = sample_token(logits, key, self.temperature,
                                 top_k=self.top_k, top_p=self.top_p)
            tokens = [first]
            cur = first
            # The two warm-up steps consume generation slots too.
            n_prof = min(profile_decode_steps, max(gen_len - 3, 0))
            if n_prof > 0:
                # Warm the step jit before tracing, then capture only
                # steady-state steps.  TWO warm-ups: the first step's
                # token, key and cache come from prefill and the host,
                # every later step's from the step itself — committed,
                # differently-sharded arguments, a second jit
                # signature with its own compilation (seen on the v5e:
                # a 3.5 s compile inside a one-warm-up window).  When
                # an outer trace is already active (profile=True)
                # don't start a nested one.
                for _ in range(2):
                    cur, cache, key = self._step(params, cur, cache, key)
                    tokens.append(cur)
                jax.block_until_ready(cur)
                with group_profile("engine_decode_steps",
                                   do_prof=not profile):
                    for _ in range(n_prof):
                        if obs:
                            set_step(len(tokens))
                        with span("engine.decode_step",
                                  step=len(tokens)):
                            cur, cache, key = self._step(
                                params, cur, cache, key)
                        tokens.append(cur)
                    # The device runs behind the host: a trace stopped
                    # at dispatch holds no device event at all.
                    jax.block_until_ready(cur)
            remaining = gen_len - len(tokens)
            if remaining > 0:
                if self.scan_decode:
                    with span("engine.decode_scan", steps=remaining):
                        toks, cache = self._rollout(params, cur, cache,
                                                    key, remaining)
                    out = jnp.concatenate(
                        [jnp.stack(tokens, axis=1), toks], axis=1)
                else:
                    for _ in range(remaining):
                        if obs:
                            set_step(len(tokens))
                        with span("engine.decode_step",
                                  step=len(tokens)):
                            cur, cache, key = self._step(
                                params, cur, cache, key)
                        tokens.append(cur)
                    out = jnp.stack(tokens, axis=1)
            else:
                out = jnp.stack(tokens, axis=1)
            # inside the (optional) whole-serve trace: see above
            jax.block_until_ready(out)
        if obs:
            # Cold key includes the profile-steps knob: it shifts the
            # rollout's static `remaining` arg, which retraces and
            # recompiles even at an already-seen (b, s, gen_len).
            self._record_serve_metrics(
                b, s, gen_len, cache, t_prefill,
                time.perf_counter() - t_serve0,
                shape_key=(b, s, gen_len, profile_decode_steps,
                           self.scan_decode))
        if caller_cache:
            return out, cache
        return out

    def _record_serve_metrics(self, b, s, gen_len, cache, t_prefill,
                              t_total, shape_key=None):
        """Emit one "engine" event + gauges/histograms per serve call.
        Decode latency is (total - prefill) / steps — steady-state
        steps run inside one compiled scan, so per-step host timing
        does not exist by design (that IS the optimisation).

        The first call per shape includes jit trace+compile time: it
        emits an event tagged ``cold=True`` but is kept OUT of the
        process-lifetime histograms/gauges, which would otherwise be
        dominated forever by the one compile outlier."""
        from triton_distributed_tpu.observability import (
            emit_kernel_event, get_registry)
        shape_key = shape_key or (b, s, gen_len)
        cold = shape_key not in self._served_shapes
        self._served_shapes.add(shape_key)
        reg = get_registry()
        decode_steps = max(gen_len - 1, 1)
        t_decode = max(t_total - t_prefill, 1e-9)
        ms_per_step = t_decode / decode_steps * 1e3
        prefill_tps = b * s / max(t_prefill, 1e-9)
        try:
            max_seq = cache.ks[0].shape[2]
            occupancy = min((s + gen_len) / max_seq, 1.0)
        except (AttributeError, IndexError):
            occupancy = None
        reg.counter("engine_tokens_generated_total").inc(b * gen_len)
        if not cold:
            reg.histogram("engine_prefill_ms").observe(t_prefill * 1e3)
            reg.histogram("engine_decode_step_ms").observe(ms_per_step)
            reg.gauge("engine_prefill_tokens_per_s").set(prefill_tps)
            reg.gauge("engine_decode_tokens_per_s").set(
                b * decode_steps / t_decode)
            if occupancy is not None:
                reg.gauge("engine_kv_cache_occupancy").set(occupancy)
        emit_kernel_event(
            "engine_serve", kind="engine", shape=(b, s),
            measured_us=t_total * 1e6, cold=cold,
            batch=b, prompt_len=s, gen_len=gen_len,
            prefill_ms=round(t_prefill * 1e3, 3),
            decode_ms_per_step=round(ms_per_step, 4),
            prefill_tokens_per_s=round(prefill_tps, 1),
            kv_occupancy=occupancy)
