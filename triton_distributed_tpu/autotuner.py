"""Contextual autotuner: tunes whole multi-kernel, side-effectful,
distributed thunks — not single kernels.

Reference: `python/triton_dist/autotuner.py` (256 LoC) —
`ContextualAutoTuner.__call__:68-93`, `contextual_autotune:95`,
`_do_bench_iterator:104`; config errors → skip & retry; per-rank logs
`.autotune_logs/rank-N.log`; distributed aggregation so every rank
picks the same winner (docs/autotuner.md).

TPU notes: a "config" here is typically a `MatmulConfig` or a method
enum; candidates that fail to compile (Mosaic tiling limits) are
skipped like the reference skips CUDA OOM configs.  Under multi-process
JAX, every process times the same candidates on its own devices and the
winner is agreed by broadcasting process 0's choice, so all ranks run
identical programs afterwards.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import os
import time
from typing import Any, Callable, Optional, Sequence

import jax

try:
    import fcntl
except ImportError:  # non-POSIX platform: fall back to lockless saves
    fcntl = None

from triton_distributed_tpu.utils.debug import logger


@dataclasses.dataclass
class _Entry:
    config: Any
    time_s: float
    #: Full (time_s, config) ranking, fastest first — lets callers
    #: re-examine finalists whose margin is within measurement noise.
    ranking: list = dataclasses.field(default_factory=list)
    #: Closed-loop staleness marker ({"z", "ts"}), persisted beside
    #: the disk entry: a winner whose live latency drifted multi-sigma
    #: off its baseline is demoted to its second-best until a re-tune
    #: lands (observability.feedback; None = trusted).
    stale: Any = None


class ContextualAutotuner:
    def __init__(self, fn: Callable, configs: Sequence[Any],
                 key_fn: Optional[Callable] = None,
                 iters: int = 5, warmup: int = 2,
                 log_dir: str = ".autotune_logs",
                 chain: Optional[Callable] = None,
                 cache_path: Optional[str] = None,
                 jit_configs: bool = False):
        self.fn = fn
        self.configs = list(configs)
        self.key_fn = key_fn or self._default_key
        self.iters = iters
        self.warmup = warmup
        self.log_dir = log_dir
        #: Wrap each candidate in its own `jax.jit` closure.  A RAW
        #: (unjitted) fn retraces on EVERY chained call — pure Python
        #: tracing that drowns a ~40 µs kernel by orders of
        #: magnitude.  Off by default only because
        #: some callers pass pre-jitted thunks.
        self.jit_configs = jit_configs
        self._config_jits = {}
        #: With ``jit_configs`` + a ``chain``, each timing sample runs
        #: ``scan_inner`` chained iterations inside ONE jitted
        #: `lax.scan` (one dispatch for the whole chain): ops
        #: under ~150 µs CANNOT be ranked by per-dispatch chains — the
        #: host's dispatch floor dominates and the tuner picks noise.
        self.scan_inner = 16
        #: Optional ``chain(out, *args) -> new_args``: threads each
        #: call's output back into the next call's inputs.  Without it
        #: N queued calls keep N live output buffers (HBM pressure
        #: distorts timings at large N), so unchained runs should keep
        #: ``iters`` modest.
        self.chain = chain
        self.cache = {}
        #: Optional JSON file persisting winners across processes (the
        #: role of Triton's on-disk autotune cache).  Entries are keyed
        #: by device kind + world size + the call key, and configs are
        #: matched back by repr — a candidate list change invalidates
        #: stale entries naturally (no repr match → re-tune).
        self.cache_path = cache_path
        self._disk = self._load_disk() if cache_path else {}
        #: Optional feedback bus (`observability.feedback.SignalBus`):
        #: on cache hits the tuner asks it whether the cached winner's
        #: live latency has drifted multi-sigma off its rolling
        #: baseline.  None = consult the ambient bus (armed by
        #: TDT_CLOSED_LOOP=1); with neither, hits behave exactly as
        #: before.
        self.bus = None
        #: Run staleness-triggered re-tunes synchronously instead of
        #: on a daemon thread (tests / latency-insensitive callers).
        self.retune_inline = False
        #: Keys whose staleness has already been acted on this
        #: process (don't re-demote per call) / re-tunes in flight.
        self._stale_handled: set = set()
        self._retunes_inflight: set = set()

    def _device_key(self) -> str:
        d = jax.devices()[0]
        # Include the tuned function's identity: two tuners for
        # different ops sharing one cache_path (same arg shapes, same
        # candidate reprs) must not reuse each other's winners.
        # Module-qualified (bare __qualname__ like "main.<locals>.op"
        # collides across scripts), with a STABLE fallback for
        # callables — repr() would embed a memory address and the key
        # would never hit across processes.  functools.partial has no
        # __qualname__: unwrap to the underlying function so two
        # partials of DIFFERENT ops don't collapse to one key.
        return f"{d.device_kind}/w{jax.device_count()}/{self._fn_id()}"

    def _fn_id(self) -> str:
        fn = self.fn
        while isinstance(fn, functools.partial):
            fn = fn.func
        mod = getattr(fn, "__module__", None)
        qual = getattr(fn, "__qualname__", None)
        return f"{mod}.{qual}" if mod and qual else type(fn).__name__

    def _load_disk(self) -> dict:
        try:
            with open(self.cache_path) as f:
                return json.load(f)
        except Exception:
            return {}

    def _save_disk(self):
        try:
            # Locked merge-on-save: two processes saving concurrently
            # between each other's load and os.replace would otherwise
            # drop the other's freshly-tuned entries on shared-FS
            # multi-rank runs.  No fcntl (non-POSIX): lockless merge.
            if fcntl is not None:
                with open(self.cache_path + ".lock", "w") as lock:
                    fcntl.flock(lock, fcntl.LOCK_EX)
                    self._merge_save()
            else:
                self._merge_save()
        except Exception as e:
            logger.warning("autotune cache write failed: %s", e)

    def _merge_save(self):
        merged = self._load_disk()
        merged.update(self._disk)
        self._disk = merged
        tmp = self.cache_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self._disk, f, indent=1)
        os.replace(tmp, self.cache_path)

    def _candidates_repr(self) -> list:
        return sorted(repr(c) for c in self.configs)

    def _disk_lookup(self, key):
        """Rebuild an _Entry from the persisted ranking.  The entry is
        valid only for the EXACT candidate list it was tuned over —
        a grown space would otherwise silently never benchmark the new
        candidates, and a shrunk one could resurrect a removed best."""
        rec = self._disk.get(f"{self._device_key()}|{key}")
        if not rec:
            return None
        if rec.get("candidates") != self._candidates_repr():
            return None  # candidate list changed: stale entry
        by_repr = {repr(c): c for c in self.configs}
        ranking = [(t, by_repr[r]) for t, r in rec.get("ranking", [])
                   if r in by_repr]
        if not ranking or rec.get("best") not in by_repr:
            return None
        # The persisted staleness marker (closed-loop invalidation)
        # rides along so the demotion survives a process restart.
        return _Entry(by_repr[rec["best"]], ranking[0][0], ranking,
                      stale=rec.get("stale"))

    @staticmethod
    def _default_key(*args, **kwargs):
        def leaf_key(x):
            if hasattr(x, "shape") and hasattr(x, "dtype"):
                return (tuple(x.shape), str(x.dtype))
            return x if isinstance(x, (int, float, str, bool, tuple)) else None
        return tuple(jax.tree.map(leaf_key, (args, tuple(sorted(
            kwargs.items())))) .__repr__().split())  # stable string key

    def _config_fn(self, config) -> Callable:
        """The callable used to run one candidate ONCE (per-config jit
        when ``jit_configs``; the raw fn otherwise)."""
        if not self.jit_configs:
            return functools.partial(self.fn, config=config)
        key = ("call", repr(config))
        f = self._config_jits.get(key)
        if f is None:
            f = jax.jit(functools.partial(self.fn, config=config))
            self._config_jits[key] = f
        return f

    def _bench_fn(self, config, have_kwargs: bool = False) -> tuple:
        """(callable, calls_per_dispatch) used for TIMING one
        candidate.  With jit_configs + chain, the callable runs
        ``scan_inner`` chained iterations inside one jitted scan and
        returns the final chained args.  The scanned wrapper takes
        positional args only — kwarg calls fall back to the
        single-call path rather than TypeError-ing out of every
        candidate."""
        if have_kwargs or not (self.jit_configs and self.chain
                               and self.scan_inner):
            return self._config_fn(config), 1
        key = ("scan", repr(config))
        f = self._config_jits.get(key)
        if f is None:
            fn, chain, n = self.fn, self.chain, self.scan_inner

            def scanned(*a):
                def body(c, _):
                    out = fn(*c, config=config)
                    return tuple(chain(out, *c)), None

                final, _ = jax.lax.scan(body, tuple(a), None, length=n)
                return final

            f = jax.jit(scanned)
            self._config_jits[key] = f
        return f, self.scan_inner

    def _bench_one(self, config, args, kwargs) -> float:
        """Two-point fit: dispatches pipeline on the device queue, and
        each sample ends in one fence.  Timing N1 and N2 dispatches
        and differencing removes every fixed per-sample cost:
        t = (T(N2) - T(N1)) / (N2 - N1)."""
        run, per_dispatch = self._bench_fn(config, bool(kwargs))
        for _ in range(max(self.warmup, 1)):
            out = run(*args, **kwargs)
        jax.block_until_ready(out)
        scanned = per_dispatch > 1

        def total(n_calls: int) -> float:
            t0 = time.perf_counter()
            cur = args
            out = None
            for _ in range(n_calls):
                out = run(*cur, **kwargs)
                if scanned:
                    cur = tuple(out)       # scan returns chained args
                elif self.chain is not None:
                    cur = self.chain(out, *cur)
            jax.block_until_ready(out)
            return time.perf_counter() - t0

        import statistics
        n1, n2 = self.iters, 6 * self.iters
        t1s, t2s = [], []
        for _ in range(3):  # interleave to decorrelate drift
            t1s.append(total(n1))
            t2s.append(total(n2))
        return max((statistics.median(t2s) - statistics.median(t1s))
                   / ((n2 - n1) * per_dispatch), 1e-9)

    def _log(self, msg: str):
        try:
            os.makedirs(self.log_dir, exist_ok=True)
            rank = jax.process_index()
            with open(os.path.join(self.log_dir, f"rank-{rank}.log"),
                      "a") as f:
                f.write(msg + "\n")
        except Exception:
            pass

    def _agree(self, choice_idx: int) -> int:
        """All processes adopt process 0's winner (reference:
        distributed aggregation of tuning results)."""
        if jax.process_count() <= 1:
            return choice_idx
        from jax.experimental import multihost_utils
        import numpy as np
        return int(multihost_utils.broadcast_one_to_all(
            np.int32(choice_idx)))

    def _collective_disk_hit(self, hit):
        """Make the disk hit/miss decision collective.  Under
        multi-process JAX a per-host cache file may exist on some hosts
        and not others; if hitting ranks skipped the benchmark while
        missing ranks ran it and called `broadcast_one_to_all`, the
        collective participation mismatch would hang (and even absent a
        hang, ranks could run different configs).  Rank 0's lookup is
        authoritative: if it hit, every rank adopts its winner by
        config index (candidate lists are identical across ranks — the
        module's identical-programs invariant); if it missed, every
        rank re-tunes, including local hitters."""
        if jax.process_count() <= 1:
            return hit
        from jax.experimental import multihost_utils
        import numpy as np
        reprs = [repr(c) for c in self.configs]
        idx = -1
        if hit is not None and repr(hit.config) in reprs:
            idx = reprs.index(repr(hit.config))
        idx = int(multihost_utils.broadcast_one_to_all(np.int32(idx)))
        if idx < 0:
            return None
        cfg = self.configs[idx]
        if hit is not None and repr(hit.config) == reprs[idx]:
            return hit  # local entry agrees: keep its timing/ranking
        # Adopted without a local measurement: NaN timing + empty
        # ranking, so consumers of time_s/ranking (finalist
        # re-examination by margin) can't mistake a fabricated 0.0 for
        # a real result.  Never persisted: __call__ only writes disk
        # entries on the re-tune path.
        return _Entry(cfg, float("nan"), [])

    def _metrics(self):
        """Registry hooks (None when observability is off)."""
        from triton_distributed_tpu.observability import (
            get_registry, observability_enabled)
        return get_registry() if observability_enabled() else None

    def __call__(self, *args, **kwargs):
        key = self.key_fn(*args, **kwargs)
        reg = self._metrics()
        if key in self.cache and reg is not None:
            reg.counter("autotune_cache_hits_total", level="memory").inc()
        if key not in self.cache and self.cache_path:
            hit = self._collective_disk_hit(self._disk_lookup(key))
            if hit is not None:
                self.cache[key] = hit
                logger.info("autotune %s: disk cache hit, best=%s",
                            key, hit.config)
                if reg is not None:
                    reg.counter("autotune_cache_hits_total",
                                level="disk").inc()
        if key in self.cache:
            # Closed loop: a cache hit is only as good as the winner
            # still performing — consult the anomaly baselines before
            # trusting it (no-op without a bus / observability).
            self._check_winner_health(key, args, kwargs)
        if key not in self.cache:
            self.cache[key] = self._tune_now(key, args, kwargs)
        return self._config_fn(self.cache[key].config)(*args, **kwargs)

    def _tune_now(self, key, args, kwargs) -> _Entry:
        """Benchmark every candidate and persist the winner (the
        former __call__ miss path, shared with background re-tunes)."""
        from triton_distributed_tpu.observability import span
        reg = self._metrics()
        t_tune0 = time.perf_counter()
        results = []
        for i, cfg in enumerate(self.configs):
            try:
                # One runtime span per candidate trial: the tuning
                # wall time becomes attributable per-config on the
                # cross-rank timeline (a candidate that compiles
                # slowly on one rank shows up as that rank's span).
                with span("autotune.trial", op=self._fn_id(),
                          config=repr(cfg), index=i):
                    t = self._bench_one(cfg, args, kwargs)
                results.append((t, i))
                self._log(f"{key}: config[{i}]={cfg} -> {t*1e3:.3f} ms")
            except Exception as e:  # config invalid on this hw
                # Dropping a candidate is the tuner's job; the log
                # keeps the compiler's own message for whoever asks
                # why it was dropped.
                last_error = f"{type(e).__name__}: {e}"
                self._log(f"{key}: config[{i}]={cfg} FAILED: "
                          f"{last_error}")
        if not results:
            raise RuntimeError(
                f"autotune: every config failed for key {key}; "
                f"last: {last_error}")
        results.sort()
        best_idx = self._agree(results[0][1])
        ranking = [(t, self.configs[i]) for t, i in results]
        entry = _Entry(self.configs[best_idx], results[0][0], ranking)
        logger.info("autotune %s: best=%s (%.3f ms)", key,
                    self.configs[best_idx], results[0][0] * 1e3)
        if reg is not None:
            wall_s = time.perf_counter() - t_tune0
            reg.counter("autotune_cache_misses_total").inc()
            reg.histogram("autotune_tuning_seconds").observe(wall_s)
            from triton_distributed_tpu.observability import (
                emit_kernel_event)
            emit_kernel_event(
                # Plain function identity as the op (like every
                # other emitter): the device kind already rides in
                # the snapshot meta — a device-prefixed op would
                # explode label cardinality.
                self._fn_id(), kind="autotune",
                measured_us=results[0][0] * 1e6,
                config=repr(self.configs[best_idx]),
                tuning_wall_s=round(wall_s, 3),
                n_configs=len(self.configs),
                n_failed=len(self.configs) - len(results))
        if self.cache_path:
            # A fresh tune rewrites the disk entry WITHOUT any stale
            # marker — re-tuning is how an invalidated key heals.
            self._disk[f"{self._device_key()}|{key}"] = {
                "best": repr(self.configs[best_idx]),
                "ranking": [[t, repr(c)] for t, c in ranking],
                "candidates": self._candidates_repr(),
            }
            self._save_disk()
        return entry

    # -- closed-loop staleness (observability.feedback) ------------------

    def winner_baseline_key(self, config, scope: str = "") -> str:
        """The anomaly-baseline key runtime measurements of ``config``
        roll into (see :meth:`observe_runtime`) and the staleness
        check reads.  ``scope`` namespaces feeds that measure
        DIFFERENT quantities — the serving loop observes whole-step
        host latency while bench drivers observe the tuned op alone;
        mixing them in one rolling baseline would make its z-scores
        meaningless (a store warmed with ~50 µs kernel samples would
        flag every ~1 ms serving step as sustained-slow)."""
        from triton_distributed_tpu.observability.anomaly import (
            event_key)
        op = f"autotune:{self._fn_id()}"
        if scope:
            op = f"{op}#{scope}"
        return event_key(op, method=repr(config),
                         world=jax.device_count())

    def _observe_store(self):
        """The baseline store runtime observations roll into — the
        SAME store the staleness check reads through the bus, so a
        tuner wired to a private bus/store keeps a coherent loop
        (writing to the global store while reading a private one
        would leave invalidation silently inert)."""
        from triton_distributed_tpu.observability import feedback
        bus = self.bus if self.bus is not None else (
            feedback.ambient_bus())
        if bus is not None:
            store = bus.read().store
            if store is not None:
                return store
        from triton_distributed_tpu.observability.anomaly import (
            get_baseline_store)
        return get_baseline_store()

    def observe_runtime(self, key, us: float, scope: str = ""):
        """Roll one measured runtime of the cached winner for ``key``
        into its rolling baseline — the feed the staleness check
        consumes.  Callers with a host-side latency for the tuned op
        (bench drivers) call this bare; feeds measuring a different
        quantity (the serving loop's whole-step latency) pass a
        ``scope`` so each baseline stays self-consistent.  Returns
        the z-score (None while warming) like
        ``BaselineStore.observe``."""
        entry = self.cache.get(key)
        if entry is None:
            return None
        return self._observe_store().observe(
            self.winner_baseline_key(entry.config, scope), float(us))

    def arm_serving(self, *args, **kwargs) -> None:
        """Arm this tuner's entry for the given call signature to be
        fed by the serving decode loop (:func:`observe_serving_step`)
        — call it where the tuned serving op is built, after tuning."""
        arm_serving_observation(self, self.key_fn(*args, **kwargs))

    def _check_winner_health(self, key, args, kwargs) -> None:
        """On a cache hit: demote a winner whose live latency is
        SUSTAINED multi-sigma slow (or whose disk entry carries a
        persisted stale marker) to the second-best config, and
        schedule a background re-tune.  Exactly a no-op when
        observability is off or no bus (explicit or ambient) exists —
        the degradation contract is today's static behavior."""
        from triton_distributed_tpu.observability.metrics import (
            observability_enabled)
        if not observability_enabled() or key in self._stale_handled:
            return
        from triton_distributed_tpu.observability import feedback
        bus = self.bus if self.bus is not None else (
            feedback.ambient_bus())
        if bus is None:
            return
        entry = self.cache[key]
        from triton_distributed_tpu.observability.anomaly import (
            SUSTAINED_N, Z_THRESHOLD)
        stale = entry.stale          # persisted marker from disk
        if stale is None:
            # Sustained drift in EITHER feed acts: the bench-fed
            # kernel baseline and the serving-fed whole-step baseline
            # are separate (scoped) keys, each compared only against
            # itself.
            sig = bus.read()
            zs = [sig.sustained_z(
                      self.winner_baseline_key(entry.config, scope))
                  for scope in ("", SERVING_SCOPE)]
            zs = [z for z in zs if z is not None]
            z = max(zs) if zs else None
            if z is None or z < Z_THRESHOLD:
                return
            stale = {"z": round(float(z), 2), "ts": round(time.time(), 3),
                     "sustained_n": SUSTAINED_N}
        self._stale_handled.add(key)
        self._invalidate(key, entry, stale, args, kwargs)

    def _invalidate(self, key, entry: _Entry, stale: dict,
                    args, kwargs) -> None:
        from triton_distributed_tpu.observability import feedback
        fallback_reason = None
        choice = entry.config
        if len(entry.ranking) > 1:
            t2, choice = entry.ranking[1]
            self.cache[key] = _Entry(choice, t2, entry.ranking,
                                     stale=stale)
        else:
            # Nothing to fall back to: keep the winner, but say so.
            fallback_reason = "no_second_best"
            self.cache[key] = dataclasses.replace(entry, stale=stale)
        # Persist the marker beside the disk entry so the demotion
        # survives a process restart (the re-tune clears it).
        dkey = f"{self._device_key()}|{key}"
        if self.cache_path and dkey in self._disk:
            self._disk[dkey]["stale"] = stale
            self._save_disk()
        reg = self._metrics()
        if reg is not None:
            reg.counter("autotune_invalidations_total").inc()
        self._log(f"{key}: winner {entry.config} marked stale "
                  f"(z={stale.get('z')}), using {choice}")
        feedback.record_decision(feedback.DecisionEvent(
            consumer="autotune.invalidate", op=self._fn_id(),
            choice=repr(choice),
            candidates=[{"name": repr(c),
                         "score_us": round(t * 1e6, 3)}
                        for t, c in entry.ranking[:6]]
            or [{"name": repr(entry.config)}],
            inputs={"stale": stale,
                    "baseline_key": self.winner_baseline_key(
                        entry.config)},
            fallback=fallback_reason))
        self._schedule_retune(key, args, kwargs)

    def _schedule_retune(self, key, args, kwargs) -> None:
        """Background re-tune of an invalidated key.  Single-process
        only — the distributed winner agreement is a collective and
        must not run off the main control flow — and never under
        ``TDT_OBSERVABILITY=0`` (the caller already gates on it)."""
        from triton_distributed_tpu.observability import feedback
        if jax.process_count() > 1:
            feedback.record_decision(feedback.DecisionEvent(
                consumer="autotune.retune", op=self._fn_id(),
                choice="skipped", inputs={"key": str(key)},
                fallback="multiprocess"))
            return
        if key in self._retunes_inflight:
            return
        self._retunes_inflight.add(key)
        if self.retune_inline:
            self._retune(key, args, kwargs)
            return
        import threading
        threading.Thread(target=self._retune,
                         args=(key, args, kwargs),
                         name="tdt-autotune-retune",
                         daemon=True).start()

    def _retune(self, key, args, kwargs) -> None:
        from triton_distributed_tpu.observability import feedback
        try:
            entry = self._tune_now(key, args, kwargs)
            self.cache[key] = entry
            self._stale_handled.discard(key)
            feedback.record_decision(feedback.DecisionEvent(
                consumer="autotune.retune", op=self._fn_id(),
                choice=repr(entry.config),
                candidates=[{"name": repr(c),
                             "score_us": round(t * 1e6, 3)}
                            for t, c in entry.ranking[:6]],
                inputs={"trigger": "staleness", "key": str(key)}))
        except Exception as e:
            # A failed background re-tune leaves the second-best
            # fallback in place — never crash the serving thread.
            self._log(f"{key}: background re-tune failed: {e}")
            feedback.record_decision(feedback.DecisionEvent(
                consumer="autotune.retune", op=self._fn_id(),
                choice="failed", inputs={"key": str(key),
                                         "error": str(e)},
                fallback=type(e).__name__))
        finally:
            self._retunes_inflight.discard(key)


# ---------------------------------------------------------------------------
# Serving-loop runtime observation (ROADMAP item 4 follow-up)
# ---------------------------------------------------------------------------

#: Tuners armed to receive the serving decode loop's per-step host
#: latency: ``(weakref(tuner), cache key)`` pairs.  The scheduler
#: (`serving.scheduler._decode_step`) calls :func:`observe_serving_step`
#: once per measured step, so tuned-kernel anomaly baselines warm from
#: production traffic — previously only the bench drivers fed
#: `observe_runtime`, and a winner could go stale in a server that
#: never runs benches.
_SERVING_OBSERVERS: list = []

#: Baseline-key scope for the serving feed: whole-step host latency
#: is a different quantity than the bench drivers' tuned-op-only
#: latency and must never share a rolling baseline with it.
SERVING_SCOPE = "serving"


def arm_serving_observation(tuner: "ContextualAutotuner",
                            key) -> None:
    """Register ``tuner``'s cached entry for ``key`` (its call key —
    ``tuner.key_fn(*serving_args)``) to be fed by every serving decode
    step.  Weakly referenced: a dropped tuner silently unarms.
    Idempotent per (tuner, key): an op rebuilt after a re-tune heal or
    engine restart re-arms without double-feeding every step."""
    import weakref
    for ref, k in _SERVING_OBSERVERS:
        if ref() is tuner and k == key:
            return
    _SERVING_OBSERVERS.append((weakref.ref(tuner), key))


def clear_serving_observers() -> None:
    """Test hook: drop every armed (tuner, key) pair."""
    _SERVING_OBSERVERS.clear()


def observe_serving_step(us: float) -> None:
    """Feed one serving decode step's host latency (µs) to every
    armed tuner's winner baseline (`observe_runtime`).  The step time
    CONTAINS the tuned op — as a rolling baseline compared against
    itself that is exactly the sustained-drift signal the closed
    loop's invalidation consumes.  No-op (one empty-list check) when
    nothing is armed."""
    if not _SERVING_OBSERVERS:
        return
    dead = []
    for pair in list(_SERVING_OBSERVERS):
        ref, key = pair
        tuner = ref()
        if tuner is None:
            dead.append(pair)
            continue
        tuner.observe_runtime(key, float(us), scope=SERVING_SCOPE)
    for pair in dead:
        try:
            _SERVING_OBSERVERS.remove(pair)
        except ValueError:
            pass


DEFAULT_CACHE = ".autotune_cache.json"


def tune(fn, configs: Sequence[Any], args: tuple, *, chain=None,
         iters: int = 8, cache_path: str = DEFAULT_CACHE,
         scan_inner: int = 16):
    """Tune ``fn(*args, config=...)`` over ``configs`` on the current
    device, persisting the winner to the shared disk cache.  Returns
    ``(best_config, disk_hit)`` — benches report ``disk_hit`` so
    committed numbers are traceably machine-tuned (VERDICT r4 missing
    #1: the tuner machinery existed but flash/decode/grouped configs
    were hand-picked prose).

    ``fn`` must be a module-level function (its qualified name is part
    of the cache key), so the same entry serves both the bench that
    tuned it and the AOT bundle builder that ships it
    (:func:`disk_winner`)."""
    tuner = ContextualAutotuner(fn, configs, iters=iters, chain=chain,
                                cache_path=cache_path, jit_configs=True)
    # Sub-100 µs ops need a LONG in-scan chain per dispatch or the
    # drifting dispatch floor out-votes the kernel (observed: S=1024
    # flash picks flipping between runs at scan_inner=16).
    tuner.scan_inner = scan_inner
    key = tuner.key_fn(*args)
    disk_hit = tuner._disk_lookup(key) is not None
    tuner(*args)
    entry = tuner.cache[key]
    logger.info("autotune %s: %s, best=%s",
                tuner._device_key(),
                "disk cache hit" if disk_hit else "tuned fresh",
                entry.config)
    return entry.config, disk_hit


def disk_winner(fn, configs: Sequence[Any], args: tuple, *,
                cache_path: str = DEFAULT_CACHE):
    """Return the PERSISTED winner for ``(fn, args)`` or None — no
    timing.  AOT bundle builders use this to compile the machine-tuned
    config for each declared shape (reference:
    `scripts/aot_kernels.txt` + `tools/compile_aot.py:61` spaces);
    ``args`` may be `jax.ShapeDtypeStruct`s."""
    tuner = ContextualAutotuner(fn, configs, cache_path=cache_path)
    entry = tuner._disk_lookup(tuner.key_fn(*args))
    return entry.config if entry is not None else None


def contextual_autotune(configs: Sequence[Any],
                        key_fn: Optional[Callable] = None,
                        iters: int = 5, warmup: int = 2):
    """Decorator form (reference `contextual_autotune(is_dist=...)`):

        @contextual_autotune(configs=[MatmulConfig(...), ...])
        def my_op(a, b, *, config): ...
    """
    def deco(fn):
        tuner = ContextualAutotuner(fn, configs, key_fn, iters, warmup)
        functools.update_wrapper(tuner, fn, updated=[])
        return tuner
    return deco
