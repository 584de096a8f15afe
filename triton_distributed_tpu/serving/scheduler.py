"""Continuous-batching scheduler: Orca-style iteration-level loop.

Every `step()` is one scheduler iteration:

1. **admit** — while the FIFO head has arrived, a slot is free and the
   KV budget (bytes for ``kv_layout="slots"``, actual PAGES for
   ``"paged"``) allows, run a bucketed single-row prefill — or, on a
   radix prefix-cache hit with a prefix-aware model, a suffix-only
   prefill — and insert it into the running decode batch (requests
   join mid-flight; nobody waits for the batch to drain);
2. **decode** — ONE jitted masked step for all slots
   (`engine_batched.make_masked_step_fn`); free/finished slots emit
   the pad id and don't advance offsets or RNG keys.  The step is
   PIPELINED: iteration N dispatches step t+1 — its input tokens are
   step t's output, still on the device, with the first tokens of
   rows admitted in this iteration merged in by one small jitted
   program — BEFORE it reads step t's tokens, so the device runs
   while the host commits, returns to its caller, admits and
   enqueues.  Two paths yield something other than one token a row a
   dispatch.  BLOCK GENERATION (a model with ``block_length``,
   `models.sdar_moe`) is pipelined like the plain step: a dispatch
   feeds every row's block in flight for one denoise pass (some
   masked positions revealed) and, in front of it, the block the row
   finished in the pass before — that block's COMMIT (its K/V made
   final, the write cursor moved on by the block) rides on the next
   block's first denoise pass, so a block costs as many passes as it
   has denoise steps and no pass is a commit alone.  Both blocks'
   tokens and revealed flags live on the device between passes
   (`make_block_pass_fn`), and the reveal schedule is static, so the
   host knows what each row's next pass carries, its pages and its
   last pass without reading a token; 1..block tokens a row are
   delivered a pass, in position order.  With ``spec_k`` set the
   scheduler stays SERIAL (a drafter needs the committed tokens on
   the host, and a verify round's yield is not known before it is
   read) and the dispatch is a speculative draft–verify round instead
   (`make_spec_verify_fn` + `serving.speculative` drafters):
   K proposed tokens scored in one scanned program, the accepted
   prefix + bonus token committed per row, the rejected tail's KV
   cursor / pages / key chain rolled back — token-for-token
   identical output, ``1 + E[accept]`` tokens per dispatch.  Paged
   mode first maps pages for the positions this dispatch writes
   (`PagedKV.ensure`), preempting the newest request — resumed later,
   bit-exactly — if the pool is dry even after LRU-evicting
   unreferenced prefix pages;
3. **retire** — the tokens of the step dispatched one iteration
   EARLIER are synced to host (the one unavoidable sync), appended,
   streamed via ``on_token``, and rows that hit EOS /
   ``max_new_tokens`` / the KV horizon release their slot (and,
   paged, their private pages — prompt pages stay cached for future
   prefix hits).  The commit is TWO PASSES — deliver, then retire:
   every row of the read has its token(s) before the first row is
   retired, and the rows that ended are then retired in the order the
   loop met them, with the read's own ``now``, before the call's
   admissions and its next dispatch.  A release is milliseconds of
   host work (radix, pages, a stateful model's reset program); inside
   the loop every row behind the retiring one got its token — the
   moment a client stamps — that much later.  A row that ends by
   length or horizon is known to end before its last step is read
   and is simply not in the next dispatch; a row that ends by EOS is
   seen one step late — the token of the step that ran for it is
   discarded and counted.

Backpressure is at `submit`: a bounded queue and static feasibility
checks reject with a typed reason instead of queueing unservable work.

Time comes from an injectable ``clock`` (+ optional ``clock_advance``
for virtual time), so tests and the cluster's virtual clock replay
deterministic arrival schedules.  Request-level observability rides
the PR-1/2 stack: TTFT / TBT / queue-wait histograms, queue-depth /
slot-occupancy / KV-budget gauges (all in the Prometheus export), one
detached `serving.request` span per request feeding the cross-rank
timeline, and a span around each phase of a step (`serving.step` >
`serving.admit` > `serving.admit.prefill`, `serving.admit.request`: an
admission's two halves; `serving.pages`, `serving.dispatch`,
`serving.sync`, `serving.moe` (a sparse model's expert counters),
`serving.diffusion` (a block pass's rows by phase and tokens),
`serving.commit` — with ``deliver_ms``, span start to the last
``on_token``'s return, and ``retire_ms``, the rest —
`serving.gauges`) that says where the host's time in a step went.

An admission joins the pipeline: the host never waits for a prefill.
It is made in two halves — the first (`_admit_front`) matches the
prefix and ENQUEUES the prefill, the second (`_admit_insert`)
dispatches the insert and keeps the books — and in a call that admits
while a step is in flight the host's one wait is for THAT step, whose
tokens are due: it is read once, early, and `_decode_step` then finds
nothing in flight, dispatches step t+1 behind prefill and insert and
reads nothing.  Where the read goes is decided by what the host has
measured (`_front_fits`): BETWEEN the halves when the first half's
host time — the slowest of the last few — fits into what is left of
the step in flight — the prefill then waits on the device behind it
and the chip never idles — else BEFORE the first half, so that the tokens of the step in
flight are not held up by it (where the host's dispatch costs more
than half a step, as over four chips, a first half in front of the
read would lengthen a second token gap of every running row).  The
device's queue is the same either way — step t, prefill, insert, step
t+1 — with every argument of the provenance the serial order gave it;
only the moment at which the host blocks differs.  What a prefill
took is read from the one sync there is: the first read after it
measures prefill + step, and that reading less the rolling step time
is the prefill's (`_prefill_reading`); it is kept out of the step
metrics.

An admission is a SEQUENCE of such enqueues, each followed by its
insert — a whole prefill being a sequence of one.  Where the model
offers a prefill of one chunk over the pages already in the pool
(``make_prefill_suffix_fn``) and names a chunk length
(``prefill_chunk``), a prompt with more than a chunk still to prefill
— after the prefix match — is carried out chunk by chunk: chunk k
attends the rows its predecessors put into the request's pages, and AT
MOST ONE ENQUEUE — a chunk, or a short prompt's whole prefill — stands
between two decode dispatches, so a running row's token gap holds a
step and a chunk where it held a step and the longest prefill.  A
model with recurrent layers is paced the same way (`_paced`), and where
it offers the chunk program its chunks cover the prompt FROM POSITION 0
whatever the prefix match found: a state has no snapshot, so the
matched pages are shared for storage alone, and chunk k+1 starts from
the state and the convolution's tail chunk k returned — carried on the
admission, in and out through the row cache's ``states`` / ``convs``,
with ``length`` the tokens of the chunk the state absorbs; the last
chunk's insert writes the state into the slot's row, as a whole
prefill's does.  (One that offers no chunk program has its whole
prefill as the piece.)  The
request's slot and pages are claimed at its first insert (at its
first chunk that reads the pool, if that comes first) and the slot
stays masked — its row of the page table NULL — until the last chunk
is in; only then does the radix tree learn the prompt.  One admission
is under way at a time, first come first; with nothing running there
is no gap to protect and its chunks are enqueued in one call.  Each
chunk follows the rules of the paragraph above (`_front_fits`, the
early read), is a prefill in the read's record, and is timed by the
step's own sync.  A model without the program and without a recurrent
state admits as ever, several prefills a call included.

Every token gap is put down to what made it.  Each read leaves its
record on its `serving.sync` span — the interval since the read before
it (the gap every committed row sees), the rows committed, the
prefills that stood in front, whether it was the early read of an
admitting call and whether the tokens had already landed when the host
came for them — and each enqueue of a prefill or a step asks, without
blocking, whether the program enqueued last has finished: if so the
chip is standing idle for this enqueue (`starved`).  Both are asked
only where spans are recorded.
Metric and span names: docs/serving.md, docs/observability.md.
"""

from __future__ import annotations

import collections
import dataclasses
import statistics
import time
from typing import Callable, Deque, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from triton_distributed_tpu.models.base import ServedModel
from triton_distributed_tpu.observability.tracing import (
    NULL_SPAN,
    get_tracer,
    install_gc_hook,
    span,
)
from triton_distributed_tpu.serving.engine_batched import (
    DEFAULT_PREFILL_BUCKETS,
    make_block_pass_fn,
    make_masked_step_fn,
    make_spec_verify_fn,
    pad_prompt,
    pick_bucket,
    request_key,
)
from triton_distributed_tpu.serving.request import (
    FinishReason,
    RejectReason,
    Request,
    RequestState,
)
from triton_distributed_tpu.serving.slots import SlotKV


@dataclasses.dataclass
class SchedulerConfig:
    num_slots: int = 8
    #: Bounded submit queue — `submit` rejects (QUEUE_FULL) beyond it.
    max_queue: int = 64
    #: Prefill length buckets (entries > max_seq are dropped); one
    #: compiled prefill per bucket actually used.
    prefill_buckets: Sequence[int] = DEFAULT_PREFILL_BUCKETS
    #: Decode-cache sequence capacity; None = model config's
    #: max_seq_len.
    max_seq: Optional[int] = None
    #: Cap on KV bytes live slots may pin (None = all slots).  In
    #: paged mode this sizes the PAGE POOL (budget // bytes_per_page
    #: usable pages) — admission then counts actual pages, not
    #: max-context estimates.
    kv_budget_bytes: Optional[int] = None
    #: KV layout: "slots" = one contiguous row of max_seq per request
    #: (`serving.slots.SlotKV`); "paged" = page-table-indexed pool
    #: with radix prefix sharing (`serving.pages.PagedKV`) — a request
    #: pins only the pages it has actually filled, so admitted
    #: concurrency on the same HBM budget is bounded by REAL usage.
    kv_layout: str = "slots"
    #: Tokens per KV page (paged mode).  For token-for-token equality
    #: with the slot engine keep max_seq a multiple of this.
    page_size: int = 16
    #: Usable pages in the pool (paged mode); None = derived from
    #: kv_budget_bytes, else slot-engine parity (num_slots pages to
    #: max_seq each).
    num_pages: Optional[int] = None
    #: Radix prefix cache: requests sharing a prompt prefix share
    #: refcounted pages; full prompt pages are cached after use and
    #: evicted LRU under pressure (paged mode).
    prefix_cache: bool = True
    #: Host-memory spill capacity in pages (paged mode; 0 disables).
    #: Under KV pressure, refcount-0 prefix pages park their content
    #: in a `serving.pages.SpillPool` instead of being destroyed, and
    #: restore bit-exactly on the next prefix hit — which keeps
    #: prefix-dependent admission (prompts longer than every prefill
    #: bucket, servable only via suffix prefill) alive through
    #: pressure instead of shedding it.
    spill_pages: int = 0
    #: Disk tier below the host spill (`serving.kvtier.DiskTier`):
    #: when BOTH are set (and ``spill_pages`` > 0 — host is the tier
    #: above disk), host-spill overflow demotes the coldest parked
    #: page to a CRC-verified segment file under this directory
    #: instead of dropping it.  A corrupt or lost segment degrades
    #: that prefix chain to recompute at the admission probe — never
    #: wrong bytes.  See docs/serving.md "Cache hierarchy".
    spill_disk_dir: Optional[str] = None
    spill_disk_pages: int = 0
    pad_id: int = 0
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    #: Speculative decoding: draft–verify ``spec_k`` proposed tokens
    #: per decode dispatch (`engine_batched.make_spec_verify_fn`).
    #: 0 = off.  With it on, each dispatch scores K proposals + the
    #: bonus position in one scanned program and commits the accepted
    #: prefix plus one token — on average ``1 + E[accept]`` tokens per
    #: target-model dispatch, with the rejected tail's KV cursor and
    #: key chain rolled back so output is TOKEN-FOR-TOKEN identical to
    #: the non-speculative engine at any temperature (the accept rule
    #: is exact-match verification — see docs/serving.md).  A
    #: speculating scheduler reads every dispatch before the next
    #: (drafting needs the committed tokens on the host): it is the
    #: one configuration that does not pipeline its steps.  Rows
    #: without a proposal this round (or near their KV horizon) fall
    #: back to the plain masked step, bit-identically.
    spec_k: int = 0
    #: Draft source when ``spec_k > 0``: ``"ngram"``/None for the
    #: model-free prompt-lookup drafter, a
    #: `serving.speculative.Drafter` instance (e.g.
    #: `DraftModelDrafter` wrapping a tiny model that shares the
    #: target's tokenizer — shareable across a cluster's replicas;
    #: state is keyed by request id), or a CALLABLE factory receiving
    #: the scheduler (how each replica gets its own
    #: `BatchedDraftModelDrafter` over its slot space).
    spec_drafter: Optional[object] = None
    #: Accept-rate floor: when the cumulative accept rate falls below
    #: this after ``spec_probe_tokens`` proposals, drafting is
    #: DISABLED for the scheduler's lifetime (every dispatch falls
    #: back to the plain masked step, bit-identically) and the
    #: throttle is recorded as a DecisionEvent — the runtime half of
    #: the doctor's accept-collapse note: a verify round burns K+1
    #: model steps to commit ~1 token when the draft source has
    #: stopped predicting the workload.  0 (default) never throttles.
    spec_min_accept: float = 0.0
    #: Proposals to observe before `spec_min_accept` may trigger.
    spec_probe_tokens: int = 64
    #: SLO-aware admission (closed loop, `observability.feedback`):
    #: a time-between-tokens target in milliseconds.  When set, the
    #: scheduler consults the rolling decode-step baseline before
    #: admitting: a queue head whose admission cannot meet the target
    #: (predicted step time already past it) is DEFERRED — left
    #: queued with a truthful, recorded reason (DecisionEvent +
    #: ``serving_slo_deferrals_total``) — until the predicted step
    #: time clears or the engine drains.  An EMPTY engine always
    #: admits (deferral must never starve the only request), and with
    #: the target unset (default) or no usable baseline the admission
    #: order is bit-identical to the static scheduler.
    slo_tbt_ms: Optional[float] = None


def prefill_baseline_key(bucket: int) -> str:
    """Anomaly-baseline key for one bucketed prefill.  Every measured
    admission prefill rolls into it (the same store the decode-step
    baseline lives in), and the cluster router's ship-vs-recompute
    cost model reads it back as the PREDICTED prefill cost — "what
    does prefilling this bucket cost here, now" vs "what does
    shipping the cached pages cost over the measured wire"."""
    from triton_distributed_tpu.observability.anomaly import event_key
    return event_key("serving.prefill", None, (int(bucket),), 1)


def _is_ready(arr) -> bool:
    """Has the device finished ``arr``?  Never blocks."""
    return arr.is_ready()


def _flight_label(behind: bool, read: bool) -> str:
    """Where an admission's prefill stands to the step that was in
    flight: enqueued behind it, after its read, or there was none."""
    return "behind" if behind else "read" if read else "none"


def _prefills_label(n: int) -> str:
    return str(n) if n < 2 else "2+"


def _observe_prefill(bucket: int, ms: float) -> None:
    from triton_distributed_tpu.observability.anomaly import (
        get_baseline_store)
    get_baseline_store().observe(prefill_baseline_key(bucket),
                                 ms * 1e3)


@dataclasses.dataclass
class _Flight:
    """One decode dispatch whose tokens the host has not read yet."""
    #: (B,) tokens the step returned — (B, K+1) targets of a verify
    #: round — on the device.
    toks: object
    #: slot -> request of the rows that ran in it.
    rows: Dict[int, Request]
    #: `step_timer` reading when its preparation began.
    t0: float
    #: A sparse model's expert counters (`_moe_counters`), or None.
    counted: object = None
    #: Verify round only: accept lengths (device), proposals (host).
    accept: object = None
    n_draft: object = None
    #: Block pass only (``toks`` is then the (B, 2, 2n) block state it
    #: returned): slot -> (first position of the row's block in
    #: flight, positions it reveals, whether the pass also carried the
    #: commit of the block before it — FOLDED —, whether it finished
    #: the block: its tokens then stand in the state's front half).
    denoised: Optional[Dict[int, tuple]] = None
    #: (bucket, request) of the prefills enqueued since the dispatch
    #: before this one: the device runs them first, so the read of
    #: this dispatch times THEM (`_prefill_reading`), not a step.
    prefills: Sequence[tuple] = ()


@dataclasses.dataclass
class _Admission:
    """One request on its way into a slot: the prefill enqueues it
    takes (``pieces``), each followed by its insert."""
    req: Request
    #: What is prefilled: the prompt, or a resumed stream's context.
    tokens: Sequence[int]
    key: object
    #: The matched radix path (paged layout).
    shared: list
    #: The lineage admission class: local / shipped / suffix / chunk.
    mode: str
    #: (first position, bucket) of each piece, in order.
    pieces: List[tuple]
    #: A shipment's row: nothing is enqueued for it.
    row: object = None
    #: Pieces whose insert is dispatched.
    done: int = 0
    #: Paged layout: the slot, once its pages are claimed.
    slot: Optional[int] = None
    #: A model with recurrent layers, in chunks: the row the last chunk
    #: enqueued returned — its ``states`` / ``convs`` are what the next
    #: chunk starts from.
    carry: object = None


class ContinuousBatchingScheduler:
    """model: a `models.base.ServedModel` — one of the six families, or
    `serving.toy.ToyModel`.  That class's docstring is the contract:
    what the scheduler calls (`make_prefill_fn`, `make_paged_decode_fn`,
    `make_prefill_suffix_fn`, `create_cache`, `create_paged_cache`; and
    `make_decode_fn` under ``kv_layout="slots"``) and what it reads
    (``block_length``, ``prefill_chunk``, ``window``, ``STATS``,
    ``latent_bytes_per_token``)."""

    def __init__(self, model, params,
                 config: Optional[SchedulerConfig] = None,
                 clock: Optional[Callable[[], float]] = None,
                 clock_advance: Optional[Callable[[float], None]] = None,
                 bus=None):
        if not isinstance(model, ServedModel):
            raise ValueError(
                f"{type(model).__name__} is not a `ServedModel`: it "
                f"lacks the paged engine contract (models/base.py)")
        self.model = model
        self.params = params
        self.config = cfg = config or SchedulerConfig()
        self.clock = clock or time.monotonic
        #: Feedback bus for SLO-aware admission (only consulted when
        #: ``cfg.slo_tbt_ms`` is set — which IS the opt-in; None then
        #: means the process-global bus).
        self._bus = bus
        #: Current deferral episode: {"request_id", "since",
        #: "predicted_ms"} while the queue head is SLO-deferred.
        self._slo_episode: Optional[dict] = None
        #: With a virtual clock, how the idle loop moves time forward
        #: to the next arrival; with the default wall clock we sleep.
        self._clock_advance = clock_advance
        #: The ONE wall-clock measurement on the decode hot path (the
        #: `serving_decode_step_ms` timing around `_decode_step`).
        #: Injectable so a deterministic replay
        #: (`observability.replay`) can pin measured step durations —
        #: everything else already rides the injected `clock`.
        self.step_timer: Callable[[], float] = time.perf_counter
        max_seq = cfg.max_seq or model.config.max_seq_len
        self.max_seq = int(max_seq)
        self.buckets = tuple(sorted(
            b for b in cfg.prefill_buckets if b <= self.max_seq))
        if not self.buckets:
            raise ValueError(
                f"no prefill bucket fits max_seq={self.max_seq}")
        self.paged = cfg.kv_layout == "paged"
        #: > 1: the model generates by blocks of this many positions;
        #: the step is a block pass (module docstring).
        self._block = int(model.block_length)
        if self._block > 1 and (not self.paged or cfg.spec_k):
            raise ValueError(
                "block generation runs over the paged layout, without "
                "speculation")
        if self.paged:
            from triton_distributed_tpu.serving.pages import PagedKV
            self.slots = PagedKV(
                model, cfg.num_slots, max_seq=self.max_seq,
                page_size=cfg.page_size, num_pages=cfg.num_pages,
                kv_budget_bytes=cfg.kv_budget_bytes,
                prefix_cache=cfg.prefix_cache,
                spill_pages=cfg.spill_pages,
                spill_disk_dir=cfg.spill_disk_dir,
                spill_disk_pages=cfg.spill_disk_pages)
            decode_fn = model.make_paged_decode_fn(
                page_size=cfg.page_size)
            # ``(params, ids, start, row_cache, (ks, vs), page_ids)
            # -> row_cache``: a prefill of positions ``start ...`` over
            # the rows the pool already holds at ``page_ids``.
            sfn = model.make_prefill_suffix_fn
            self._prefill_suffix = (jax.jit(sfn())
                                    if sfn is not None else None)
        elif cfg.kv_layout == "slots":
            self.slots = SlotKV(model.create_cache(cfg.num_slots,
                                                   max_seq=self.max_seq),
                                cfg.kv_budget_bytes)
            decode_fn = model.make_decode_fn()
            self._prefill_suffix = None
        else:
            raise ValueError(f"unknown kv_layout {cfg.kv_layout!r}")
        #: Tokens a chunk of a long prompt's prefill (module docstring);
        #: 0: the model does not offer it, nothing is ever chunked.
        self._chunk = (int(model.prefill_chunk)
                       if self._prefill_suffix is not None else 0)
        #: The admission whose next chunk is due, and whether a prefill
        #: was enqueued since the last decode dispatch (paced
        #: admissions — `_paced` — get one between two).
        self._underway: Optional[_Admission] = None
        self._spent = False
        #: The page ids of a first chunk that reads no row of the pool.
        self._no_pages = (np.zeros(self.slots.pages_per_seq, np.int32)
                          if self.paged else None)
        #: The model has recurrent layers (`models.kv_cache`): a state
        #: a slot beside the pages.  Its prefill is told each row's
        #: true length, and a resumed or prefix-sharing request
        #: recomputes its state through that prefill (no snapshot).
        self._stateful = bool(getattr(self.slots, "state_bytes_per_slot",
                                      0))
        #: At most ONE prefill enqueue between two decode dispatches
        #: while rows run: a model that chunks (a chunk is the piece),
        #: and a model with recurrent layers (a chunk where it offers
        #: the program, else its whole prefill) — its short answers
        #: hand slots on every few steps, and a token gap that held a
        #: step and two or three prefills holds one.
        self._paced = bool(self._chunk or self._stateful)
        #: The model has sliding-window layers (`serving.pages`: pages
        #: by layer kind): their pages behind the window are gone, so —
        #: as with a state — a resumed or prefix-sharing request
        #: prefills from position 0 (the matched pages are shared for
        #: the full layers' storage), and nothing rolls back.
        self._windowed = bool(getattr(self.slots, "window", 0))
        if self._windowed and cfg.spec_k:
            raise ValueError("speculation over sliding-window layers "
                             "is not built (no rollback of pages given "
                             "back)")
        #: Window pages given back / tokens a window-aware prefix hit
        #: or resume would have saved, as of the last `serving.window`.
        self._window_seen = [0, 0]
        self._window_recomputed = 0
        #: What the decode program leaves in the cache's `stats`, by
        #: name (`_moe_phase`).
        self._stats_names = model.STATS
        #: Host-side halves of the `serving.state` span, as of the
        #: last one: slots reset, tokens whose state was recomputed.
        self._state_seen = [0, 0]
        self._state_recomputed = 0
        self._prefill = jax.jit(model.make_prefill_fn())
        if self._block > 1:
            if cfg.temperature:
                raise ValueError(
                    "block generation is greedy: sampling with "
                    "temperature inside a block is not built")
            gen = model.config
            self._step = make_block_pass_fn(
                decode_fn, self._block, gen.mask_token_id, gen.remasking)
            #: Positions a denoise pass reveals, and whether they are
            #: always the leftmost masked ones.
            self._reveal = self._block // gen.denoising_steps
            self._sequential = gen.remasking == "sequential"
            #: The host's word for the two block-widths of a newly
            #: admitted row (`_fresh`): a dead front half; in the back
            #: half the prompt's tail revealed, the rest masked.
            self._blk_host = np.zeros(
                (cfg.num_slots, 2, 2 * self._block), np.int32)
            #: Revealed and not yet deliverable, as of the last read.
            self._held_back = 0
        else:
            self._step = make_masked_step_fn(
                decode_fn, cfg.temperature, cfg.top_k, cfg.top_p,
                cfg.pad_id)
        #: Host-known first tokens merged into the token vector the
        #: last dispatch left on the device: ONE jitted program, run
        #: in front of every dispatch of `_step`, full batch or not,
        #: pipeline empty or not — no `jnp` operation runs on the
        #: host path between the programs of a step.
        self._merge = jax.jit(
            lambda prev, host, fresh: jnp.where(fresh, host, prev))
        #: A sparse model's expert counters, copied out of the cache a
        #: dispatch returned (`_moe_counters`): a jitted program too.
        self._keep = jax.jit(jnp.copy)
        #: Speculative verify program + drafter (``spec_k > 0``).
        self._spec_fn = None
        self.drafter = None
        if cfg.spec_k:
            if cfg.spec_k < 1:
                raise ValueError(f"spec_k must be >= 1, got "
                                 f"{cfg.spec_k}")
            from triton_distributed_tpu.serving.speculative import (
                make_drafter)
            self.drafter = make_drafter(cfg.spec_drafter, self)
            self._spec_fn = make_spec_verify_fn(
                decode_fn, cfg.temperature, cfg.top_k, cfg.top_p,
                cfg.pad_id, k=cfg.spec_k)
            #: Cumulative draft/verify outcome — feeds the
            #: ``serving_spec_accept_rate`` gauge (rides heartbeats;
            #: the doctor calls out a collapse below 0.3).
            self._spec_proposed = 0
            self._spec_accepted = 0
            self._spec_throttled = False
        from triton_distributed_tpu.observability.anomaly import (
            event_key)
        #: Baseline key every measured decode step rolls into — and
        #: the SLO admission check reads back as the predicted step
        #: time (the empirical "what does a step cost HERE, NOW").
        self._step_key = event_key("serving.decode_step", None,
                                   (cfg.num_slots,), 1)
        #: Actor label on this engine's lineage hops (the cluster's
        #: `Replica` renames it to "replica-<i>" so a hop says WHERE).
        self.name = "engine"
        #: Input token of each slot as far as the HOST knows it (the
        #: last prompt token at admission, the last committed token
        #: after) and, in `_fresh`, the rows where the device does not
        #: know it yet: set at admission, cleared by the dispatch that
        #: ships them.
        self._tokens = np.full(cfg.num_slots, cfg.pad_id, np.int32)
        self._fresh = np.zeros(cfg.num_slots, bool)
        #: What the last dispatch returned, still on the device: the
        #: next dispatch's input tokens.  Kept across idle periods, so
        #: that from a process's second dispatch on this argument is
        #: always "a token vector a step returned"; the first gets
        #: zeros, placed where the cache's own per-row vector lies —
        #: with the model's parameters — so that the merged tokens
        #: reach `_step` placed alike from the first dispatch on and
        #: the decode program is not compiled once more for it.
        # (A block pass returns, and takes, the (B, 2, 2n) block state.)
        self._prev = jax.device_put(
            self._blk_host.copy() if self._block > 1
            else np.zeros(cfg.num_slots, np.int32),
            self.slots.cache.offset.sharding)
        #: The decode step dispatched and not yet read.
        self._flight: Optional[_Flight] = None
        #: `step_timer` reading when the last step's tokens landed.
        self._read_at = float("-inf")
        #: The last plain dispatches' times, each from the landing of
        #: the one before it to its own (seconds; reads that carried a
        #: prefill are kept out) — their median is the rolling step
        #: time — and the host time of the last admissions' first
        #: halves: what `_front_fits` and `_prefill_reading` reckon
        #: with.  (Medians and a maximum of few: one pause of the
        #: collector inside a step must not sit in a mean for seconds.)
        self._step_times: Deque[float] = collections.deque(maxlen=32)
        self._front_times: Deque[float] = collections.deque(maxlen=16)
        #: (bucket, request) of the prefills enqueued since the last
        #: dispatch, and `step_timer` at the first one's enqueue: the
        #: next dispatch takes them along (`_Flight.prefills`).
        self._prefills: List[tuple] = []
        self._prefill_t0 = 0.0
        #: The call began with nothing running and nothing in flight:
        #: its first enqueue is `starved` for want of arrivals, not of
        #: the host (`_starved` says ``idle=1`` and clears this).
        self._idle = True
        #: Per-bucket reusable prefill input caches (see _admit).
        self._row_caches: Dict[int, object] = {}
        self._queue: Deque[Request] = collections.deque()
        self._by_slot: Dict[int, Request] = {}
        #: Open `serving.request` spans by slot.
        self._spans: Dict[int, object] = {}
        #: Scheduler iterations so far (`serving.step`'s ``step``).
        self._steps = 0
        self._stopped = False
        self.finished: List[Request] = []
        install_gc_hook()
        self._update_gauges()

    @property
    def tracer(self):
        """The tracer this scheduler's spans land in."""
        return get_tracer()

    def close(self) -> None:
        """End the `serving.request` spans of requests still in their
        slots: a scheduler dropped mid-run must not leave them open in
        the process's tracer (idempotent; `stop()` retires them with
        a reason instead)."""
        while self._spans:
            _, sp = self._spans.popitem()
            sp.__exit__(None, None, None)

    def __del__(self):
        try:
            self.close()
        except Exception:       # interpreter shutdown
            pass

    # -- submission / backpressure --------------------------------------

    def structural_reject(self, req: Request,
                          full_prefill: bool = False
                          ) -> Optional[RejectReason]:
        """The admission checks that depend only on request geometry
        vs this engine's static configuration — never on queue state.
        A hit is final: the request can never run here (and, replicas
        being homogeneous, nowhere else in a cluster — which is why
        the cluster's prefill-worker dispatch pre-validates with this
        instead of finding out via an assert inside the worker).

        One check is geometry-vs-CACHE, not geometry-vs-config: a
        prompt longer than every prefill bucket is still servable
        when a cached radix prefix leaves a bucketable suffix
        (prefix-dependent admission — the storage AND compute halves
        of prefix sharing).  ``full_prefill=True`` disables that
        allowance (the cluster's prefill-worker path computes the
        whole prompt on a worker, which needs a full-prompt bucket).
        If the prefix is evicted between this check and admission,
        the admission path sheds the request with the truthful
        ``KV_PRESSURE`` reason (`SchedulerConfig.spill_pages` keeps
        the prefix restorable instead)."""
        if pick_bucket(req.prompt_len, self.buckets) is None:
            if (full_prefill or not self.paged
                    or self._prefill_suffix is None):
                return RejectReason.PROMPT_TOO_LONG
            # some plan of pieces has to cover it (`_plan`): chunks on
            # a model that chunks, else a cached prefix and the rest
            # through a bucket
            shared = self.slots.match_prefix(req.prompt)
            c = len(shared) * self.config.page_size
            if not self._pieces_cover(req.prompt_len, c):
                return RejectReason.PROMPT_TOO_LONG
        if (req.prompt_len + req.max_new_tokens > self.max_seq + 1
                and self._block <= 1):
            # offset after the last generated token may reach max_seq:
            # position max_seq-1 is the last writable KV row, and the
            # final token needs no KV write of its own.  (A block's
            # horizon is the end of its last block: `feasible` below.)
            return RejectReason.EXCEEDS_KV_CAPACITY
        if self.paged and not self.slots.feasible(
                req.prompt_len, req.max_new_tokens):
            # page arithmetic: the request's horizon
            # (prompt + max_new - 1 positions) costs more pages than
            # the pool holds — it can never run, even alone.
            return RejectReason.EXCEEDS_KV_CAPACITY
        if (not self.paged
                and self.slots.kv_budget_bytes < self.slots.bytes_per_slot):
            # a budget below one slot can never admit anything —
            # queueing it would make drain() spin forever.
            return RejectReason.EXCEEDS_KV_CAPACITY
        return None

    def _prefill_from(self, cached: int) -> int:
        """Where the prefill of a prompt begins whose first ``cached``
        tokens' pages matched: there — or at 0 on a model with a
        recurrent state (no snapshot) or sliding-window layers (their
        pages are nobody's to share)."""
        return 0 if self._stateful or self._windowed else cached

    def _pieces_cover(self, s: int, cached: int) -> bool:
        """Does `_plan` find a plan of pieces for a prompt of ``s``
        tokens past every bucket, ``cached`` of them matched?"""
        lo = self._prefill_from(cached)
        return bool((self._chunk and s - lo > self._chunk)
                    or (lo > 0 and pick_bucket(s - lo, self.buckets)))

    def submit(self, req: Request) -> bool:
        """Enqueue; False = rejected with ``req.reject_reason`` set."""
        now = self.clock()
        if req.tenant != "default":
            # A real tenant label is the opt-in for per-tenant cost
            # accounting (golden discipline: default-only runs never
            # arm it, so they charge and emit nothing).
            from triton_distributed_tpu.observability.costs import (
                maybe_arm_for_tenant)
            maybe_arm_for_tenant(req.tenant)
        req.t_arrival = (req.arrival_time if req.arrival_time is not None
                         else now)
        reason = None
        if self._stopped:
            reason = RejectReason.STOPPED
        elif len(self._queue) >= self.config.max_queue:
            reason = RejectReason.QUEUE_FULL
        else:
            reason = self.structural_reject(req)
        reg = self._registry()
        if reason is not None:
            req.state = RequestState.REJECTED
            req.reject_reason = reason
            if reg:
                reg.counter("serving_requests_rejected_total",
                            reason=reason.value).inc()
                if reason not in (RejectReason.QUEUE_FULL,
                                  RejectReason.STOPPED):
                    # Structural rejects are terminal lineage hops.
                    # Transient refusals (backpressure, a draining
                    # engine) are NOT recorded: the cluster retries
                    # them every event-loop tick, and lineage keeps
                    # the commit-on-accept discipline decisions do —
                    # a refused attempt that never landed is not a
                    # hop the request crossed.
                    self._hop(req, "reject", now, reason=reason.value)
            return False
        self._queue.append(req)
        if reg:
            reg.counter("serving_requests_submitted_total").inc()
            reg.gauge("serving_queue_depth").set(len(self._queue))
            # ts clamps forward to the arrival: a pre-submitted future
            # arrival "enters the queue" when it becomes eligible, and
            # a cluster attempt delivered mid-stream (shipped KV, a
            # failover resume) enqueues at delivery time, keeping each
            # request's lineage timestamps monotone.
            self._hop(req, "enqueue", max(req.t_arrival, now),
                      prompt_len=req.prompt_len,
                      queued=len(self._queue))
        return True

    # -- the iteration loop ---------------------------------------------

    def has_work(self) -> bool:
        """True while anything is queued, running, or dispatched and
        unread: the last tokens of the last request are delivered by
        the `step()` after the one that dispatched them."""
        return (bool(self._queue) or bool(self._by_slot)
                or self._flight is not None
                or self._underway is not None)

    def step(self) -> dict:
        """One scheduler iteration.  Returns counts for introspection:
        ``{"admitted", "active", "retired"}``."""
        self._steps += 1
        with span("serving.step", step=self._steps) as sp:
            out = self._step_phases()
            if sp is not NULL_SPAN:
                sp.attrs.update(out)
        return out

    def _step_phases(self) -> dict:
        now = self.clock()
        self._idle = (not self._by_slot and self._flight is None
                      and self._underway is None)
        admitted, retired = self._admit(now)
        # (rows that an admission's read retired were in the batch)
        active_n = len(self._by_slot) + retired
        if self._by_slot or self._flight is not None:
            retired += self._decode_step()
        elif self._queue:
            # Nothing running, head not arrived yet: move time.
            dt = self._queue[0].t_arrival - now
            if dt > 0:
                if self._clock_advance is not None:
                    self._clock_advance(dt)
                else:
                    time.sleep(min(dt, 0.001))
        if admitted or retired:
            with span("serving.gauges"):
                self._update_gauges()
        return {"admitted": admitted, "active": active_n,
                "retired": retired}

    def drain(self) -> List[Request]:
        """Run until queue and slots are empty; returns the finished
        requests in completion order."""
        while self.has_work():
            self.step()
        return self.finished

    def run(self, requests: Sequence[Request]) -> List[Request]:
        """Submit everything (arrivals still gate admission), then
        drain."""
        for r in requests:
            self.submit(r)
        return self.drain()

    def stop(self) -> None:
        """Abort: live requests finish with reason STOPPED, queued ones
        are rejected, later submits are rejected.  A step in flight is
        dropped unread — an abort delivers no token: what each request
        streamed so far is what a scheduler stopped one step earlier
        would have streamed (the cluster's failover calls this AFTER
        it took the streams over; a token delivered here could end
        one it is about to resume)."""
        self._stopped = True
        self._drop_flight()
        self._give_up_underway()
        for slot in list(self._by_slot):
            self._retire(slot, self.clock(), FinishReason.STOPPED)
        reg = self._registry()
        while self._queue:
            req = self._queue.popleft()
            if req.generated:
                # A preempted-and-requeued request already streamed
                # tokens: it finishes (partial output delivered), it
                # isn't rejected.
                req.state = RequestState.FINISHED
                req.finish_reason = FinishReason.STOPPED
                req.t_finish = self.clock()
                if reg:
                    reg.counter("serving_requests_completed_total",
                                reason=FinishReason.STOPPED.value).inc()
                self.finished.append(req)
                continue
            req.state = RequestState.REJECTED
            req.reject_reason = RejectReason.STOPPED
            # Same accounting as the submit() reject path, so
            # submitted == completed + rejected + in-flight holds
            # across a shutdown.
            if reg:
                reg.counter("serving_requests_rejected_total",
                            reason=RejectReason.STOPPED.value).inc()
        self._update_gauges()

    def restart(self) -> None:
        """Re-open a stopped scheduler.  The cluster uses this on
        re-admission after a false-positive drain (the replica never
        died — its heartbeat flapped): `stop()` already cleared the
        queue and slots deterministically; restarting just accepts
        new submissions again."""
        assert not self._by_slot and not self._queue, (
            "restart() before stop() drained the engine")
        self._stopped = False

    # -- internals ------------------------------------------------------

    def _registry(self):
        from triton_distributed_tpu.observability import (
            get_registry, observability_enabled)
        return get_registry() if observability_enabled() else None

    def _lineage_key(self, req: Request):
        """The id this request's lineage hops record under: the
        cluster-assigned record id when one exists (so one user
        request's lineage spans replica attempts), else a namespaced
        engine-local key (record ids and request ids come from
        different counters and would collide as raw ints)."""
        if req.lineage_id is not None:
            return req.lineage_id
        return f"eng-{req.request_id}"

    def _hop(self, req: Request, hop: str, ts: float,
             **detail) -> None:
        """Record one lineage hop for ``req``.  Call sites sit behind
        the existing ``if reg:`` registry guard, so the disabled hot
        path never reaches here (bit-identical, zero allocations)."""
        from triton_distributed_tpu.observability.lineage import (
            record_hop)
        record_hop(self._lineage_key(req), hop, ts, self.name,
                   **detail)

    # -- cost attribution (observability.costs; every hook no-ops
    # -- until a tenant/SLO policy arms accounting) ----------------------

    def _charge_device(self, phase: str, us: float, reqs) -> None:
        """Charge one measured device window, split exactly across
        the requests that shared it (the cost analogue of the lineage
        interval-charging rule)."""
        from triton_distributed_tpu.observability import costs
        if costs.cost_accounting_enabled():
            costs.charge_device(
                phase, us,
                [(self._lineage_key(r), r.tenant) for r in reqs])

    def _charge_tokens(self, kind: str, req: Request, n: int) -> None:
        from triton_distributed_tpu.observability import costs
        if costs.cost_accounting_enabled():
            costs.charge_tokens(kind, self._lineage_key(req),
                                req.tenant, n)

    def _charge_kv_residency(self, reqs, now: float) -> None:
        """Integrate KV page-seconds for every active request: pages
        currently pinned × time since its previous charge.  Paged
        mode bills the pages the request has actually filled; slot
        mode bills the whole pinned row (that IS its footprint)."""
        from triton_distributed_tpu.observability import costs
        if not costs.cost_accounting_enabled():
            return
        page = max(self.config.page_size, 1)
        row_pages = -(-self.max_seq // page)
        for r in reqs:
            if self.paged:
                tokens = min(r.prompt_len + len(r.generated),
                             self.max_seq)
                pages = -(-tokens // page)
            else:
                pages = row_pages
            costs.charge_kv_occupancy(self._lineage_key(r), r.tenant,
                                      pages, now)

    def _can_admit_head(self) -> bool:
        if not self.paged:
            return self.slots.can_admit()
        head = self._queue[0]
        return self.slots.can_admit(head.resume_tokens or head.prompt)

    def _request_key(self, req: Request):
        """The slot PRNG key a request starts (or RESUMES) from: its
        snapshot/recomputed resume key when one is carried (preempt
        re-admission, cluster failover — the stream continues the
        exact sample chain), else the pure function of its seed."""
        if req.resume_key is not None:
            return jnp.asarray(req.resume_key, jnp.uint32)
        return request_key(req.seed)

    def _shipped_row(self, req: Request, reg):
        """Admission of a prefill-worker shipment
        (`serving.cluster.transport.KVShipment`): the shipped
        single-row cache replaces the local prefill — the identical
        artifact, inserted by the identical program, with zero prompt
        FLOPs spent on this replica."""
        ship = req.shipped_kv
        req.shipped_kv = None
        assert ship.prompt_len == req.prompt_len, (
            ship.prompt_len, req.prompt_len)
        if reg:
            reg.counter("serving_shipped_inserts_total").inc()
        return ship.to_row_cache(), ship.prompt_len, ship.bucket

    def _row_cache(self, bucket: int):
        # One reusable input row cache per bucket: prefill is
        # functional (input untouched, output fully overwritten up
        # to the bucket), so admissions don't re-zero HBM — the
        # same point as Engine.serve's caller-provided cache.
        row_in = self._row_caches.get(bucket)
        if row_in is None:
            row_in = self.model.create_cache(1, max_seq=bucket)
            self._row_caches[bucket] = row_in
        return row_in

    def _slo_gate(self, now: float) -> bool:
        """SLO-aware admission (closed loop): True = the queue head
        may be admitted now.  With no ``slo_tbt_ms`` target this is
        unconditionally True — the static scheduler, bit-identically.
        Runs only AFTER capacity said yes (``_can_admit_head``): a
        recorded choice="admit" must mean the head is actually
        admitted this call, and a capacity wait must not close an
        open SLO-deferral episode (which would double-count
        ``serving_slo_deferrals_total`` for one continuous wait).

        The predicted step time is the rolling decode-step baseline
        (every measured step feeds it); if it already exceeds the TBT
        target, admitting more work cannot meet the SLO, so the head
        is deferred — truthfully recorded ONCE per episode as a
        DecisionEvent — until the prediction clears or the engine
        drains.  An empty engine always admits: deferral must never
        starve the only runnable request (and an idle engine is how
        the baseline re-learns that steps got cheap again)."""
        slo = self.config.slo_tbt_ms
        if slo is None:
            return True
        head = self._queue[0]
        if not self._by_slot:
            return self._slo_admit(head, now, reason="engine_empty")
        from triton_distributed_tpu.observability import feedback
        bus = self._bus if self._bus is not None else (
            feedback.get_signal_bus())
        sig = bus.read()
        if not sig.fresh(bus.clock(), bus.staleness_s):
            return self._slo_admit(head, now, reason="signals_stale")
        pred_us = sig.predicted_us(self._step_key)
        if pred_us is None:
            return self._slo_admit(head, now, reason="no_baseline")
        pred_ms = pred_us / 1e3
        if pred_ms <= slo:
            return self._slo_admit(head, now, predicted_ms=pred_ms)
        if (self._slo_episode is None
                or self._slo_episode["request_id"] != head.request_id):
            # Episode start: record the deferral, its inputs, and the
            # truthful reason — this is the "why wasn't I admitted"
            # answer the doctor replays.
            self._slo_episode = {"request_id": head.request_id,
                                 "since": now,
                                 "predicted_ms": pred_ms}
            reg = self._registry()
            if reg:
                reg.counter("serving_slo_deferrals_total").inc()
            feedback.record_decision(feedback.DecisionEvent(
                consumer="serving.admission",
                op=f"request:{head.request_id}", choice="defer",
                candidates=[{"name": "admit",
                             "score_us": round(pred_us, 1)},
                            {"name": "defer"}],
                inputs=dict(sig.to_inputs(),
                            predicted_step_ms=round(pred_ms, 3),
                            slo_tbt_ms=float(slo),
                            active=len(self._by_slot),
                            queued=len(self._queue))))
        return False

    def _slo_admit(self, head, now: float, predicted_ms=None,
                   reason=None) -> bool:
        """Close a deferral episode (if one was open for this head)
        with a recorded admit decision; always returns True."""
        ep = self._slo_episode
        if ep is not None and ep["request_id"] == head.request_id:
            self._slo_episode = None
            from triton_distributed_tpu.observability import feedback
            inputs = {"deferred_s": round(now - ep["since"], 6),
                      "slo_tbt_ms": float(self.config.slo_tbt_ms)}
            if predicted_ms is not None:
                inputs["predicted_step_ms"] = round(predicted_ms, 3)
            if reason is not None:
                inputs["cleared_by"] = reason
            feedback.record_decision(feedback.DecisionEvent(
                consumer="serving.admission",
                op=f"request:{head.request_id}", choice="admit",
                inputs=inputs))
        return True

    def _head_ready(self, now: float) -> bool:
        """The queue's head has arrived, a slot and the pool take it
        and the SLO gate lets it in: it is admitted in this call."""
        return bool(self._queue and self._queue[0].t_arrival <= now
                    and self._can_admit_head() and self._slo_gate(now))

    def _piece_due(self, now: float) -> bool:
        """A prefill may be enqueued now: an admission is under way, or
        the queue's head can begin one — and, where admissions are
        paced (`_paced`), none was enqueued since the last decode
        dispatch while anything runs that would wait for a second
        one."""
        if (self._paced and self._spent
                and (self._by_slot or self._flight is not None)):
            return False
        return self._underway is not None or self._head_ready(now)

    def _admit(self, now: float) -> tuple:
        """Admit what the queue's head, the slots and the pool allow,
        and carry the admission under way a chunk further.
        Returns (requests admitted, rows retired): a call that enqueues
        a prefill while a step is in flight READS that step here, once
        — between the first admission's halves or ahead of them
        (`_front_fits`) — so its tokens never wait for an insert's
        dispatch, and the host waits for no prefill at all.  A slot
        that read frees may be filled in the same call; every admission
        after it has no flight to read and enqueues straight through.
        """
        # Nothing to try: no chunk due and no arrived head, or no slot
        # to put it in (both layouts refuse without a free slot) — and
        # no span.
        if self._stopped or (self._underway is None and (
                not self._queue or self._queue[0].t_arrival > now
                or not self.slots.free_slots)):
            return 0, 0
        n = retired = 0
        read = False      # this call read the step in flight
        began = None      # the piece whose insert waits for that read
        due = self._piece_due(now)
        while due or began is not None:
            if self._flight is not None and (began is not None
                                             or not self._front_fits()):
                retired += self._read(self._take_flight(), early=True)
                read = True
            with span("serving.admit", queued=len(self._queue)):
                if began is not None:
                    n += self._admit_insert(began, now, read)
                    began, read = None, False
                    due = self._piece_due(now)
                while due:
                    front = self._admit_front(now, read)
                    if front is not None and self._flight is not None:
                        began = front       # its insert: after the read
                        break
                    if front is not None:   # (None: retired at admission)
                        n += self._admit_insert(front, now, read)
                        read = False
                    due = self._piece_due(now)
        return n, retired

    def _front_fits(self) -> bool:
        """With a step in flight and an admission due: True when the
        admission's first half may run in front of the read of that
        step — its tokens are still not due when the prefill is
        enqueued, and the device goes from the step into the prefill
        without waiting for the host.  False: read first; the chip then
        idles for the first half, and no token waits for it.  Judged by
        what was measured: the step lands a rolling step time after
        the one before it did; the first half takes what the slowest
        of the last few took (they scatter with the prompt's length
        and bucket).  A step behind a prefill has all of that
        prefill's time left.  Nothing measured yet: read first."""
        flight = self._flight
        if flight.prefills:
            return True
        if not (self._step_times and self._front_times):
            return False
        lands = max(flight.t0, self._read_at) + self._step_s()
        return self.step_timer() + max(self._front_times) <= lands

    def _step_s(self) -> float:
        """The rolling time of a plain dispatch, in seconds."""
        return statistics.median(self._step_times)

    def _admit_front(self, now: float, read: bool):
        """First half of a piece of an admission — the next chunk of
        the one under way, else the first (or only) piece of the
        queue's head: prefix match and plan, then the prefill's
        ENQUEUE — or a shipment's row.  Nothing here waits for the
        device.  ``read``: this call has read the step in flight
        already.  Returns what `_admit_insert` takes, or None when the
        request was retired at admission."""
        reg = self._registry()
        adm = self._underway
        req = adm.req if adm is not None else self._queue.popleft()
        behind = self._flight is not None
        t0 = self.step_timer()
        with span("serving.admit.prefill", request_id=req.request_id,
                  flight=_flight_label(behind, read),
                  queue_wait_ms=max(now - req.t_arrival, 0.0) * 1e3
                  ) as sp:
            if adm is None:
                adm = self._plan(req, now, reg)
                if adm is None:
                    return None           # retired at admission
            row = self._enqueue_piece(adm, sp)
            bucket = adm.pieces[adm.done][1]
            if sp is not NULL_SPAN:
                at = adm.pieces[adm.done][0]
                sp.attrs.update(
                    bucket=bucket, start=at,
                    tokens=min(bucket, len(adm.tokens) - at))
                if len(adm.pieces) > 1:
                    sp.attrs.update(chunk=adm.done,
                                    chunks=len(adm.pieces))
        # A consumed shipment (`_shipped_row` clears the hook) ran NO
        # local prefill — it has its own serving_shipped_inserts_total,
        # and counting it would desync serving_prefills_total from the
        # serving_prefill_ms histogram it pairs with.
        local = adm.row is None
        if local:
            if not self._prefills:
                self._prefill_t0 = t0
            self._prefills.append((bucket, req))
            self._front_times.append(self.step_timer() - t0)
            self._spent = True
        return adm, row, local, behind

    def _admit_insert(self, front, now: float, read: bool) -> int:
        """Second half of a piece: the insert's dispatch into the
        request's slot and, behind the last piece, the books.
        ``read``: the step in flight was read for this piece
        (`serving.admit.request`'s ``read_flight``).  Returns 1 when
        the request is now running, 0 while chunks are left."""
        adm, row, local, behind = front
        req, tokens = adm.req, adm.tokens
        start, bucket = adm.pieces[adm.done]
        adm.done += 1
        last = adm.done == len(adm.pieces)
        self._underway = None if last else adm
        reg = self._registry()
        with span("serving.admit.request", request_id=req.request_id,
                  prompt_len=req.prompt_len) as sp:
            cached = 0
            if self.paged:
                s = len(tokens)
                cached = len(adm.shared) * self.config.page_size
                if adm.slot is None:
                    adm.slot = self.slots.begin_prefill(s, adm.shared)
                slot = adm.slot
                if last:
                    cursor = (s // self._block * self._block
                              if self._block > 1 else None)
                    self.slots.insert_rows(slot, row, start, adm.key,
                                           cursor)
                    self.slots.finish_prefill(slot, tokens, cursor)
                else:
                    # rows alone: the slot stays masked, untouched
                    self.slots.insert_rows(slot, row, start)
            else:
                slot = self.slots.insert_prefill(row, len(tokens),
                                                 adm.key)
            if last:
                if self._block > 1:
                    self._start_block(slot, req, tokens)
                else:
                    self._tokens[slot] = tokens[-1]
                self._fresh[slot] = True
                req.state = RequestState.RUNNING
                req.slot = slot
                req.bucket = bucket
                req.t_admitted = now
                self._by_slot[slot] = req
                if self.drafter is not None and not self._spec_throttled:
                    # Admission (or resume) seeds the draft state from
                    # the full committed context — same tokens that
                    # seeded the slot's input above.  A throttled
                    # engine skips the upkeep entirely (draft prefills,
                    # reconcile dispatches): the throttle is for the
                    # scheduler's lifetime, so the draft cache will
                    # never be read.
                    self.drafter.start(req, tokens)
                life = get_tracer().detached(
                    "serving.request", request_id=req.request_id,
                    prompt_len=req.prompt_len, slot=slot, bucket=bucket)
                life.__enter__()
                self._spans[slot] = life
            if sp is not NULL_SPAN:
                sp.attrs.update(bucket=bucket, cached_tokens=cached,
                                mode=adm.mode, slot=slot,
                                read_flight=int(read))
        if not reg:
            return int(last)
        if local:
            reg.counter("serving_prefills_total",
                        bucket=str(bucket)).inc()
            if len(adm.pieces) > 1:
                reg.counter("serving_prefill_chunks_total").inc()
            # enqueued and never waited for: behind the step in
            # flight, after its read, or with nothing in flight
            reg.counter("serving_admit_overlapped_total",
                        flight=_flight_label(behind, read)).inc()
        if not last:
            return 0
        reg.histogram("serving_queue_wait_ms").observe(
            max(now - req.t_arrival, 0.0) * 1e3)
        if (req.resume_tokens is not None or req.preemptions
                or req.resume_key is not None):
            # A preempt-and-requeue (or failover re-prefill)
            # resume: the "resume" half of the seam.  The
            # tokens recomputed by this admission are the
            # preemption's waste bill.
            self._charge_tokens("reprefill", req, len(tokens))
            self._hop(req, "admit", now, slot=slot,
                      bucket=bucket, mode=adm.mode, resumed=True)
        else:
            self._hop(req, "admit", now, slot=slot,
                      bucket=bucket, mode=adm.mode)
        return 1

    def _plan(self, req: Request, now: float,
              reg) -> Optional[_Admission]:
        """How ``req`` (just off the queue) gets into a slot: the radix
        prefix match, then the prefill enqueues it takes — a shipment's
        row and none; on a hit with a prefix-aware model ONLY the
        private suffix (near-zero-cost shared system prompts); in
        chunks where more than the model's chunk length is left; else
        the whole prompt through its bucket.  A model with recurrent
        layers prefills from position 0 whatever matched (its state has
        no snapshot: the matched pages are shared for storage alone),
        and so does one with sliding-window layers (the matched pages
        are the full layers'; a window page is never shared).
        None: the request had to be retired at admission (a resumed
        stream that no longer fits any prefill bucket)."""
        tokens = req.resume_tokens or req.prompt
        s = len(tokens)
        shared = self.slots.match_prefix(tokens) if self.paged else []
        c = len(shared) * self.config.page_size
        key = self._request_key(req)
        #: where the prefill begins
        lo = self._prefill_from(c)
        if req.shipped_kv is not None and req.resume_tokens is None:
            # Prefill-worker shipment: the full-prompt row arrives
            # precomputed; shared prefix pages (if any matched) are
            # still mapped and the insert discards their writes, so
            # storage sharing composes with shipping unchanged.
            row, s2, bucket = self._shipped_row(req, reg)
            assert s2 == s, (s2, s)
            adm = _Admission(req, tokens, key, shared, "shipped",
                             [(0, bucket)], row=row)
        elif self._chunk and s - lo > self._chunk:
            # More than a chunk to prefill: a chunk a step, each
            # attending what its predecessors (and a prefix hit) left
            # in the pool.
            adm = _Admission(req, tokens, key, shared, "chunk",
                             [(at, self._chunk)
                              for at in range(lo, s, self._chunk)])
        elif (lo > 0 and self._prefill_suffix is not None
              and (bucket := pick_bucket(s - c, self.buckets))):
            # Prefix hit with a prefix-aware model: prefill ONLY the
            # private suffix — the shared pages are already in the
            # pool.  This is the compute half of prefix sharing (the
            # storage half — page reuse — works for any model).
            adm = _Admission(req, tokens, key, shared, "suffix",
                             [(c, bucket)])
        else:
            bucket = pick_bucket(s, self.buckets)
            if bucket is None:
                self._retire_unbucketed(req, now, reg)
                return None
            adm = _Admission(req, tokens, key, shared, "local",
                             [(0, bucket)])
        redone = s if req.resume_tokens is not None else c
        if self._stateful and redone and adm.row is None:
            # a snapshot of the state would have saved these
            self._state_recomputed += redone
            if reg:
                reg.counter(
                    "serving_state_recomputed_tokens_total").inc(redone)
        if self._windowed and redone and adm.row is None:
            # a hit that kept the prefix's last window would have
            self._window_recomputed += redone
            if reg:
                reg.counter(
                    "serving_window_recomputed_tokens_total").inc(redone)
        if reg and self.paged:
            reg.counter("serving_prefix_cache_hit_tokens_total").inc(c)
            reg.counter("serving_prefix_cache_miss_tokens_total").inc(
                s - c)
        return adm

    def _retire_unbucketed(self, req: Request, now: float, reg) -> None:
        """No full-prompt bucket for a request already off the queue.
        (The matched chain was never acquired — nothing to undo.)  Two
        ways here."""
        assert self.paged                 # submit() validated
        if (req.resume_tokens is None and req.resume_key is None
                and not req.generated):
            # A fresh request admitted on the strength of a
            # cached prefix (prefix-dependent admission,
            # `structural_reject`) whose prefix was EVICTED
            # under pressure before it reached a slot: shed
            # it with the truthful reason.  With spill
            # enabled the prefix would have been restored —
            # this branch is the no-spill degradation.
            req.state = RequestState.REJECTED
            req.reject_reason = RejectReason.KV_PRESSURE
            req.t_finish = now
            if reg:
                reg.counter("serving_requests_rejected_total",
                            reason=RejectReason.KV_PRESSURE.value).inc()
                self._hop(req, "reject", now,
                          reason=RejectReason.KV_PRESSURE.value)
            self.finished.append(req)
            return
        # Resume: prompt + generated outgrew every bucket —
        # deliver what it has.
        req.state = RequestState.FINISHED
        req.finish_reason = FinishReason.KV_CAPACITY
        req.t_finish = now
        if reg:
            reg.counter("serving_requests_completed_total",
                        reason=FinishReason.KV_CAPACITY.value).inc()
            self._hop(req, "retire", now,
                      reason=FinishReason.KV_CAPACITY.value,
                      generated=len(req.generated))
        self.finished.append(req)

    def _enqueue_piece(self, adm: _Admission, sp):
        """ENQUEUE the prefill of the admission's next piece; ``sp`` is
        the `serving.admit.prefill` span, told whether the enqueue
        found the chip idle.  Returns the row its insert takes."""
        if adm.row is not None:
            return adm.row
        start, bucket = adm.pieces[adm.done]
        s = len(adm.tokens)
        ids, _ = pad_prompt(adm.tokens[start:start + bucket], bucket,
                            self.config.pad_id)
        row_in = self._row_cache(bucket)
        if self._stateful:
            # the state absorbs what lies below position s-1 (the
            # first decode step takes that token, as it rewrites that
            # position's K/V), never the bucket's padded tail; a chunk
            # starts from what the one before it returned (the reusable
            # row's own zeros in front of the first: never written)
            was = row_in if adm.carry is None else adm.carry
            row_in = dataclasses.replace(
                row_in, states=was.states, convs=was.convs,
                length=np.full(
                    (1,), min(max(s - 1 - start, 0), bucket), np.int32))
        if adm.mode == "local":
            self._starved("prefill", sp)
            _, row = self._prefill(self.params, ids, row_in)
            return row
        # positions ``start ...`` over the rows below them: the pages
        # are claimed here where the piece reads them, else — as for a
        # whole prefill — at its insert, behind the enqueue
        if start and adm.slot is None:
            adm.slot = self.slots.begin_prefill(s, adm.shared)
        pages = (self.slots.prefill_pages(adm.slot)
                 if adm.slot is not None else self._no_pages)
        cache = self.slots.cache
        pools = (cache.ks, cache.vs)
        if self._windowed:
            # the window layers' pools, and their pages in a second row
            pools += (cache.wks, cache.wvs)
            pages = np.stack([pages, (
                self.slots.prefill_window_pages(adm.slot)
                if adm.slot is not None else self._no_pages)])
        self._starved("prefill", sp)
        row = self._prefill_suffix(self.params, ids, jnp.int32(start),
                                   row_in, pools, pages)
        if self._stateful:
            adm.carry = row
        return row

    def _give_up_underway(self) -> None:
        """Drop the admission under way: its slot and pages go back,
        its request to the head of the queue (what was enqueued for it
        writes pages nobody reads before their next owner's own
        rows; its prefills stay counted where a read still times them).
        A carried state goes with it: the request starts again from
        position 0."""
        adm, self._underway = self._underway, None
        if adm is None:
            return
        adm.carry = None
        if adm.slot is not None:
            self.slots.release(adm.slot)
        self._queue.appendleft(adm.req)

    # -- generation by blocks (module docstring) -------------------------

    def _start_block(self, slot: int, req: Request, tokens) -> None:
        """Admission (or resume) of a block-generating request: the
        prefill left the whole blocks of ``tokens`` below the cursor —
        a finished block whose commit was still to come among them —
        and what is left of them enters the first block in flight
        already revealed, the rest of it masked.  A resume drops the
        block that was in flight and redoes it from the tokens
        delivered."""
        n = self._block
        start = len(tokens) // n * n
        tail = list(tokens[start:])
        blk = self._blk_host[slot]
        blk[:] = 0
        blk[0, n:n + len(tail)] = tail
        blk[1, n:n + len(tail)] = 1
        req.block_start = start
        req.block_masked = n - len(tail)
        req.block_pending = False

    def _block_ends(self, req: Request) -> bool:
        """True once the passes DISPATCHED for ``req`` reveal its last
        token and every position before it: no further pass is
        dispatched, so no pass carries the last block's commit
        (nothing will read it).  Sequential reveals leave a revealed
        prefix, so that is known position by position; otherwise only
        a block with nothing masked is known to hold its every token —
        the last block then runs whole."""
        end = req.prompt_len + req.max_new_tokens
        edge = req.block_start + self._block
        if self._sequential:
            return edge - req.block_masked >= end
        return req.block_masked == 0 and edge >= end

    def _dispatch_block(self, rows: Dict[int, Request], t0: float,
                        inflight: bool) -> _Flight:
        """Enqueue one block pass for ``rows`` — each row's block in
        flight reveals its next share, and a row whose block before it
        is finished and not committed yet (`Request.block_pending`)
        has that commit carried along — and move the host's picture of
        each row on as the program moves its own: a block that this
        pass finishes becomes the pending one and the next block, all
        masked, the one in flight, unless the request ends with it.
        Nothing here reads the device."""
        n = self._block
        slots = self.config.num_slots
        active = np.zeros(slots, bool)
        n_reveal = np.zeros(slots, np.int32)
        denoised = {}
        self._count_dispatch(inflight)
        for slot, req in rows.items():
            active[slot] = True
            k = min(self._reveal, req.block_masked)
            n_reveal[slot] = k
            req.block_masked -= k
            finished = req.block_masked == 0
            denoised[slot] = (req.block_start, k, req.block_pending,
                              finished)
            req.block_pending = finished and not self._block_ends(req)
            if req.block_pending:
                req.block_start += n
                req.block_masked = n
        with span("serving.dispatch", k=n, spec=False,
                  inflight=int(inflight)) as sp:
            self._starved("step", sp)
            blk, cache = self._step(
                self.params, self._prev, self.slots.cache,
                self._blk_host.copy(), self._fresh.copy(), active,
                n_reveal)
            self.slots.cache = cache
        self._prev = blk
        self._fresh[:] = False
        return _Flight(blk, rows, t0, counted=self._moe_counters(),
                       denoised=denoised)

    def _commit_block(self, rows, flight: _Flight, blk_host, now,
                      reg):
        """Deliver what one block pass revealed: a token reaches its
        request as soon as it and every earlier position are revealed
        — in position order, each once; positions past the request's
        length are never delivered.  (A block the pass finished stands
        in the front half of the state it returned.)  Every row has
        its tokens before any row is retired, as in `_commit_tokens`.
        Returns (rows ended: [(slot, reason)], tokens delivered)."""
        n = self._block
        generated = held = 0
        ended = []
        for slot, req in rows:
            start, _, _, finished = flight.denoised[slot]
            half = slice(0, n) if finished else slice(n, 2 * n)
            toks, shown = blk_host[slot][:, half]
            j = req.prompt_len + len(req.generated) - start
            reason = None
            while j < n and shown[j] and reason is None:
                reason = self._emit_token(slot, req, int(toks[j]), now,
                                          reg)
                generated += 1
                j += 1
            if reason is not None:
                ended.append((slot, reason))
            else:
                last = req.prompt_len + req.max_new_tokens - start
                held += int(shown[j:last].sum())
        self._held_back = held
        return ended, generated

    def _diffusion_phase(self, flight: _Flight, delivered: int,
                         reg) -> None:
        """After a block pass's host sync and commit: what the pass
        was, as a `serving.diffusion` span's attributes and as metrics
        (all the host's own counts: no sync of its own).  Every row's
        pass is a denoise pass; ``rows_folded`` of them carried the
        commit of the block before theirs, and none is a commit alone
        (``rows_commit`` stands at 0: the benchmark's reader of tokens
        a pass sums it with ``rows_denoise``).  ``positions_fed``
        counts the positions that carry work: a dead front half is
        padding."""
        n = self._block
        denoise = len(flight.denoised)
        folded = sum(f for _, _, f, _ in flight.denoised.values())
        revealed = sum(k for _, k, _, _ in flight.denoised.values())
        with span("serving.diffusion") as sp:
            sp.attrs.update(
                rows_denoise=denoise, rows_folded=folded, rows_commit=0,
                positions_fed=n * (denoise + folded),
                tokens_revealed=revealed, tokens_delivered=delivered,
                blocks_committed=folded)
        if reg:
            reg.counter("serving_diffusion_passes_total",
                        phase="denoise").inc(denoise - folded)
            reg.counter("serving_diffusion_passes_total",
                        phase="folded").inc(folded)
            reg.counter(
                "serving_diffusion_tokens_revealed_total").inc(revealed)
            reg.counter(
                "serving_diffusion_blocks_committed_total").inc(folded)
            reg.gauge("serving_diffusion_tokens_held_back").set(
                self._held_back)

    def _ahead(self, req: Request) -> int:
        """Tokens dispatched for ``req`` and not read yet (0 or 1)."""
        f = self._flight
        return int(f is not None and f.rows.get(req.slot) is req)

    def _ends_unread(self, req: Request) -> bool:
        """True when the step in flight holds ``req``'s LAST token: it
        ends by length or at the KV horizon, which no token decides —
        so it is known before that step is read, and the row is not in
        the next dispatch."""
        ahead = self._ahead(req)
        if self._block > 1:
            return bool(ahead) and self._block_ends(req)
        g = len(req.generated) + ahead
        return bool(ahead) and (g >= req.max_new_tokens
                                or req.prompt_len + g > self.max_seq)

    def _prepare_pages(self, k: int) -> bool:
        """Paged mode, before a dispatch: every active slot must have
        pages mapped for the ``k`` positions this dispatch writes.  A
        row's length after the step in flight does not depend on that
        step's token, so nothing here waits for the device.
        The pool evicts unreferenced prefix pages on demand; if it is
        STILL dry, preempt the most recently admitted request (its
        pages fund the older ones; it resumes later, exactly — see
        `Request.resume_tokens`).  Admission feasibility guarantees a
        sole remaining request can always grow to its horizon.

        False: the pool is dry and a step is in flight.  Preemption
        reads the victim's committed tokens and its slot's key, so the
        caller first reads that step (which may retire rows and free
        pages), then calls again."""
        while True:
            ok = True
            for slot, req in list(self._by_slot.items()):
                # Cap at the request's OWN horizon (what feasible()
                # budgeted), not just max_seq.  A row that ends by
                # EOS is seen one step late: the step that ran for it
                # wrote below this horizon (into its own page, or
                # through a NULL page-table entry into the trash
                # page) and its token is discarded.  Kept tokens only
                # ever attend KV below the horizon, so this is exact.
                if self._block > 1:
                    # the block in flight, mapped whole (a finished
                    # block before it, whose commit this dispatch
                    # carries, is mapped already)
                    need = min(req.block_start + self._block,
                               self.max_seq)
                else:
                    need = min(req.prompt_len + len(req.generated)
                               + self._ahead(req) + k - 1,
                               req.prompt_len + req.max_new_tokens - 1,
                               self.max_seq)
                if not self.slots.ensure(slot, need):
                    ok = False
                    break
            if ok:
                return True
            if self._flight is not None:
                return False
            if (self._underway is not None
                    and self._underway.slot is not None):
                # the newest claim on the pool: its pages fund the
                # rows that are running, it starts over later
                self._give_up_underway()
                continue
            assert len(self._by_slot) > 1, (
                "page pool cannot hold a sole feasible request — "
                "allocator invariant broken")
            victim = max(self._by_slot,
                         key=lambda sl: (self._by_slot[sl].t_admitted,
                                         self._by_slot[sl].request_id))
            self._preempt(victim)

    def _pages_phase(self, writes: int, sp) -> bool:
        """Paged mode, the KV phase of a dispatch: map the pages it
        writes (evicting, preempting), then re-ship the page table if
        that changed it.  ``sp`` is the `serving.pages` span (or the
        no-op one): it records what the phase did, and the pages live
        requests hold once it is done.  False: `_prepare_pages` needs
        the step in flight read first."""
        slots = self.slots

        def work():
            return (slots.mapped_pages, slots.flushed_rows,
                    slots.radix.freed_pages if slots.radix else 0,
                    -len(self._by_slot))

        before = work() if sp is not NULL_SPAN else None
        mapped_all = self._prepare_pages(writes)
        if mapped_all and self._by_slot:
            slots.flush()
        if before is not None:
            mapped, flushed, evicted, preempted = (
                x - y for x, y in zip(work(), before))
            sp.attrs.update(mapped=mapped, flushed_rows=flushed,
                            evicted=evicted, preempted=preempted,
                            live_pages=(slots.live_pages
                                        + slots.window_pages_live))
        return mapped_all

    def _preempt(self, slot: int) -> None:
        assert self._flight is None, "preempting with a step in flight"
        req = self._by_slot.pop(slot)
        if self.drafter is not None:
            # Draft state is rebuilt from the committed context at
            # re-admission — nothing mid-speculation survives the
            # preemption (the verify pass already rolled the slot's
            # cursor and key chain back to committed state, so the
            # snapshot below is exact).
            self.drafter.stop(req)
        # The slot's PRNG key is the sample-chain state: snapshot it
        # so the resumed stream continues bit-exactly.
        req.resume_key = self.slots.snapshot_key(slot)
        req.resume_tokens = list(req.prompt) + list(req.generated)
        req.preemptions += 1
        req.state = RequestState.QUEUED
        req.slot = None
        self.slots.release(slot)
        self._tokens[slot] = self.config.pad_id
        self._fresh[slot] = False
        sp = self._spans.pop(slot, None)
        if sp is not None:
            sp.__exit__(None, None, None)
        self._queue.appendleft(req)
        reg = self._registry()
        if reg:
            reg.counter("serving_preemptions_total").inc()
            self._hop(req, "preempt", self.clock(),
                      generated=len(req.generated),
                      preemptions=req.preemptions)

    def _spec_drafts(self):
        """Proposals for this dispatch — ``(drafts (B, K), n_draft
        (B,))`` numpy — or None when speculation cannot help this
        round (spec off, a row too close to its KV horizon for K+1
        writes, or nobody proposed): the caller then takes the plain
        masked step, bit-identically."""
        if self._spec_fn is None or not self._by_slot:
            return None
        if self._spec_throttle():
            return None
        K = self.config.spec_k
        for req in self._by_slot.values():
            # The verify pass writes K+1 positions, which a row
            # near its KV horizon has no room for.
            if (self.max_seq - req.prompt_len - len(req.generated)
                    + 1) < K + 1:
                return None
        # Proposals beyond a request's own budget are pure waste
        # (retire truncates at max_new anyway): cap at remaining - 1
        # — the bonus token is the +1.
        caps = {slot: min(K, req.max_new_tokens
                          - len(req.generated) - 1)
                for slot, req in self._by_slot.items()}
        eligible = {slot: self._by_slot[slot]
                    for slot, c in caps.items() if c > 0}
        if not eligible:
            return None
        if getattr(self.drafter, "batched", False):
            # One masked rollout dispatch proposes for every slot;
            # the draft VALUES stay on device (the verify program
            # consumes them there — no per-round proposal sync).
            out = self.drafter.propose_batched(eligible, K)
            if out is None:
                return None
            drafts, n_draft = out
            n_draft = n_draft.copy()
            for slot, c in caps.items():
                n_draft[slot] = min(int(n_draft[slot]), c)
            if not n_draft.any():
                return None
            return drafts, n_draft
        props = {slot: self.drafter.propose(req, K)
                 for slot, req in eligible.items()}
        drafts = np.full((self.config.num_slots, K),
                         self.config.pad_id, np.int32)
        n_draft = np.zeros(self.config.num_slots, np.int32)
        for slot, p in props.items():
            n = min(len(p), caps[slot])
            if n > 0:
                drafts[slot, :n] = p[:n]
                n_draft[slot] = n
        if not n_draft.any():
            return None
        return drafts, n_draft

    def _spec_throttle(self) -> bool:
        """Accept-collapse guard (``spec_min_accept``): once the
        cumulative accept rate is measurably below the floor,
        drafting stops — recorded ONCE as a DecisionEvent and a
        counter, visible on the accept-rate gauge the doctor reads.
        The fallback is the plain masked step, so throttling changes
        dispatch shape only — never tokens."""
        if self._spec_throttled:
            return True
        floor = self.config.spec_min_accept
        if (not floor
                or self._spec_proposed < self.config.spec_probe_tokens
                or self._spec_accepted
                >= floor * self._spec_proposed):
            return False
        self._spec_throttled = True
        rate = self._spec_accepted / self._spec_proposed
        name = self.drafter.name
        # The throttle is for the scheduler's lifetime: release the
        # drafter (a batched one pins a device-resident draft KV
        # cache + compiled rollout/reconcile programs) and the verify
        # program — every call site guards on `drafter is not None`,
        # and in-flight requests simply stop being assisted.
        self.drafter = None
        self._spec_fn = None
        reg = self._registry()
        if reg:
            reg.counter("serving_spec_throttled_total").inc()
        from triton_distributed_tpu.observability import feedback
        feedback.record_decision(feedback.DecisionEvent(
            consumer="serving.speculative",
            op=f"drafter:{name}", choice="throttle",
            candidates=[{"name": "speculate",
                         "score_us": round(rate, 4)},
                        {"name": "throttle"}],
            inputs=dict(accept_rate=round(rate, 4),
                        min_accept=float(floor),
                        proposed=self._spec_proposed,
                        accepted=self._spec_accepted)))
        return True

    def _decode_step(self) -> int:
        """Dispatch the next decode step, THEN read the one in flight
        (dispatched by the previous iteration) and commit its tokens:
        while the host commits, returns to its caller, admits and
        enqueues, the device runs.  Returns the rows retired."""
        t0 = self.step_timer()
        retired = 0
        spec = self._spec_drafts()
        # Paged mode maps pages for every position this dispatch
        # writes: K proposals + the bonus position under speculation.
        writes = self.config.spec_k + 1 if spec is not None else 1
        if self.paged and not all(map(self._ends_unread,
                                      self._by_slot.values())):
            # (only where a dispatch follows: a page table shipped and
            # then met first by an insert would be a kind of argument
            # no set-up has shown that program)
            with span("serving.pages") as sp:
                mapped = self._pages_phase(writes, sp)
            if not mapped:
                # Pool dry with a step in flight: read it (it may
                # retire rows), then map again, preempting if need be.
                retired += self._read(self._take_flight())
                with span("serving.pages") as sp:
                    self._pages_phase(writes, sp)
        rows = {slot: req for slot, req in self._by_slot.items()
                if not self._ends_unread(req)}
        prior = self._take_flight()
        if rows and self._block > 1:
            self._flight = self._dispatch_block(rows, t0,
                                                prior is not None)
        elif rows:
            self._flight = self._dispatch(rows, spec, t0,
                                          prior is not None)
        if rows and self._prefills:
            # program order puts them in front of this dispatch: its
            # read is theirs, and counts from the first one's enqueue
            self._flight.prefills, self._prefills = self._prefills, []
            self._flight.t0 = self._prefill_t0
        if prior is not None:
            retired += self._read(prior)
        if self.config.spec_k and self._flight is not None:
            # Drafts are built from tokens the host has read: a
            # speculating scheduler keeps nothing in flight.
            retired += self._read(self._take_flight())
        return retired

    def _take_flight(self) -> Optional[_Flight]:
        flight, self._flight = self._flight, None
        return flight

    def _drop_flight(self) -> None:
        """Forget the step in flight, unread (`stop`): its tokens are
        counted as discarded, and the prefills nothing will time any
        more as unobserved."""
        flight = self._take_flight()
        lost = len(self._prefills)
        self._prefills = []
        reg = self._registry()
        if flight is not None and reg:
            reg.counter("serving_decode_discarded_tokens_total").inc(
                len(flight.rows))
            lost += len(flight.prefills)
        if lost and reg:
            reg.counter("serving_prefill_unobserved_total").inc(lost)

    def _starved(self, program: str, sp) -> None:
        """Just before the enqueue of a prefill or a step, under its
        span ``sp``: has the program enqueued last — a step, an insert,
        a slot's reset: the cache as it stands is its output, and is
        asked before the next program donates it — finished?  Then the
        chip is standing idle for this enqueue: ``starved=1`` on the
        span, and counted.  The first enqueue of a call that began
        with nothing to do is starved for want of arrivals
        (``idle=1``), which a reader leaves out and the counter does
        not count.  One non-blocking question; none where nothing
        records."""
        if sp is NULL_SPAN:
            return
        idle, self._idle = self._idle, False
        starved = idle or _is_ready(self.slots.cache.offset)
        sp.attrs["starved"] = int(starved)
        reg = self._registry()
        reg.counter("serving_enqueues_total", program=program).inc()
        if idle:
            sp.attrs["idle"] = 1
        elif starved:
            reg.counter("serving_enqueue_starved_total",
                        program=program).inc()

    def _count_dispatch(self, inflight: bool) -> None:
        self._spent = False     # the next chunk may follow this step
        reg = self._registry()
        if reg:
            reg.counter("serving_decode_dispatch_total").inc()
            if inflight:
                reg.counter("serving_decode_overlapped_total").inc()

    def _dispatch(self, rows: Dict[int, Request], spec, t0: float,
                  inflight: bool) -> _Flight:
        """Enqueue one decode dispatch for ``rows``.  Every argument
        of every program has a fixed provenance: the previous tokens
        are what the last dispatch returned, the host's tokens and the
        masks are fresh `numpy` arrays of fixed shape and dtype (new
        ones each time: the step that reads them may still be running
        when the host next writes its own), the page table is shipped
        by `PagedKV.flush`."""
        active = np.zeros(self.config.num_slots, bool)
        active[list(rows)] = True
        self._count_dispatch(inflight)
        if spec is not None:
            drafts, n_draft = spec
            with span("serving.dispatch", k=self.config.spec_k,
                      spec=True, inflight=int(inflight)) as sp:
                self._starved("step", sp)
                targets, accept, cache, keys = self._spec_fn(
                    self.params, jnp.asarray(self._tokens),
                    jnp.asarray(drafts), self.slots.cache,
                    self.slots.keys, active, jnp.asarray(n_draft))
                self.slots.cache = cache
                self.slots.keys = keys
            return _Flight(targets, rows, t0, accept=accept,
                           n_draft=n_draft)
        # A speculating scheduler reads every step before the next, and
        # a verify round leaves no token vector of this form behind:
        # there the host's word counts for every row.
        fresh = active if self.config.spec_k else self._fresh.copy()
        with span("serving.dispatch", k=1, spec=False,
                  inflight=int(inflight)) as sp:
            self._starved("step", sp)
            tokens = self._merge(self._prev, self._tokens.copy(), fresh)
            toks, cache, keys = self._step(
                self.params, tokens, self.slots.cache, self.slots.keys,
                active)
            self.slots.cache = cache
            self.slots.keys = keys
        self._prev = toks
        self._fresh[:] = False
        return _Flight(toks, rows, t0, counted=self._moe_counters())

    def _read(self, flight: _Flight, early: bool = False) -> int:
        """Read one dispatch's tokens — THE host sync — and commit
        them; ``early``: the read an admitting call makes between or
        ahead of its admission's halves.  Returns the rows retired."""
        spec = flight.accept is not None
        block = flight.denoised is not None
        accept_host = None
        with span("serving.sync") as sync:
            # landed already: the sync waits for nothing, and the
            # tokens waited for the host
            late = sync is not NULL_SPAN and _is_ready(flight.toks)
            toks_host = np.asarray(flight.toks)   # THE host sync
            if spec:
                accept_host = np.asarray(flight.accept)
        landed = self.step_timer()
        now = self.clock()
        reg = self._registry()
        if not spec and not block:
            toks_host = toks_host[:, None]
        if flight.counted is not None:
            self._moe_phase(flight.counted, len(flight.rows), reg)
        # A row that ended by EOS one step ago ran in this step too
        # (its slot may already hold another request): its token is
        # discarded.
        rows = [(slot, req) for slot, req in flight.rows.items()
                if self._by_slot.get(slot) is req]
        discarded = len(flight.rows) - len(rows)
        if sync is not NULL_SPAN:
            self._read_record(sync, flight, rows, landed, early, late)
        if self._windowed and reg:
            self._window_phase(rows, reg)
        # One step's time: from its dispatch — or from when the step
        # before it landed, if that was later: the device runs one
        # step at a time — to its own tokens on the host.
        elapsed_ms = (landed - max(flight.t0, self._read_at)) * 1e3
        self._read_at = landed
        # A read that carried a prefill measures prefill + step: the
        # SLO gate and the router price "a token here, now" from the
        # step metrics, and a prefill inside would read as a straggler.
        prefill_ms = 0.0
        if flight.prefills:
            prefill_ms = self._prefill_reading(flight.prefills,
                                               elapsed_ms, reg)
        else:
            self._step_times.append(elapsed_ms / 1e3)
        if reg and discarded:
            reg.counter("serving_decode_discarded_tokens_total").inc(
                discarded)
        if reg and not flight.prefills:
            # Normalize the step metric by tokens COMMITTED, not
            # positions scanned: serving_decode_step_ms/us feed the
            # SLO admission baseline and the router's placement
            # scoring as "cost per token here, now" — a collapsed
            # drafter must read as slow (K+1 forwards, ~1 token),
            # not as K+1 healthy steps.
            steps = (float(np.mean(accept_host[[s for s, _ in rows]]))
                     + 1.0 if spec and rows else 1.0)
            step_ms = elapsed_ms / steps
            reg.histogram("serving_decode_step_ms").observe(step_ms)
            # Last measured step as a gauge: rides the heartbeat
            # files, where it is the `step_us` a PEER router scores
            # placement from (`cluster.router.heartbeat_signals`).
            reg.gauge("serving_decode_step_us").set(step_ms * 1e3)
            # Rolling-baseline anomaly check on the serving hot path:
            # a decode step that goes multi-sigma slow (a contended
            # ICI link, a straggling rank) is counted AND dropped into
            # the flight ring, so a later doctor report can line the
            # slow step up against what else was on the links.  The
            # store is memory-only here (no disk I/O per step).
            from triton_distributed_tpu.observability.anomaly import (
                Z_THRESHOLD, get_baseline_store)
            # Warm tuned-kernel baselines in production: tuners armed
            # with `autotuner.arm_serving_observation` receive every
            # step's host latency — the same feed the bench drivers
            # give `observe_runtime`, so the closed loop's sustained-z
            # invalidation works from serving traffic, not just
            # benches (ROADMAP item 4 follow-up).
            from triton_distributed_tpu import autotuner as _autotuner
            _autotuner.observe_serving_step(step_ms * 1e3)
            z = get_baseline_store().observe(self._step_key,
                                             step_ms * 1e3)
            if z is not None and z > Z_THRESHOLD:
                reg.counter("serving_decode_anomalies_total").inc()
                from triton_distributed_tpu.observability.events \
                    import emit_kernel_event
                emit_kernel_event(
                    "serving.decode_step", kind="engine",
                    measured_us=step_ms * 1e3, anomaly_z=round(z, 2))
        if reg and rows:
            # Cost attribution: the dispatch's measured window is
            # split exactly across the rows that ran in it (a spec
            # round is one fused draft+verify window — charged to the
            # verify phase, mirroring the spec_verify lineage hop),
            # and each row's pinned KV pages integrate page-seconds
            # since their previous charge.
            self._charge_device(
                "spec_verify" if spec else "decode",
                (elapsed_ms - prefill_ms) * 1e3, [r for _, r in rows])
            self._charge_kv_residency([r for _, r in rows], now)
        with span("serving.commit") as sp:
            if spec:
                self._spec_outcome(rows, accept_host, flight.n_draft,
                                   now, reg)
            # Deliver, then retire: every row of the read has its
            # token(s) before the first slot is released, so no
            # stream waits out a neighbour's retirement.
            outcomes = ()
            if block:
                ended, generated = self._commit_block(
                    rows, flight, toks_host, now, reg)
            else:
                ended, generated, outcomes = self._commit_tokens(
                    rows, toks_host, accept_host, now, reg)
            delivered = time.perf_counter()
            for slot, reason in ended:
                self._retire(slot, now, reason)
            if outcomes:
                self.drafter.commit_batched(outcomes)
            retired = len(ended)
            if sp is not NULL_SPAN:
                # The span's two halves on its own clock.  A commit
                # that retires nothing is all delivery.
                done = time.perf_counter()
                if not ended:
                    delivered = done
                sp.attrs.update(
                    tokens=generated, retired=retired,
                    discarded=discarded,
                    deliver_ms=(delivered - sp.t0) * 1e3,
                    retire_ms=(done - delivered) * 1e3)
        if block:
            self._diffusion_phase(flight, generated, reg)
        if reg:
            reg.counter("serving_tokens_generated_total").inc(generated)
        return retired

    def _read_record(self, sync, flight: _Flight, rows, landed: float,
                     early: bool, late: bool) -> None:
        """What a read knows of the token gap it closes, on its
        `serving.sync` span and as counters: commit to commit
        (``interval_ms``; none before a process's first read), the
        rows committed and how many of them got their request's first
        token (no gap), the prefills the device ran in front — however
        many: together they are what the interval holds over a plain
        one — and whether the tokens had landed before the host asked
        (``landed``)."""
        n = len(flight.prefills)
        sync.attrs.update(
            rows=len(rows),
            first_tokens=sum(not req.generated for _, req in rows),
            prefills=n,
            prefill_tokens=sum(bucket for bucket, _ in flight.prefills),
            early=int(early), landed=int(late))
        if self._read_at > float("-inf"):
            sync.attrs["interval_ms"] = (landed - self._read_at) * 1e3
        if 1 <= n <= 4:
            sync.attrs["prefill_request_ids"] = [
                req.request_id for _, req in flight.prefills]
        reg = self._registry()
        label = _prefills_label(n)
        reg.counter("serving_reads_total", prefills=label).inc()
        reg.counter("serving_read_rows_total",
                    prefills=label).inc(len(rows))
        if late:
            reg.counter("serving_read_late_total").inc()

    def _prefill_reading(self, prefills, elapsed_ms: float,
                         reg) -> float:
        """The read of a dispatch that had ``prefills`` in front of it
        took ``elapsed_ms``: less the rolling step time, that is what
        the prefills took — no sync of its own.  Their requests are
        charged it; where it was ONE prefill, `serving_prefill_ms` and
        its bucket's baseline (the router's ship-or-recompute price)
        get the reading; several cannot be told apart and are counted
        as unobserved, as is one read before any plain step was.
        Returns the milliseconds that were the prefills'."""
        if not self._step_times:
            if reg:
                reg.counter("serving_prefill_unobserved_total").inc(
                    len(prefills))
            return 0.0
        ms = max(elapsed_ms - self._step_s() * 1e3, 0.0)
        if reg:
            self._charge_device("prefill", ms * 1e3,
                                [req for _, req in prefills])
            if len(prefills) == 1:
                reg.histogram("serving_prefill_ms").observe(ms)
                _observe_prefill(prefills[0][0], ms)
            else:
                reg.counter("serving_prefill_unobserved_total").inc(
                    len(prefills))
        return ms

    def _moe_counters(self):
        """What a sparse model's expert layers counted in the dispatch
        just enqueued (`layers.moe_mlp.MOE_STATS`, left in the cache's
        `stats` by the decode program itself), its copy to the host
        started so that it lands with the step's tokens: no sync of
        its own.  It travels with its step (`_Flight.counted`) and is
        read when that step is.  None: the model counts nothing, or
        nothing records."""
        counted = getattr(self.slots.cache, "stats", None)
        if counted is None or self._registry() is None:
            return None
        # the next dispatch (or insert) donates the cache, and these
        # with it, before this step is read: keep a copy of our own
        counted = self._keep(counted)
        counted.copy_to_host_async()
        return counted

    def _moe_phase(self, counted, rows: int, reg) -> None:
        """After the step's host sync: the counters as a `serving.moe`
        span's attributes and as metrics — and, where the model keeps
        a recurrent state, a `serving.state` span beside it.  ``rows``:
        the live rows the step carried (the host's own count: what
        turns `experts_hit` into bytes a token)."""
        read = dict(zip(self._stats_names,
                        (float(v) for v in np.asarray(counted))))
        live = read.pop("live_slots", None)
        with span("serving.moe") as sp:
            sp.attrs.update(read, rows=rows)
        reg.counter("serving_moe_pairs_total").inc(read["pairs"])
        reg.counter("serving_moe_experts_hit_total").inc(
            read["experts_hit"])
        reg.gauge("serving_moe_expert_load_max").set(
            read["expert_load_max"])
        if live is None:
            return
        # a model with recurrent layers: what its state pool held in
        # that step, and what the host did to it since the last one
        seen = [self.slots.state_resets, self._state_recomputed]
        with span("serving.state") as sp:
            sp.attrs.update(
                live_slots=live,
                state_bytes_live=live * self.slots.state_bytes_per_slot,
                resets=seen[0] - self._state_seen[0],
                recomputed_tokens=seen[1] - self._state_seen[1])
        self._state_seen = seen

    def _window_phase(self, rows, reg) -> None:
        """A model with sliding-window layers: a `serving.window` span
        beside the step's read — what each kind of layer held for the
        rows of that step (pages, and the tokens its kernel read: the
        last ``window`` of a row for a window layer, all of them for a
        full one) and what the host gave back or recomputed since the
        last one.  All the host's own: no sync."""
        slots = self.slots
        lengths = [req.prompt_len + len(req.generated) for _, req in rows]
        seen = [slots.window_released, self._window_recomputed]
        with span("serving.window") as sp:
            sp.attrs.update(
                window_pages_live=slots.window_pages_live,
                full_pages_live=slots.live_pages,
                window_pages_released=seen[0] - self._window_seen[0],
                recomputed_tokens=seen[1] - self._window_seen[1],
                window_tokens_live=sum(min(n, slots.window)
                                       for n in lengths),
                full_tokens_live=sum(lengths))
        self._window_seen = seen
        reg.gauge("serving_kv_pages_live", kind="window").set(
            slots.window_pages_live)
        reg.gauge("serving_kv_pages_live", kind="full").set(
            slots.live_pages)

    def _spec_outcome(self, rows, accept_host, n_draft, now,
                      reg) -> None:
        """Post-verify bookkeeping, BEFORE tokens are appended: paged
        page rollback for the rejected tails, accept metrics, one
        ``spec_verify`` lineage hop per active request."""
        for slot, req in rows:
            a = int(accept_host[slot])
            n = int(n_draft[slot])
            if self.paged:
                # Restore the mapping to exactly what a plain engine
                # that decoded only the accepted prefix would hold:
                # pages covering [0, min(offset', horizon)) where
                # offset' = off0 + a + 1 — the rejected tail's pages
                # unmap and free (the rollback invariant
                # `analysis.serving_model` proves).
                off_new = req.prompt_len + len(req.generated) + a
                horizon = min(req.prompt_len + req.max_new_tokens - 1,
                              self.max_seq)
                self.slots.rollback(slot, min(off_new, horizon))
            req.spec_proposed += n
            req.spec_accepted += a
            self._spec_proposed += n
            self._spec_accepted += a
            if reg:
                reg.histogram("serving_spec_accept_tokens").observe(a)
                reg.counter(
                    "serving_spec_proposed_tokens_total").inc(n)
                reg.counter(
                    "serving_spec_accepted_tokens_total").inc(a)
                reg.counter(
                    "serving_spec_rejected_tokens_total").inc(n - a)
                self._charge_tokens("wasted_spec", req, n - a)
                self._hop(req, "spec_verify", now, proposed=n,
                          accepted=a)
        if reg and self._spec_proposed:
            reg.gauge("serving_spec_accept_rate").set(
                self._spec_accepted / self._spec_proposed)

    def _commit_tokens(self, rows, toks_host, accept_host, now, reg):
        """Append one dispatch's tokens to their requests: stream via
        ``on_token``, check EOS / budget / KV horizon and (speculative
        mode) note what the drafter has to reconcile with.  A row
        emits ``accept + 1`` tokens under speculation, else one;
        tokens decoded past a retirement reason are discarded —
        bounded over-generation.

        This is the DELIVERY half of a commit and retires nothing: a
        row that ended is only noted, and the caller retires the noted
        rows — in this loop's order, with this read's ``now`` — once
        the last row has its token.  Releasing a slot is milliseconds
        of host work (radix, pages, a stateful model's reset program);
        done inside the loop, every row behind the retiring one got
        its token that much later, and its client saw one gap a
        retirement too long.
        Returns (rows ended: [(slot, reason)], tokens delivered, a
        batched drafter's outcomes to commit AFTER the retirements)."""
        generated = 0
        ended = []
        batched = getattr(self.drafter, "batched", False)
        outcomes = []
        for slot, req in rows:
            count = (int(accept_host[slot]) + 1
                     if accept_host is not None else 1)
            committed = []
            reason = None
            for j in range(count):
                token = int(toks_host[slot, j])
                committed.append(token)
                generated += 1
                reason = self._emit_token(slot, req, token, now, reg)
                if reason is not None:
                    # Tokens decoded past this point are discarded —
                    # bounded over-generation.
                    ended.append((slot, reason))
                    break
            if reason is None:
                self._tokens[slot] = int(toks_host[slot, count - 1])
                if (self.drafter is not None
                        and not self._spec_throttled):
                    # Continuing stream: the drafter catches up with
                    # the committed outcome (accepted prefix kept,
                    # rejected tail rolled back; a plain-step commit
                    # is accept=0 with one token).  Batched drafters
                    # reconcile every row in one dispatch set, behind
                    # the retirements.
                    acc = (count - 1 if accept_host is not None
                           else 0)
                    if batched:
                        outcomes.append((req, acc, committed))
                    else:
                        self.drafter.commit(req, acc, committed)
        return ended, generated, outcomes

    def _emit_token(self, slot: int, req: Request, token: int,
                    now: float, reg) -> Optional[FinishReason]:
        """One token to its request: appended, timed, streamed via
        ``on_token``, then checked against EOS / budget / KV horizon.
        Returns the reason if it was the request's last, else None;
        the row is NOT retired here (it reads and writes nothing a
        retirement touches, so the commit can deliver a whole read
        first: `_commit_tokens`)."""
        req.generated.append(token)
        if req.t_first_token is None:
            req.t_first_token = now
            if reg:
                reg.histogram("serving_ttft_ms").observe(
                    max(req.ttft, 0.0) * 1e3)
                # The TTFT endpoint: `now` is the same clock
                # value the cluster's token mirror stamps, so
                # the lineage sum telescopes to the measured
                # TTFT exactly (ttft_breakdown's invariant).
                self._hop(req, "first_token", now, slot=slot)
        elif reg:
            # With a multi-token dispatch the whole batch
            # lands at one sync: TBT is reported at sync
            # granularity (the first token carries the gap,
            # the rest ~0).
            reg.histogram("serving_tbt_ms").observe(
                max(now - req.t_last_token, 0.0) * 1e3)
        req.t_last_token = now
        if req.on_token is not None:
            req.on_token(req, token)
        if token in req.eos_token_ids:
            return FinishReason.EOS
        if len(req.generated) >= req.max_new_tokens:
            return FinishReason.LENGTH
        if req.prompt_len + len(req.generated) > self.max_seq:
            # The NEXT step would write KV at offset
            # prompt+generated-1 > max_seq-1; the admission
            # rule mirrors this (the final token needs no KV
            # write of its own).
            return FinishReason.KV_CAPACITY
        return None

    def _retire(self, slot: int, now: float,
                reason: FinishReason) -> None:
        req = self._by_slot.pop(slot)
        if self.drafter is not None:
            self.drafter.stop(req)
        req.state = RequestState.FINISHED
        req.finish_reason = reason
        req.t_finish = now
        self.slots.release(slot)
        self._tokens[slot] = self.config.pad_id
        self._fresh[slot] = False
        sp = self._spans.pop(slot, None)
        if sp is not None:
            sp.__exit__(None, None, None)
        reg = self._registry()
        if reg:
            reg.counter("serving_requests_completed_total",
                        reason=reason.value).inc()
            if req.latency is not None:
                reg.histogram("serving_request_latency_ms").observe(
                    req.latency * 1e3)
            self._hop(req, "retire", now, reason=reason.value,
                      generated=len(req.generated))
        self.finished.append(req)

    def _update_gauges(self) -> None:
        reg = self._registry()
        if not reg:
            return
        reg.gauge("serving_queue_depth").set(len(self._queue))
        reg.gauge("serving_active_slots").set(self.slots.active_slots)
        reg.gauge("serving_slot_occupancy").set(self.slots.occupancy)
        reg.gauge("serving_kv_bytes_in_use").set(self.slots.bytes_in_use)
        reg.gauge("serving_kv_budget_bytes").set(
            self.slots.kv_budget_bytes)
        if self.paged:
            reg.gauge("serving_kv_pages_free").set(self.slots.free_pages)
            reg.gauge("serving_kv_pages_used").set(self.slots.used_pages)
            # Pages live requests hold, apart from the prefix pages
            # the radix cache merely retains: in use against reserved.
            reg.gauge("serving_kv_pages_live").set(self.slots.live_pages)
            reg.gauge("serving_kv_page_occupancy").set(
                self.slots.page_occupancy)
            latent = self.model.latent_bytes_per_token
            if latent:
                # a latent-attention model: the bytes of the live
                # context that carry information (rows less their pad)
                reg.gauge("serving_kv_latent_bytes_live").set(
                    latent * sum(r.prompt_len + len(r.generated)
                                 for r in self._by_slot.values()))
            if self._stateful:
                reg.gauge("serving_state_slots_live").set(
                    self.slots.active_slots)
                reg.gauge("serving_state_bytes_live").set(
                    self.slots.active_slots
                    * self.slots.state_bytes_per_slot)
            reg.gauge("serving_prefix_cache_pages").set(
                self.slots.cached_prefix_pages)
            # Per-tier admission accounting mirrored as gauges so the
            # hierarchy's hit profile rides heartbeat files into the
            # doctor's "KV tier" section (counters don't travel;
            # gauges do — the serving_decode_step_us precedent).
            for k, v in self.slots.tier_stats.items():
                reg.gauge(f"serving_kvtier_{k}").set(v)
            # Collapse inputs: is a warm (spill) tier even configured,
            # and how many evictions destroyed pages anyway?  The
            # doctor must never call a plain paged engine's ordinary
            # misses a "collapse" — only a configured tier failing to
            # absorb evictions is one.
            reg.gauge("serving_kvtier_warm_tiers").set(
                int(self.slots.spill is not None))
            if self.slots.radix is not None:
                reg.gauge("serving_kvtier_dropped_evictions").set(
                    self.slots.radix.evicted_pages)
