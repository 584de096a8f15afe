"""Serving requests: the unit the continuous-batching scheduler moves
through queue → slot → retirement.

A `Request` carries the immutable submission (prompt, generation
budget, EOS set, RNG seed, streaming callback) plus the mutable
lifecycle the scheduler writes: state, slot, SLO timestamps
(arrival / admission / first token / finish) and the generated tokens.
Timestamps come from the *scheduler's* clock — injectable, so tests
and benchmarks replay deterministic arrival schedules with no
wall-clock randomness.
"""

from __future__ import annotations

import dataclasses
import enum
import itertools
from typing import Callable, List, Optional, Sequence, Tuple

_next_id = itertools.count()


class RequestState(enum.Enum):
    QUEUED = "queued"
    RUNNING = "running"
    FINISHED = "finished"
    REJECTED = "rejected"


class FinishReason(enum.Enum):
    EOS = "eos"                  # sampled a token in `eos_token_ids`
    LENGTH = "length"            # hit `max_new_tokens`
    KV_CAPACITY = "kv_capacity"  # slot ran into the cache's max_seq
    STOPPED = "stopped"          # scheduler.stop() aborted it


class RejectReason(enum.Enum):
    QUEUE_FULL = "queue_full"
    PROMPT_TOO_LONG = "prompt_too_long"      # exceeds largest bucket
    EXCEEDS_KV_CAPACITY = "exceeds_kv_capacity"  # prompt+gen > max_seq
    STOPPED = "stopped"          # submitted after scheduler.stop()
    #: Load shed under KV pressure: the request was only admittable
    #: through a cached prompt prefix (suffix-only prefill), and that
    #: prefix was evicted — not just spilled — before admission.  The
    #: truthful degradation reason: with a `SpillPool` the prefix
    #: would have been restored and the request served.
    KV_PRESSURE = "kv_pressure_shed"


@dataclasses.dataclass
class Request:
    prompt: Sequence[int]
    max_new_tokens: int
    eos_token_ids: Tuple[int, ...] = ()
    #: Per-request RNG seed (folded into the slot's PRNG key) so a
    #: request samples the same tokens whichever slot or batch
    #: composition it lands in.
    seed: int = 0
    #: Scheduler-clock time the request becomes eligible for
    #: admission; None = eligible at submit time.
    arrival_time: Optional[float] = None
    #: Streaming hook, called as ``on_token(request, token)`` from the
    #: scheduler loop right after each token is decoded to host.
    on_token: Optional[Callable[["Request", int], None]] = None
    #: Cost-attribution / QoS label (`observability.costs`): which
    #: tenant this request is billed to.  The default keeps every
    #: pre-tenant call site byte-identical (cost accounting only arms
    #: when a non-default tenant or an SLO policy shows up).
    tenant: str = "default"
    request_id: int = dataclasses.field(
        default_factory=lambda: next(_next_id))

    # -- lifecycle (scheduler-owned) -----------------------------------
    state: RequestState = RequestState.QUEUED
    slot: Optional[int] = None
    generated: List[int] = dataclasses.field(default_factory=list)
    finish_reason: Optional[FinishReason] = None
    reject_reason: Optional[RejectReason] = None
    #: Prefill length bucket the prompt was padded to at admission.
    bucket: Optional[int] = None
    #: Preemption state (paged engine only): when the page pool runs
    #: dry mid-stream the scheduler may evict this request and requeue
    #: it.  ``resume_tokens`` = prompt + tokens generated so far (the
    #: re-prefill recomputes their KV bit-identically), ``resume_key``
    #: = the slot's PRNG key at eviction, so the resumed stream
    #: continues the exact same sample chain.
    resume_tokens: Optional[List[int]] = None
    resume_key: Optional[object] = None
    preemptions: int = 0
    #: Speculative-decoding outcome (``SchedulerConfig.spec_k``):
    #: draft tokens proposed for / accepted by this request's verify
    #: rounds.  ``spec_accepted / spec_proposed`` is the per-request
    #: accept rate the bench rows report; both stay 0 on the
    #: non-speculative path.
    spec_proposed: int = 0
    spec_accepted: int = 0
    #: Request-lineage join key (`observability.lineage`): the id
    #: every hop this request crosses is recorded under.  The cluster
    #: sets it to the `ClusterRequest.record_id` so one user request's
    #: lineage spans every replica attempt (and joins DecisionEvents /
    #: FaultEvents); a standalone scheduler derives ``eng-<request_id>``.
    lineage_id: Optional[object] = None
    #: Disaggregated-prefill hook (`serving.cluster`): a prefilled-KV
    #: shipment (`cluster.transport.KVShipment`-shaped: ``prompt_len``,
    #: ``bucket``, ``to_row_cache()``) a dedicated prefill worker
    #: produced for this prompt.  When set, admission inserts the
    #: shipped row cache instead of running prefill locally — the
    #: artifact is identical to a local prefill's, so tokens are
    #: unchanged.  Cleared at admission.
    shipped_kv: Optional[object] = None
    #: A model that generates by blocks (`models.sdar_moe`): beside
    #: prompt + tokens delivered the request has a BLOCK IN FLIGHT —
    #: ``block_start`` its first position, ``block_masked`` how many of
    #: its positions are still masked — and, where ``block_pending``,
    #: the block before it FINISHED BUT NOT COMMITTED (the slot's write
    #: cursor then stands at that block, else at the one in flight):
    #: its commit rides on the request's next pass.  All three AS THE
    #: LAST DISPATCH LEAVES THEM: the schedule is static, so the host
    #: knows what a row's next pass carries without reading a token
    #: (`ContinuousBatchingScheduler._dispatch_block`).  Both blocks'
    #: K/V lies in pages at and past the cursor that nobody else may
    #: read; a preemption drops the block in flight and the resume
    #: prefills the pending one with the tokens delivered before it
    #: and redoes the other.  None: one token a step.
    block_start: Optional[int] = None
    block_masked: int = 0
    block_pending: bool = False

    # -- SLO timestamps (scheduler clock, seconds) ---------------------
    t_arrival: Optional[float] = None
    t_admitted: Optional[float] = None
    t_first_token: Optional[float] = None
    t_last_token: Optional[float] = None
    t_finish: Optional[float] = None

    def __post_init__(self):
        self.prompt = list(int(t) for t in self.prompt)
        if not self.prompt:
            raise ValueError("empty prompt")
        if self.max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {self.max_new_tokens}")
        self.eos_token_ids = tuple(int(t) for t in self.eos_token_ids)

    # -- derived SLO metrics (None until the event happened) -----------

    @property
    def prompt_len(self) -> int:
        return len(self.prompt)

    @property
    def queue_wait(self) -> Optional[float]:
        if self.t_admitted is None or self.t_arrival is None:
            return None
        return self.t_admitted - self.t_arrival

    @property
    def ttft(self) -> Optional[float]:
        """Time to first token, measured from arrival (includes queue
        wait — the user-visible number)."""
        if self.t_first_token is None or self.t_arrival is None:
            return None
        return self.t_first_token - self.t_arrival

    @property
    def latency(self) -> Optional[float]:
        if self.t_finish is None or self.t_arrival is None:
            return None
        return self.t_finish - self.t_arrival

    @property
    def done(self) -> bool:
        return self.state in (RequestState.FINISHED,
                              RequestState.REJECTED)

    def to_dict(self) -> dict:
        """JSON-friendly summary (flight-recorder / bench reporting).
        ``tenant`` rides along only when set to something non-default,
        so untenanted summaries stay byte-identical."""
        out = {
            "request_id": self.request_id,
            "state": self.state.value,
            "prompt_len": self.prompt_len,
            "max_new_tokens": self.max_new_tokens,
            "generated": len(self.generated),
            "slot": self.slot,
            "bucket": self.bucket,
            "finish_reason": (self.finish_reason.value
                              if self.finish_reason else None),
            "reject_reason": (self.reject_reason.value
                              if self.reject_reason else None),
            "queue_wait_s": self.queue_wait,
            "ttft_s": self.ttft,
            "latency_s": self.latency,
            "preemptions": self.preemptions,
            "spec_proposed": self.spec_proposed,
            "spec_accepted": self.spec_accepted,
        }
        if self.tenant != "default":
            out["tenant"] = self.tenant
        return out
