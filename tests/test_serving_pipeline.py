"""The pipelined decode step: `step()` N dispatches step t+1 before it
reads step t's tokens.  CPU-only, deterministic, tier-1.

Two halves.

**No program and no kind of argument is first met after set-up.**  The
benchmark's OWN `cellbench.run.warm_up` runs against a `System`-shaped
object (the benchmark's adapters at test size, for both model classes,
and a toy model), and then — under a `jax.monitoring` listener like
`cellbench.run.CompileCounters`, and comparing `_cache_size()` of every
jitted program of the step path before and after — the scheduler is
driven through the transitions `qwen3-8b-1c.longprompt-steady` has and
the benchmark's CPU rehearsal lacks: it runs dry and restarts, requests
are admitted into an empty batch with and without a step in flight, one
row retires beside one that goes on, two retire in one step, a lone row
crosses page boundaries, every bucket of the mix comes after an idle
period.  Nothing may compile and no program may gain a cache entry.

**What the mechanism means**: tokens are delivered one call after
their dispatch and `has_work()` says so; the streams are the serial
goldens' whatever happens to a step in flight (EOS seen one step late,
a preemption, an admission beside a retirement); the counters, the
`inflight=` attribute and the span tree say what happened.
"""

import importlib
import json
import os
import sys
import time

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from cellbench import run as cellrun                      # noqa: E402
from triton_distributed_tpu.serving import (             # noqa: E402
    ContinuousBatchingScheduler,
    FinishReason,
    Request,
    SchedulerConfig,
    ToyConfig,
    ToyModel,
)
from triton_distributed_tpu.serving.speculative import Drafter  # noqa: E402

COMPILED = "/jax/core/compile/backend_compile_duration"


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

class Clock:
    def __init__(self):
        self.t = 0.0

    def now(self):
        return self.t

    def advance(self, dt):
        self.t += dt


@pytest.fixture(scope="module")
def toy():
    model = ToyModel(ToyConfig(vocab_size=61, hidden=16, max_seq_len=64))
    return model, model.init_params(jax.random.key(0))


def make_sched(model, params, layout="paged", **kw):
    kw.setdefault("num_slots", 3)
    kw.setdefault("prefill_buckets", (8, 16, 32, 64))
    kw.setdefault("page_size", 8)
    ck = Clock()
    return ContinuousBatchingScheduler(
        model, params, SchedulerConfig(kv_layout=layout, **kw),
        clock=ck.now, clock_advance=ck.advance)


def rand_prompts(n, vocab=61, seed=0, lo=3, hi=20):
    rng = np.random.default_rng(seed)
    return [list(map(int, rng.integers(1, vocab, rng.integers(lo, hi))))
            for _ in range(n)]


def golden(model, params, prompt, n, seed=0, **kw):
    """The stream of one request served alone by a speculating
    scheduler whose drafter never proposes: the SERIAL step (dispatch,
    read, commit in one call) of the same programs."""
    sched = make_sched(model, params, num_slots=1, spec_k=2,
                       spec_drafter=lambda s: _NoDrafts(), **kw)
    req = Request(prompt=prompt, max_new_tokens=n, seed=seed)
    sched.run([req])
    assert sched._flight is None
    return req.generated


class _NoDrafts(Drafter):
    """A drafter that never proposes: every dispatch of its scheduler
    is the plain masked step, read before the next."""
    name = "none"

    def _propose(self, req, k):
        return []


@pytest.fixture
def metrics():
    from triton_distributed_tpu.observability import get_registry
    reg = get_registry()
    reg.clear()
    yield reg
    reg.clear()


@pytest.fixture
def tracer():
    from triton_distributed_tpu.observability import get_tracer
    tr = get_tracer()
    tr.clear()
    yield tr
    tr.clear()


def counter(reg, name):
    return sum(v for k, v in reg.snapshot()["counters"].items()
               if k.split("{")[0] == name)


# ---------------------------------------------------------------------------
# 1. nothing is first met after set-up
# ---------------------------------------------------------------------------

class ToySystem:
    """What `cellbench.run.warm_up` and `settle` ask of a system, over
    the toy model: the adapters' surface, none of their weights."""

    def __init__(self, prefill_chunk=0):
        self.model = ToyModel(ToyConfig(vocab_size=61, hidden=16,
                                        max_seq_len=128,
                                        prefill_chunk=prefill_chunk))
        self.params = self.model.init_params(jax.random.key(0))
        self.config = {"vocab_size": 61}
        self.num_slots, self.max_seq = 8, 128
        self.sched = ContinuousBatchingScheduler(
            self.model, self.params,
            SchedulerConfig(num_slots=8, max_seq=128, kv_layout="paged",
                            page_size=8, num_pages=96, max_queue=64),
            clock=time.monotonic)
        self.buckets = self.sched.buckets
        self.page_size = self.sched.slots.page_size

    def submit(self, prompt, max_new, due, on_token):
        req = Request(prompt, max_new, eos_token_ids=(), seed=0,
                      arrival_time=due, on_token=on_token)
        if self.sched.submit(req):
            return req, None
        return None, req.reject_reason.value

    def step(self):
        return self.sched.step()

    def has_work(self):
        return self.sched.has_work()


def _qwen_system(devices, chips=1):
    from cellbench.adapters import qwen3
    # the cell's configuration at the rehearsal's sizes, as
    # `cellbench/run.py --rehearse` overlays them
    cfg = dict(
        cellrun.load_json(ROOT, "cellbench", "configs", "qwen3-8b-1c.json"),
        **cellrun.load_json(ROOT, "cellbench", "rehearsal", "config.json"))
    return qwen3.System(cfg, 11, devices[:chips])


def _glm_system(devices):
    from cellbench.adapters import glm4_moe_lite
    from tests.test_glm4_moe_lite import TINY
    serving = dict(TINY["serving"], num_slots=8,
                   kv_budget_bytes_per_chip=8 * 128 * 3 * 256 * 2)
    return glm4_moe_lite.System(dict(TINY, serving=serving), 11,
                                devices[:1])


def _solar_system(devices):
    from cellbench.adapters import solar_open2
    from tests.test_solar_open2 import PAGE, STATE, TINY
    serving = dict(TINY["serving"], num_slots=8,
                   kv_budget_bytes_per_chip=8 * (STATE + 8 * PAGE))
    return solar_open2.System(dict(TINY, serving=serving), 11,
                              devices[:1])


def _sdar_system(devices):
    from cellbench.adapters import sdar_moe
    from tests.test_sdar_moe import TINY
    serving = dict(TINY["serving"], num_slots=8,
                   kv_budget_bytes_per_chip=8 * 128 * 2 * 2 * 16 * 2)
    return sdar_moe.System(dict(TINY, serving=serving), 11, devices[:1])


def _nemotron_system(devices):
    from cellbench.adapters import nemotron_h
    from tests.test_nemotron_h import PAGE, STATE, TINY
    serving = dict(TINY["serving"], num_slots=8,
                   kv_budget_bytes_per_chip=8 * (STATE + 8 * PAGE))
    return nemotron_h.System(dict(TINY, serving=serving), 11,
                             devices[:1])


#: Slots for `warm_up` to admit both requests of every bucket in one
#: call (1 + 2 x 3 buckets here), as every cell but the two of seven
#: buckets on eight slots has: there an insert of the fourth bucket
#: first meets a pool another insert returned in the window, on the
#: parent as on this scheduler (PERF.md section 7).
SYSTEMS = {"toy": lambda devices: ToySystem(),
           "qwen3": _qwen_system, "glm4_moe_lite": _glm_system,
           "solar_open2": _solar_system, "sdar_moe": _sdar_system,
           "nemotron_h": _nemotron_system,
           "qwen3-tp4": lambda devices: _qwen_system(devices, 4)}
#: six minutes of interpreted ring kernels: by hand (`-m slow`)
FAMILIES = [pytest.param(f, marks=pytest.mark.slow) if f == "qwen3-tp4"
            else f for f in sorted(SYSTEMS)]


class Plan:
    """What `warm_up` reads of a traffic plan."""

    def __init__(self, prompt_range, output_max):
        self.prompt_range, self.output_max = prompt_range, output_max


class Compiled:
    """`cellbench.run.CompileCounters`' listener: every backend
    compilation while `watch` is set, with what JAX says of it."""

    def __init__(self):
        self.watch, self.seen = False, []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **kw):
        if self.watch and event == COMPILED:
            self.seen.append({k: str(v) for k, v in kw.items()})


@pytest.fixture(scope="module")
def compiled():
    return Compiled()


def programs(sched):
    """Every jitted program of the step path, by name ("reset": the
    zeroing of a released slot's recurrent state, run only for a model
    that has one)."""
    out = {"merge": sched._merge, "step": sched._step,
           "keep": sched._keep, "prefill": sched._prefill,
           "insert": sched.slots._insert}
    if sched._block > 1:
        # a block pass takes the rows the host knows itself: the block
        # state it returned stays on the device, nothing is merged
        del out["merge"]
    if getattr(sched.slots, "_reset", None) is not None:
        out["reset"] = sched.slots._reset
    if sched._prefill_suffix is not None:
        out["prefill_suffix"] = sched._prefill_suffix
    if sched._chunk:
        out["rows"] = sched.slots._put_rows
    return out


def cache_sizes(sched):
    return {name: fn._cache_size() for name, fn in programs(sched).items()}


@pytest.mark.parametrize("family", FAMILIES)
def test_no_program_and_no_kind_of_argument_is_first_met_after_warm_up(
        family, devices, compiled):
    system = SYSTEMS[family](devices)
    sched = system.sched
    ps = system.page_size
    buckets = [b for b in system.buckets if b <= 64]
    lo, hi = 3, buckets[-1]
    warmed = cellrun.warm_up(system, Plan((lo, hi), 3 * ps), seed=5)
    assert warmed["buckets"] == buckets and not system.has_work()
    before = cache_sizes(sched)
    assert before.get("merge", 1) >= 1 and before["step"] >= 1
    rng = np.random.default_rng(17)
    vocab = system.config["vocab_size"]
    served = []

    def send(plen, new):
        handle, why = system.submit(
            rng.integers(0, vocab, plen).tolist(), new, 0.0, None)
        assert handle is not None, why
        served.append((handle, new))
        return handle

    def run_dry():
        while system.has_work():
            system.step()
        assert sched._flight is None

    compiled.watch, compiled.seen = True, []
    try:
        # run dry and restart, several times: into an empty batch with
        # no step in flight, a lone row crossing page boundaries
        for n in (2 * ps + 3, 1, 2, ps + 1):
            send(lo + 2, n)
            run_dry()
        # every bucket of the mix after an idle period, alone and two
        # at once (the second insert takes the pool from an insert)
        for b in buckets:
            send(min(b, hi), 3)
            run_dry()
            send(min(b, hi), 2)
            send(max(b // 2 + 1, lo), 4)
            run_dry()
        # admitted with a step in flight; one row retires while the
        # other goes on; then the survivor alone; then a newcomer into
        # the freed slot beside it
        a = send(9, 2 * ps + 4)
        system.step()
        system.step()
        assert sched._flight is not None
        b = send(17, 3)
        system.step()
        assert sched._flight is not None and len(sched._by_slot) == 2
        while b.finish_reason is None:
            system.step()
        assert a.finish_reason is None and sched._flight is not None
        system.step()
        c = send(5, 2)
        while c.finish_reason is None:
            system.step()
        assert a.finish_reason is None
        run_dry()
        # two retire in one step beside one that goes on, then three
        # admitted in one call with a step in flight
        long = send(12, 3 * ps)
        system.step()
        x, y = send(7, 4), send(20, 4)
        while x.finish_reason is None:
            system.step()
        if sched._paced:
            # a model that prefills in chunks, or one with a recurrent
            # state: one enqueue a decode dispatch, so y was admitted
            # a call after x
            assert y.t_admitted > x.t_admitted
            if sched._block > 1:
                # (a block pass yields 1..4 tokens a row: y's whole
                # block may be out before x's tail and block are)
                while y.finish_reason is None:
                    system.step()
            else:
                assert y.finish_reason is None
                system.step()
        assert y.finish_reason is not None and long.finish_reason is None
        send(6, 2), send(6, 5), send(30, 1)
        run_dry()
        # the pipeline empties with rows still queued behind an arrival
        # in the future: idle, then admitted into an empty batch
        for i in range(3):
            send(10 + i, 2)
            system.step()
        run_dry()
    finally:
        compiled.watch = False
    assert compiled.seen == [], json.dumps(compiled.seen, indent=1)
    assert cache_sizes(sched) == before
    for handle, new in served:
        assert handle.finish_reason == FinishReason.LENGTH
        assert len(handle.generated) == new


#: The chunk of the models that prefill a long prompt in chunks, at
#: test size: the warm-up's buckets 16 / 32 / 64 are then a whole
#: prefill, two chunks, and four with the last one padded — as 1024 /
#: 2048 / 4000 tokens are on `glm-4.7-flash-1c.agent-closed` (and 512 /
#: 1024 / 2000 on `nemotron-3-super-120b-1c.chat-closed`, whose chunks
#: carry a recurrent state from one to the next).
CHUNK = 16


def _chunked_system(family, devices, monkeypatch):
    if family == "toy":
        return ToySystem(prefill_chunk=CHUNK)
    monkeypatch.setattr(importlib.import_module(
        f"triton_distributed_tpu.models.{family}"), "PREFILL_CHUNK", CHUNK)
    return SYSTEMS[family](devices)


@pytest.mark.parametrize("family", ["toy", "glm4_moe_lite"])
def test_no_kind_of_chunk_argument_is_first_met_after_warm_up(
        family, devices, compiled, monkeypatch):
    chunk_arguments_are_met_in_warm_up(family, devices, compiled,
                                       monkeypatch)


def chunk_arguments_are_met_in_warm_up(family, devices, compiled,
                                       monkeypatch):
    """(`nemotron_h`'s case stands in `tests/test_nemotron_h.py`: this
    file is the last a worker takes up, and what it holds is what the
    whole run waits for.)
    The benchmark's own `warm_up`, as it stands, on a model that
    prefills in chunks; then, under the compile listener, a window's
    chunked admissions: a chunk behind a step in flight, behind an
    insert (nothing running: the chunks of one prompt in one call),
    behind a retirement's reset and a page-table flush; two and three
    long prompts due in one call, beside short ones; every number of
    chunks, the last one full and padded.  Nothing compiles and no
    program gains a cache entry."""
    system = _chunked_system(family, devices, monkeypatch)
    sched = system.sched
    assert sched._chunk == CHUNK
    ps = system.page_size
    buckets = [b for b in system.buckets if b <= 64]
    lo, hi = 3, 62
    chunks = []
    suffix = sched._prefill_suffix

    class Counting:
        """The jitted chunk program, its enqueues counted."""
        _cache_size = suffix._cache_size

        def __call__(self, p, ids, start, *a):
            chunks.append((int(start), ids.shape[1]))
            return suffix(p, ids, start, *a)
    sched._prefill_suffix = Counting()
    warmed = cellrun.warm_up(system, Plan((lo, hi), 3 * ps), seed=5)
    assert warmed["buckets"] == buckets and not system.has_work()
    # the warm-up met: a first chunk, middle ones, a padded last one
    assert {at for at, _ in chunks} == {0, 16, 32, 48}
    assert {n for _, n in chunks} == {CHUNK} and len(chunks) == 2 * (2 + 4)
    before = cache_sizes(sched)
    assert before["prefill_suffix"] >= 1
    rng = np.random.default_rng(23)
    vocab = system.config["vocab_size"]
    served = []

    def send(plen, new):
        handle, why = system.submit(
            rng.integers(0, vocab, plen).tolist(), new, 0.0, None)
        assert handle is not None, why
        served.append((handle, new))
        return handle

    def run_dry():
        while system.has_work():
            system.step()
        assert sched._flight is None and sched._underway is None

    compiled.watch, compiled.seen = True, []
    del chunks[:]
    try:
        # into an idle server: every chunk of the prompt in one call,
        # each behind the insert of the one before
        for plen in (62, 33, 48, 17):
            send(plen, ps + 2)
            assert system.step()["admitted"] == 1
            run_dry()
        assert len(chunks) == 4 + 3 + 3 + 2
        # behind a step in flight, a chunk a call, a row running all
        # the while; a short prompt's whole prefill takes its turn
        runner = send(9, 6 * ps)
        system.step()
        system.step()
        a, b, c = send(60, 3), send(12, 2), send(40, 3)
        mid = 0
        while c.finish_reason is None:
            assert sched._flight is not None
            system.step()
            mid += sched._underway is not None
        assert mid >= 5 and runner.finish_reason is None
        assert a.finish_reason == b.finish_reason == FinishReason.LENGTH
        # a chunk in the call whose early read retired a row (a slot's
        # reset in front of it), then the next prompt into that slot
        d = send(35, 2)
        while d.t_admitted is None:
            system.step()
        e = send(50, 2)
        while e.finish_reason is None:
            system.step()
        run_dry()
        # two long prompts and a short one due at once, nothing
        # running: the first goes in whole, the others a chunk a step
        send(47, 4), send(64 - 2 * ps, 2 * ps), send(5, 3)
        run_dry()
        # the pipeline empties in mid-prefill: the rows before it
        # retire, its last chunks follow in one call
        send(6, 2)
        system.step()
        send(62, 2)
        run_dry()
    finally:
        compiled.watch = False
    assert compiled.seen == [], json.dumps(compiled.seen, indent=1)
    assert cache_sizes(sched) == before
    for handle, new in served:
        assert handle.finish_reason == FinishReason.LENGTH
        assert len(handle.generated) == new


def test_the_first_dispatch_of_a_process_is_the_only_one_with_zeros(toy):
    """From the second dispatch on the previous tokens are always what
    the last dispatch returned — also across an idle period."""
    model, params = toy
    sched = make_sched(model, params)
    seen, returned = [], [sched._prev]
    merge, step = sched._merge, sched._step

    def merging(prev, host, fresh):
        seen.append((prev, host, fresh))
        return merge(prev, host, fresh)

    def stepping(*args):
        out = step(*args)
        returned.append(out[0])
        return out
    sched._merge, sched._step = merging, stepping
    sched.run([Request(prompt=[1, 2, 3], max_new_tokens=3)])
    assert not sched.has_work()
    sched.run([Request(prompt=[4, 5, 6], max_new_tokens=2)])
    assert len(seen) == 5
    assert all(prev is was for (prev, _, _), was in zip(seen, returned))
    # host tokens and masks: fresh numpy arrays of one shape and dtype
    for _, host, fresh in seen:
        assert type(host) is np.ndarray and host.dtype == np.int32
        assert type(fresh) is np.ndarray and fresh.dtype == np.bool_
        assert host.shape == fresh.shape == (3,)
    assert len({id(h) for _, h, _ in seen}) == len(seen)
    # the host's word counts where a row was admitted, nowhere else
    assert [int(f.sum()) for _, _, f in seen] == [1, 0, 0, 1, 0]


# ---------------------------------------------------------------------------
# 2. what the mechanism means
# ---------------------------------------------------------------------------

def test_has_work_until_the_last_token_is_delivered(toy):
    model, params = toy
    sched = make_sched(model, params)
    got = []
    req = Request(prompt=[5, 6, 7], max_new_tokens=2,
                  on_token=lambda r, t: got.append(t))
    sched.submit(req)
    assert sched.step() == {"admitted": 1, "active": 1, "retired": 0}
    assert got == [] and sched.has_work()           # step 1 in flight
    assert sched.step()["retired"] == 0
    assert len(got) == 1 and sched.has_work()       # step 2 in flight
    # the row ends by length: known before its last step is read, so
    # nothing is dispatched for it — and there is still work
    assert sched._flight is not None and sched._by_slot
    out = sched.step()
    assert out == {"admitted": 0, "active": 1, "retired": 1}
    assert got == req.generated and len(got) == 2
    assert not sched.has_work() and sched._flight is None
    assert req.finish_reason == FinishReason.LENGTH
    assert got == golden(model, params, [5, 6, 7], 2)


def test_a_row_that_ends_by_length_runs_no_wasted_step(toy, metrics):
    model, params = toy
    sched = make_sched(model, params)
    reqs = [Request(prompt=p, max_new_tokens=n)
            for p, n in zip(rand_prompts(3, seed=4), (1, 4, 7))]
    sched.run(reqs)
    assert counter(metrics, "serving_tokens_generated_total") == 12
    assert counter(metrics, "serving_decode_discarded_tokens_total") == 0
    # 7 steps for the longest; all but the first overlapped
    assert counter(metrics, "serving_decode_dispatch_total") == 7
    assert counter(metrics, "serving_decode_overlapped_total") == 6


@pytest.mark.parametrize("layout", ["paged", "slots"])
def test_eos_is_seen_one_step_late_and_its_overshoot_discarded(
        toy, metrics, layout):
    """The step after the EOS ran for the row: its token is discarded
    and counted, the stream is the golden's up to the EOS, and the
    neighbour's stream is untouched."""
    model, params = toy
    prompt, other = [11, 12, 13], [3, 1, 4, 1, 5]
    want = golden(model, params, prompt, 8)
    want_mate = golden(model, params, other, 8)
    eos = want[2]
    cut = want[:want.index(eos) + 1]
    metrics.clear()
    sched = make_sched(model, params, layout)
    req = Request(prompt=prompt, max_new_tokens=8, eos_token_ids=(eos,))
    mate = Request(prompt=other, max_new_tokens=8)
    sched.run([req, mate])
    assert req.finish_reason == FinishReason.EOS
    assert req.generated == cut
    assert mate.generated == want_mate
    assert counter(metrics, "serving_decode_discarded_tokens_total") == 1
    assert counter(metrics, "serving_tokens_generated_total") == (
        len(cut) + 8)


def test_eos_overshoot_writes_nothing_outside_the_rows_own_pages(toy):
    """The pool holds exactly the horizon's pages of the one request:
    the step that ran past its EOS asked for no page more (its write
    fell below the horizon), and a request admitted into the freed slot
    and pages right after reads what the golden reads."""
    model, params = toy
    prompt = [1 + i % 50 for i in range(13)]
    want = golden(model, params, prompt, 12)
    eos = want[3]                                   # position 16: a new page
    cut = want[:want.index(eos) + 1]
    sched = make_sched(model, params, num_slots=1, num_pages=3,
                       prefix_cache=False)
    req = Request(prompt=prompt, max_new_tokens=12, eos_token_ids=(eos,))
    nxt = Request(prompt=prompt[:9], max_new_tokens=10)
    sched.run([req, nxt])
    assert req.generated == cut
    assert nxt.generated == golden(model, params, prompt[:9], 10)
    assert sched.slots.used_pages == 0


def test_a_slot_reused_while_its_old_rows_step_is_unread(toy, metrics):
    """EOS frees the slot while the step that still ran for the old row
    is in flight; the next call admits a newcomer into that slot and
    dispatches it before that step is read: the old row's token is
    discarded by identity, not by slot."""
    model, params = toy
    prompt = [9, 8, 7]
    want = golden(model, params, prompt, 6)
    eos = want[1]
    sched = make_sched(model, params, num_slots=1)
    old = Request(prompt=prompt, max_new_tokens=6, eos_token_ids=(eos,))
    new = Request(prompt=[2, 4, 6, 8], max_new_tokens=4)
    sched.submit(old)
    sched.submit(new)
    while old.finish_reason is None:
        sched.step()
    assert sched._flight is not None and 0 in sched._flight.rows
    out = sched.step()
    assert out["admitted"] == 1 and new.slot == 0
    assert new.generated == []              # the old row's token: dropped
    sched.drain()
    assert old.generated == want[:want.index(eos) + 1]
    assert new.generated == golden(model, params, [2, 4, 6, 8], 4)
    assert counter(metrics, "serving_decode_discarded_tokens_total") == 1


@pytest.mark.parametrize("temperature", [0.0, 1.0])
def test_preemption_with_a_step_in_flight_resumes_exactly(
        toy, metrics, temperature):
    """The pool runs dry while a step is in flight: that step is read
    first (the victim's committed tokens and its slot's key are then
    exact), the newest request is preempted and later resumes its
    stream and its sample chain bit for bit."""
    model, params = toy
    kw = dict(temperature=temperature)
    prompts = rand_prompts(3, seed=9, lo=6, hi=8)
    want = [golden(model, params, p, 20, seed=40 + i, **kw)
            for i, p in enumerate(prompts)]
    sched = make_sched(model, params, num_pages=7, prefix_cache=False,
                       **kw)
    reqs = [Request(prompt=p, max_new_tokens=20, seed=40 + i)
            for i, p in enumerate(prompts)]
    inflight_at_preempt = []
    preempt = sched._preempt

    def spy(slot):
        inflight_at_preempt.append(sched._flight)
        preempt(slot)
    sched._preempt = spy
    sched.run(reqs)
    assert [r.generated for r in reqs] == want
    assert sum(r.preemptions for r in reqs) >= 1
    assert inflight_at_preempt and all(
        f is None for f in inflight_at_preempt)
    assert counter(metrics, "serving_preemptions_total") == len(
        inflight_at_preempt)
    assert counter(metrics, "serving_decode_discarded_tokens_total") == 0


def test_admission_and_retirement_in_one_call(toy):
    """One call admits a request (dispatching its first step) and reads
    the step that retires another; each stream is its golden."""
    model, params = toy
    sched = make_sched(model, params, num_slots=2)
    a = Request(prompt=[1, 2, 3, 4], max_new_tokens=2)
    b = Request(prompt=[5, 6, 7], max_new_tokens=5)
    sched.submit(a)
    sched.step()
    sched.step()
    sched.submit(b)
    out = sched.step()          # admits b, dispatches b alone, reads a's last
    assert out == {"admitted": 1, "active": 2, "retired": 1}
    assert a.finish_reason == FinishReason.LENGTH and b.generated == []
    sched.drain()
    assert a.generated == golden(model, params, [1, 2, 3, 4], 2)
    assert b.generated == golden(model, params, [5, 6, 7], 5)


def test_stop_drops_the_step_in_flight_unread(toy, metrics):
    """An abort delivers no token; what was streamed is a prefix of
    the golden, and the scheduler restarts clean."""
    model, params = toy
    sched = make_sched(model, params)
    got = []
    req = Request(prompt=[3, 3, 3], max_new_tokens=9,
                  on_token=lambda r, t: got.append(t))
    sched.submit(req)
    for _ in range(4):
        sched.step()
    assert sched._flight is not None and len(got) == 3
    sched.stop()
    assert sched._flight is None and not sched.has_work()
    assert req.finish_reason == FinishReason.STOPPED
    want = golden(model, params, [3, 3, 3], 9)
    assert got == req.generated == want[:3]
    assert counter(metrics, "serving_decode_discarded_tokens_total") == 1
    sched.restart()
    again = Request(prompt=[3, 3, 3], max_new_tokens=9)
    sched.run([again])
    assert again.generated == want


def test_pipelined_streams_equal_the_serial_schedulers(toy):
    """Many requests, staggered arrivals, sampled: request by request
    the pipelined scheduler serves what the serial one serves."""
    model, params = toy
    prompts = rand_prompts(9, seed=21)

    def reqs():
        return [Request(prompt=p, max_new_tokens=2 + i % 6, seed=70 + i,
                        arrival_time=0.01 * (i // 2))
                for i, p in enumerate(prompts)]

    outs = {}
    for serial in (False, True):
        kw = (dict(spec_k=2, spec_drafter=lambda s: _NoDrafts())
              if serial else {})
        sched = make_sched(model, params, temperature=0.8, **kw)
        done = sched.run(reqs())
        assert sched._flight is None
        outs[serial] = [r.generated for r in
                        sorted(done, key=lambda r: r.request_id)]
    assert outs[False] == outs[True]


def test_the_counters_and_inflight_say_what_was_overlapped(
        toy, metrics, tracer):
    model, params = toy
    sched = make_sched(model, params)
    sched.run([Request(prompt=[1, 2, 3], max_new_tokens=5)])
    sched.run([Request(prompt=[4, 5, 6], max_new_tokens=3)])
    assert counter(metrics, "serving_decode_dispatch_total") == 8
    # one dispatch per restart is made with nothing in flight
    assert counter(metrics, "serving_decode_overlapped_total") == 6
    flags = [s.attrs["inflight"] for s in tracer.finished()
             if s.name == "serving.dispatch"]
    assert flags == [0, 1, 1, 1, 1, 0, 1, 1]
    assert all(s.attrs == {"k": 1, "spec": False,
                           "inflight": s.attrs["inflight"],
                           "starved": s.attrs["starved"]}
               for s in tracer.finished() if s.name == "serving.dispatch")


def test_sync_is_a_direct_child_of_every_step_that_read_a_step(
        toy, tracer):
    """Also of a step whose page phase found the pool dry with a step
    in flight and had it read before preempting."""
    model, params = toy
    sched = make_sched(model, params, num_pages=7, prefix_cache=False)
    reqs = [Request(prompt=p, max_new_tokens=20)
            for p in rand_prompts(3, seed=9, lo=6, hi=8)]
    sched.run(reqs)
    assert sum(r.preemptions for r in reqs) >= 1
    spans = tracer.finished()
    steps = {s.id: s for s in spans if s.name == "serving.step"}
    syncs = [s for s in spans if s.name == "serving.sync"]
    commits = [s for s in spans if s.name == "serving.commit"]
    assert syncs and all(s.parent in steps for s in syncs)
    assert [c.parent for c in commits] == [s.parent for s in syncs]
    # every token was read under some step's sync
    assert sum(c.attrs["tokens"] for c in commits) == sum(
        len(r.generated) for r in reqs) == 60
    # a step that preempted read its flight BETWEEN two page phases
    preempting = [s for s in spans if s.name == "serving.pages"
                  and s.attrs.get("preempted")]
    assert preempting
    for p in preempting:
        kids = sorted((s for s in spans if s.parent == p.parent),
                      key=lambda s: s.t0)
        names = [k.name for k in kids]
        i = names.index("serving.pages")
        assert names[i:i + 4] == ["serving.pages", "serving.sync",
                                  "serving.commit", "serving.pages"]


def test_decode_step_ms_is_one_steps_time(toy, metrics):
    """Each step is timed once, from its dispatch — or from the
    landing of the step before it, if later — to its own read: the
    times add up to the clock's span, never to twice it.  The first
    read carried the prefill: it is the prefill's reading (here with
    no step measured before it: unobserved), no step's."""
    model, params = toy
    sched = make_sched(model, params)
    ticks = iter(range(10_000))
    sched.step_timer = lambda: float(next(ticks))
    sched.run([Request(prompt=[1, 2, 3], max_new_tokens=6)])
    h = metrics.snapshot()["histograms"]["serving_decode_step_ms"]
    assert h["count"] == 5
    assert counter(metrics, "serving_prefill_unobserved_total") == 1
    # the admission reads the timer twice (its first half), then two
    # readings a call (its start, a landing), and a step lands one
    # call after its dispatch.  From its own dispatch to its own read a
    # step would count 3 ticks — the landing of the step before it lies
    # between; counted from that landing it is 2, and the five add up
    # to the ten ticks after the first landing
    assert h["sum"] == pytest.approx(10e3)
    assert h["max"] == pytest.approx(2e3)


def test_a_speculating_scheduler_keeps_nothing_in_flight(toy, metrics):
    model, params = toy
    sched = make_sched(model, params, spec_k=2)
    req = Request(prompt=[1, 2, 3, 1, 2, 3, 1, 2], max_new_tokens=6)
    sched.submit(req)
    while sched.has_work():
        sched.step()
        assert sched._flight is None
    assert req.generated == golden(model, params, req.prompt, 6)
    assert counter(metrics, "serving_decode_overlapped_total") == 0


# ---------------------------------------------------------------------------
# 3. an admission joins the pipeline: the host waits for the step in
#    flight, never for the prefill
# ---------------------------------------------------------------------------

#: Where the read of the step in flight goes in an admitting call:
#: between the admission's halves, ahead of them, or as measured.
ORDERS = {"front_first": True, "read_first": False, "measured": None}


def pin_order(sched, order):
    if ORDERS[order] is not None:
        sched._front_fits = lambda: ORDERS[order]


def enqueues(sched, log):
    """Note every enqueue of a prefill and of an insert in ``log``, on
    the spans' clock."""
    prefill, insert = sched._prefill, sched.slots._insert

    def prefilling(*args):
        log.append((time.perf_counter(), "prefill"))
        return prefill(*args)

    def inserting(*args):
        log.append((time.perf_counter(), "insert"))
        return insert(*args)
    sched._prefill, sched.slots._insert = prefilling, inserting


def in_order(tracer, log, step):
    """What one `step()` did, in time order: its phase spans and the
    enqueues noted in ``log``."""
    (root,) = [s for s in tracer.finished()
               if s.name == "serving.step" and s.attrs["step"] == step]
    seen = [(s.t0, s.name) for s in tracer.finished()
            if s.parent == root.id and s.name != "serving.gauges"]
    seen += [e for e in log if root.t0 <= e[0] <= root.t0 + root.dur]
    return [name for _, name in sorted(seen)]


@pytest.mark.parametrize("order", ["front_first", "read_first"])
def test_an_admitting_call_reads_the_step_in_flight_early(
        toy, tracer, metrics, order):
    """prefill enqueue -> sync -> commit -> insert -> pages -> dispatch
    (or, where the first half would not fit: sync -> commit first), the
    step dispatched with nothing in flight; the call after it is a
    plain one: dispatch -> sync."""
    model, params = toy
    sched = make_sched(model, params)
    pin_order(sched, order)
    log = []
    enqueues(sched, log)
    got = []
    a = Request(prompt=[1, 2, 3, 4], max_new_tokens=9,
                on_token=lambda r, t: got.append(len(log)))
    sched.submit(a)
    sched.step()
    sched.step()
    assert sched._flight is not None and len(a.generated) == 1
    b = Request(prompt=[5, 6, 7], max_new_tokens=4)
    sched.submit(b)
    tracer.clear()
    del log[:]
    out = sched.step()                  # step 3: admits b
    assert out == {"admitted": 1, "active": 2, "retired": 0}
    halves = ["serving.admit", "prefill", "serving.sync",
              "serving.commit", "serving.admit", "insert"]
    if order == "read_first":
        halves = ["serving.sync", "serving.commit", "serving.admit",
                  "prefill", "insert"]
    assert in_order(tracer, log, 3) == halves + [
        "serving.pages", "serving.dispatch"]
    # a's token was delivered before the insert was enqueued
    assert len(a.generated) == 2 and len(log) == 2
    assert got[-1] == int(order == "front_first")
    assert b.generated == [] and sched._flight is not None
    (dispatch,) = [s for s in tracer.finished()
                   if s.name == "serving.dispatch"]
    assert dispatch.attrs["inflight"] == 0
    (front,) = [s for s in tracer.finished()
                if s.name == "serving.admit.prefill"]
    (one,) = [s for s in tracer.finished()
              if s.name == "serving.admit.request"]
    assert front.attrs == {
        "request_id": b.request_id, "bucket": 8, "queue_wait_ms": 0.0,
        # where the piece starts, and the prompt's tokens in it
        "start": 0, "tokens": b.prompt_len,
        "flight": "behind" if order == "front_first" else "read",
        "starved": front.attrs["starved"]}
    assert one.attrs["read_flight"] == 1
    # sync and commit stay the step's own children, between the halves
    syncs = [s for s in tracer.finished() if s.name == "serving.sync"]
    steps = [s.id for s in tracer.finished() if s.name == "serving.step"]
    assert [s.parent for s in syncs] == steps
    sched.step()                        # step 4: a plain call
    assert in_order(tracer, log, 4) == [
        "serving.pages", "serving.dispatch", "serving.sync",
        "serving.commit"]
    assert len(a.generated) == 3 and len(b.generated) == 1
    sched.drain()
    assert counter(metrics, "serving_admit_overlapped_total") == 2
    assert counter(metrics, "serving_prefills_total") == 2
    flights = {k: v for k, v in metrics.snapshot()["counters"].items()
               if k.startswith("serving_admit_overlapped_total")}
    assert flights == {
        'serving_admit_overlapped_total{flight="none"}': 1,
        'serving_admit_overlapped_total{flight="%s"}'
        % ("behind" if order == "front_first" else "read"): 1}
    assert a.generated == golden(model, params, [1, 2, 3, 4], 9)
    assert b.generated == golden(model, params, [5, 6, 7], 4)


# -- the device as one queue, the host as one clock ---------------------------

class Modelled:
    """A scheduler run against a MODEL of its machine: the device one
    queue that runs what is enqueued in order, for scripted times; the
    host one clock that every dispatch, read and commit moves on by a
    scripted cost.  The toy's arrays land at once, so the times are
    the model's and the ORDER of enqueues and reads is the
    scheduler's own — which it chooses by this clock (`step_timer`).
    `ops` is that order, `replay` runs any such order again."""

    def __init__(self, host, device):
        self.host, self.device = host, device
        self.now = self.free = 0.0
        self.ends, self.ops, self.commits = {}, [], []
        self.kept = []      # the steps' tokens: their ids stay theirs

    def call(self):
        self.ops.append(("call",))
        self.now += self.host["call"]

    def enqueue(self, kind, key=None):
        self.ops.append(("enqueue", kind, key))
        self.now += self.host[kind]
        self.free = max(self.free, self.now) + self.device[kind]
        self.ends[key] = self.free

    def read(self, key):
        self.ops.append(("read", key))
        self.now = max(self.now, self.ends[key])
        self.commits.append(self.now)
        self.now += self.host["commit"]

    @classmethod
    def replay(cls, ops, host, device):
        out = cls(host, device)
        for op in ops:
            getattr(out, op[0])(*op[1:])
        return out

    def drive(self, sched):
        """Put ``sched`` on this machine."""
        prefill, insert, step, read = (
            sched._prefill, sched.slots._insert, sched._step, sched._read)

        def prefilling(*args):
            self.enqueue("prefill")
            return prefill(*args)

        def inserting(*args):
            self.enqueue("insert")
            return insert(*args)

        def stepping(*args):
            out = step(*args)
            self.kept.append(out[0])
            self.enqueue("step", id(out[0]))
            return out

        def reading(flight, **kw):
            self.read(id(flight.toks))
            return read(flight, **kw)
        sched._prefill, sched.slots._insert = prefilling, inserting
        sched._step, sched._read = stepping, reading
        sched.step_timer = lambda: self.now

    def ready(self, arr) -> bool:
        """`scheduler._is_ready` on this machine: a step's tokens when
        that step has ended, anything else (the cache: the output of
        what was enqueued last) when the queue has run empty."""
        return self.now >= self.ends.get(id(arr), self.free)

    def long_intervals(self):
        """Commit-to-commit intervals over 1.25 x their median."""
        gaps = np.diff(self.commits)
        return int((gaps > 1.25 * np.median(gaps)).sum())


def admissions_under_load(sched, machine, n=5):
    """Two rows that run throughout and ``n`` short requests admitted
    one by one, each with a step in flight.  Returns them all."""
    reqs = [Request(prompt=[3, 1, 4, 1, 5], max_new_tokens=60),
            Request(prompt=[2, 7, 1, 8], max_new_tokens=60)]
    for r in reqs:
        sched.submit(r)
    for i in range(n * 9):
        if i % 9 == 4:
            reqs.append(Request(prompt=[9, 2, 6, 5, 3, 5 + i],
                                max_new_tokens=3))
            sched.submit(reqs[-1])
        machine.call()
        sched.step()
    assert len(sched._by_slot) == 2 and len(reqs) == n + 2
    return reqs


def misplaced(ops):
    """``ops`` with the read of every admitting call moved behind that
    call's dispatches: the admission's work and the next step's
    dispatch both in front of the read of the step in flight."""
    calls = []
    for op in ops:
        if op[0] == "call":
            calls.append([])
        calls[-1].append(op)
    for call in calls:
        if ("enqueue", "prefill", None) in call:
            call.sort(key=lambda op: op[0] == "read")
    return [op for call in calls for op in call]


#: Four chips' shape (PERF.md section 5, milliseconds): a 7.3 ms step
#: whose dispatch costs the host more than half of it; an admission
#: costs it 6 (3 to the prefill's enqueue, 3 for the insert).
TP4 = ({"call": 0.2, "step": 4.3, "prefill": 3.0, "insert": 3.0,
        "commit": 0.3},
       {"step": 7.3, "prefill": 30.0, "insert": 0.4})


@pytest.mark.parametrize("front_ms,flight", [(3.0, "behind"),
                                             (10.0, "read")])
def test_an_admission_lengthens_one_token_gap_not_two(
        toy, metrics, front_ms, flight):
    """Where the host's dispatch costs more than half a step, the
    admission's host work has to lie where the prefill hides it: ONE
    commit-to-commit interval per admission is long.  The scheduler
    places the read by what it measured — a first half that fits into
    what is left of the step in flight runs in front of the read, one
    that does not runs behind it — and either way the same order of
    enqueues and reads with the admission's work AND the next step's
    dispatch in front of the read (PR 37's tp4 regression: `itl_p95_ms`
    7.73 -> 10.58) shows two."""
    model, params = toy
    host = dict(TP4[0], prefill=front_ms)
    machine = Modelled(host, TP4[1])
    sched = make_sched(model, params, num_slots=4)
    machine.drive(sched)
    reqs = admissions_under_load(sched, machine)
    sched.drain()
    # the first admissions found nothing measured yet and read first
    placed = {k: v for k, v in metrics.snapshot()["counters"].items()
              if k.startswith("serving_admit_overlapped_total")}
    for r in reqs:
        assert r.generated == golden(model, params, r.prompt,
                                     r.max_new_tokens)
    assert placed['serving_admit_overlapped_total{flight="%s"}'
                  % flight] >= 4
    again = Modelled.replay(machine.ops, host, TP4[1])
    assert again.commits == machine.commits
    assert np.median(np.diff(machine.commits)) == pytest.approx(7.3)
    assert machine.long_intervals() == 5
    wrong = Modelled.replay(misplaced(machine.ops), host, TP4[1])
    assert len(wrong.commits) == len(machine.commits)
    assert wrong.long_intervals() == 10


def test_a_first_half_that_does_not_fit_in_front_of_the_read_costs_two(
        toy):
    """Why the read's place is measured and not fixed: with a first
    half of 10 ms against a 7.3 ms step, enqueuing the prefill in front
    of the read delivers the tokens of the step in flight late."""
    model, params = toy
    host = dict(TP4[0], prefill=10.0)
    machine = Modelled(host, TP4[1])
    sched = make_sched(model, params, num_slots=4)
    machine.drive(sched)
    pin_order(sched, "front_first")
    admissions_under_load(sched, machine)
    assert machine.long_intervals() == 10


# -- streams ------------------------------------------------------------------

@pytest.mark.parametrize("layout", ["paged", "slots"])
@pytest.mark.parametrize("order", sorted(ORDERS))
def test_streams_with_admissions_on_every_phase_equal_the_serial(
        toy, order, layout):
    """An admission into an idle server; one with a step in flight;
    a row retired by the early read and its slot refilled in the same
    call, beside a second admission of that call: token for token the
    serial scheduler's."""
    model, params = toy
    sched = make_sched(model, params, layout)
    pin_order(sched, order)
    prompts = rand_prompts(5, seed=33)
    new = (3, 12, 5, 4, 6)
    a, b, c, d, e = reqs = [
        Request(prompt=p, max_new_tokens=n, seed=90 + i)
        for i, (p, n) in enumerate(zip(prompts, new))]
    sched.submit(a)                     # into an idle server
    assert sched.step()["admitted"] == 1
    sched.submit(b)                     # with a's first step in flight
    assert sched.step() == {"admitted": 1, "active": 2, "retired": 0}
    sched.step()
    # a's last step is in flight; one slot is free, two are waiting:
    # c takes it, the early read retires a, and d takes a's slot
    assert len(a.generated) == 2 and sched._flight is not None
    sched.submit(c)
    sched.submit(d)
    out = sched.step()
    assert out == {"admitted": 2, "active": 4, "retired": 1}
    assert a.finish_reason == FinishReason.LENGTH and d.slot == 0
    assert sched._flight is not None and len(sched._flight.prefills) == 2
    sched.submit(e)                     # queued until a slot frees
    sched.drain()
    for r, p, n in zip(reqs, prompts, new):
        assert r.generated == golden(model, params, p, n, seed=r.seed)


def test_sampled_streams_with_admissions_mid_stream_equal_the_serial(
        toy):
    """The staggered, sampled mix of
    `test_pipelined_streams_equal_the_serial_schedulers`, with the
    read ahead of the halves and between them."""
    model, params = toy
    prompts = rand_prompts(9, seed=21)

    def serve(order=None, **kw):
        sched = make_sched(model, params, temperature=0.8, **kw)
        if order:
            pin_order(sched, order)
        done = sched.run([
            Request(prompt=p, max_new_tokens=2 + i % 6, seed=70 + i,
                    arrival_time=0.01 * (i // 2))
            for i, p in enumerate(prompts)])
        return [r.generated for r in
                sorted(done, key=lambda r: r.request_id)]

    serial = serve(spec_k=2, spec_drafter=lambda s: _NoDrafts())
    assert serve("front_first") == serial
    assert serve("read_first") == serial


# -- what the block fed stays fed ----------------------------------------------

def test_a_read_that_carried_a_prefill_is_the_prefills_observation(
        toy, metrics, monkeypatch):
    """`serving_decode_step_ms` gets nothing from it; where exactly one
    prefill stood in front, `serving_prefill_ms` and the bucket's
    baseline get the reading less the rolling step time; the rest are
    counted as unobserved.  No wait of its own anywhere."""
    from triton_distributed_tpu.serving import scheduler as module
    model, params = toy
    fed = []
    monkeypatch.setattr(module, "_observe_prefill",
                        lambda bucket, ms: fed.append((bucket, ms)))
    sched = make_sched(model, params, num_slots=4)
    ticks = iter(range(10_000))
    sched.step_timer = lambda: float(next(ticks))
    waited = []
    ready = jax.block_until_ready
    jax.block_until_ready = lambda x: waited.append(x) or ready(x)
    try:
        a = Request(prompt=[1, 2, 3], max_new_tokens=30)
        sched.submit(a)
        for _ in range(3):
            sched.step()    # its first read: no step measured yet
        assert counter(metrics, "serving_prefill_unobserved_total") == 1
        hists = metrics.snapshot()["histograms"]
        assert "serving_prefill_ms" not in hists
        assert hists["serving_decode_step_ms"]["count"] == 1
        b = Request(prompt=[4, 5, 6, 7], max_new_tokens=4)
        sched.submit(b)
        sched.step()        # admits b: ONE prefill before the dispatch
        assert sched._flight.prefills == [(8, b)]
        reads = metrics.snapshot()["histograms"][
            "serving_decode_step_ms"]["count"]
        sched.step()        # its read: the prefill's observation
        hists = metrics.snapshot()["histograms"]
        assert hists["serving_prefill_ms"]["count"] == 1
        assert hists["serving_prefill_ms"]["sum"] >= 0.0
        assert hists["serving_decode_step_ms"]["count"] == reads
        assert fed == [(8, hists["serving_prefill_ms"]["sum"])]
        sched.step()
        assert metrics.snapshot()["histograms"][
            "serving_decode_step_ms"]["count"] == reads + 1
        for p in ([8, 9, 10], [11, 12, 13, 14]):
            sched.submit(Request(prompt=p, max_new_tokens=2))
        sched.step()        # two prefills in front of one dispatch
        assert len(sched._flight.prefills) == 2
        sched.step()
        assert counter(metrics, "serving_prefill_unobserved_total") == 3
        assert metrics.snapshot()["histograms"][
            "serving_prefill_ms"]["count"] == 1 == len(fed)
        sched.stop()        # b and the two: nothing left to time them
        assert counter(metrics, "serving_prefills_total") == 4
    finally:
        jax.block_until_ready = ready
    assert waited == []


def test_stop_with_an_admission_enqueued_counts_its_prefill_unobserved(
        toy, metrics):
    model, params = toy
    sched = make_sched(model, params)
    a = Request(prompt=[1, 2, 3], max_new_tokens=9)
    sched.submit(a)
    for _ in range(3):
        sched.step()
    b = Request(prompt=[4, 5, 6, 7], max_new_tokens=4)
    sched.submit(b)
    sched.step()
    assert sched._flight.prefills and b.slot is not None
    sched.stop()
    assert sched._flight is None and sched._prefills == []
    assert counter(metrics, "serving_prefill_unobserved_total") == 2
    assert a.finish_reason == b.finish_reason == FinishReason.STOPPED
    sched.restart()
    again = Request(prompt=[4, 5, 6, 7], max_new_tokens=4)
    sched.run([again])
    assert again.generated == golden(model, params, [4, 5, 6, 7], 4)


# ---------------------------------------------------------------------------
# 4. every token gap put down to what made it: the read's record, the
#    starved enqueues, the counters
# ---------------------------------------------------------------------------

def syncs(tracer):
    return [s for s in tracer.finished() if s.name == "serving.sync"]


def ticking(sched):
    """A timer that moves one tick a reading (as in
    `test_decode_step_ms_is_one_steps_time`)."""
    ticks = iter(range(10_000))
    sched.step_timer = lambda: float(next(ticks))


@pytest.mark.parametrize("scenario", [
    "plain", "behind_one", "behind_two", "early_between", "early_ahead"])
def test_a_read_leaves_its_record_on_its_sync_span(
        toy, tracer, metrics, monkeypatch, scenario):
    """`interval_ms` to the timer's tick, the rows committed, the
    prefills in front and their buckets, whether the read was the early
    one of an admitting call and whether the tokens had landed."""
    from triton_distributed_tpu.serving import scheduler as module
    model, params = toy
    sched = make_sched(model, params, num_slots=4)
    landed = []
    monkeypatch.setattr(module, "_is_ready",
                        lambda arr: bool(landed and landed[-1]))
    a = Request(prompt=[1, 2, 3], max_new_tokens=30)
    sched.submit(a)
    for _ in range(3):
        sched.step()
    ticking(sched)
    sched.step()                 # a plain call: its read ends at tick 1
    tracer.clear()
    new = [Request(prompt=p, max_new_tokens=5)
           for p in ([4, 5, 6, 7], [8, 9, 10, 11, 12, 13, 14, 15, 16])]
    if scenario == "plain":
        landed.append(True)
        sched.step()             # ticks 2 (start), 3 (landed)
        (rec,) = syncs(tracer)
        assert rec.attrs == {
            "rows": 1, "first_tokens": 0, "prefills": 0,
            "prefill_tokens": 0, "early": 0, "landed": 1,
            "interval_ms": 2e3}
        return
    if scenario in ("behind_one", "behind_two"):
        n = 1 if scenario == "behind_one" else 2
        pin_order(sched, "front_first")
        for r in new[:n]:
            sched.submit(r)
        sched.step()             # admits: its early read is plain
        (early,) = syncs(tracer)
        assert early.attrs["early"] == 1 and early.attrs["prefills"] == 0
        tracer.clear()
        landed.append(False)
        sched.step()             # the read behind the prefill(s)
        (rec,) = syncs(tracer)
        assert rec.attrs["prefill_request_ids"] == [
            r.request_id for r in new[:n]]
        assert {k: rec.attrs[k] for k in (
            "rows", "first_tokens", "prefills", "prefill_tokens",
            "early", "landed")} == {
            "rows": 1 + n, "first_tokens": n, "prefills": n,
            "prefill_tokens": 8 * n + 8 * (n - 1), "early": 0,
            "landed": 0}
        # since the early read's landing: the admitting call's own
        # start, this call's start and landing — and the two readings
        # of a second admission's first half, made after that read
        assert rec.attrs["interval_ms"] == (3 + 2 * (n - 1)) * 1e3
        return
    order = ("front_first" if scenario == "early_between"
             else "read_first")
    pin_order(sched, order)
    sched.submit(new[0])
    landed.append(scenario == "early_between")
    sched.step()
    (rec,) = syncs(tracer)
    (front,) = [s for s in tracer.finished()
                if s.name == "serving.admit.prefill"]
    assert (rec.t0 > front.t0) == (scenario == "early_between")
    # between the halves the first half's two readings of the timer
    # lie in front of the landing; ahead of them nothing does
    assert rec.attrs == {
        "rows": 1, "first_tokens": 0, "prefills": 0,
        "prefill_tokens": 0, "early": 1,
        "landed": int(scenario == "early_between"),
        "interval_ms": 3e3 if scenario == "early_between" else 1e3}
    assert front.attrs["flight"] == (
        "behind" if scenario == "early_between" else "read")


@pytest.mark.parametrize("front_ms,flight", [(3.0, "behind"),
                                             (10.0, "read")])
def test_starved_is_1_exactly_where_the_device_had_finished(
        toy, tracer, metrics, monkeypatch, front_ms, flight):
    """On the modelled machine (four chips' shape): every enqueue of a
    prefill or a step says `starved` exactly where the device's queue
    had run empty at that moment — with the first half read first that
    is every admission's prefill, behind the flight none — the first
    enqueue of the idle server says `idle`, and the counters agree
    with the spans."""
    from triton_distributed_tpu.serving import scheduler as module
    model, params = toy
    host = dict(TP4[0], prefill=front_ms)
    machine = Modelled(host, TP4[1])
    sched = make_sched(model, params, num_slots=4)
    machine.drive(sched)
    monkeypatch.setattr(module, "_is_ready", machine.ready)
    truth = []
    enqueue = machine.enqueue

    def enqueuing(kind, key=None):
        if kind != "insert":
            truth.append((kind, int(machine.now >= machine.free)))
        enqueue(kind, key)
    machine.enqueue = enqueuing
    admissions_under_load(sched, machine)
    sched.drain()
    said = [("prefill" if s.name == "serving.admit.prefill" else "step",
             s.attrs["starved"], s.attrs.get("idle", 0))
            for s in tracer.finished()
            if s.name in ("serving.admit.prefill", "serving.dispatch")]
    assert [(p, st) for p, st, _ in said] == truth
    # the server was idle once: before its first request
    assert [i for _, _, i in said] == [1] + [0] * (len(said) - 1)
    hungry = [p for p, st, idle in said if st and not idle]
    # where the first half is read first the chip idles for every such
    # prefill; behind the flight it never does
    late = sum(p == "prefill" for p in hungry)
    assert late >= 4 if flight == "read" else late == 0
    counters = metrics.snapshot()["counters"]

    def total(name):
        return {k.split('"')[1]: v for k, v in counters.items()
                if k.startswith(name + "{")}
    assert total("serving_enqueues_total") == {
        p: sum(q == p for q, _, _ in said) for p in ("prefill", "step")}
    assert sum(total("serving_enqueue_starved_total").values()) == len(
        hungry)
    # the tokens of an early read that stood behind a first half too
    # long for the step had landed; those read first had not
    early = [s.attrs["landed"] for s in syncs(tracer)
             if s.attrs["early"]]
    assert len(early) == 5
    assert counter(metrics, "serving_read_late_total") == sum(
        s.attrs["landed"] for s in syncs(tracer))


@pytest.mark.parametrize("layout", ["paged", "slots"])
def test_the_read_counters_add_up(toy, tracer, metrics, layout):
    """`serving_reads_total` over its labels is the `serving.sync`
    spans; `serving_read_rows_total` is the tokens generated (one token
    a row a read)."""
    model, params = toy
    sched = make_sched(model, params, layout)
    reqs = [Request(prompt=p, max_new_tokens=3 + i % 5, seed=i,
                    arrival_time=0.01 * (i // 2))
            for i, p in enumerate(rand_prompts(9, seed=5))]
    sched.run(reqs)
    recs = syncs(tracer)
    assert counter(metrics, "serving_reads_total") == len(recs)
    assert counter(metrics, "serving_read_rows_total") == counter(
        metrics, "serving_tokens_generated_total") == sum(
        len(r.generated) for r in reqs)
    by = {k: v for k, v in metrics.snapshot()["counters"].items()
          if k.startswith("serving_reads_total")}
    for label, want in (("0", lambda n: n == 0), ("1", lambda n: n == 1),
                        ("2+", lambda n: n >= 2)):
        assert by.get('serving_reads_total{prefills="%s"}' % label,
                      0) == sum(want(s.attrs["prefills"]) for s in recs)
    assert sum(s.attrs["prefills"] for s in recs) == len(reqs)
    assert sum(s.attrs["first_tokens"] for s in recs) == len(reqs)
    # every read but the process's first closes an interval
    assert ["interval_ms" in s.attrs for s in recs] == [False] + [
        True] * (len(recs) - 1)


def test_with_observability_off_nothing_is_asked_and_streams_are_the_same(
        toy, tracer, metrics, monkeypatch):
    from triton_distributed_tpu.serving import scheduler as module
    model, params = toy
    asked = []
    monkeypatch.setattr(module, "_is_ready",
                        lambda arr: asked.append(arr) or True)

    def serve():
        sched = make_sched(model, params, temperature=0.8)
        done = sched.run([
            Request(prompt=p, max_new_tokens=2 + i % 6, seed=70 + i,
                    arrival_time=0.01 * (i // 2))
            for i, p in enumerate(rand_prompts(7, seed=21))])
        return [r.generated for r in
                sorted(done, key=lambda r: r.request_id)]

    on = serve()
    assert asked and syncs(tracer)
    del asked[:]
    tracer.clear()
    monkeypatch.setenv("TDT_OBSERVABILITY", "0")
    off = serve()
    assert asked == [] and tracer.finished() == []
    assert off == on


# ---------------------------------------------------------------------------
# 5. a read delivers every row's tokens, then retires the rows that ended
# ---------------------------------------------------------------------------

class CommitLog:
    """Every token handed to a request and every `slots.release` of one
    scheduler, through one list, call by call."""

    def __init__(self, sched, monkeypatch):
        self.sched, self.events = sched, []
        release = sched.slots.release

        def releasing(slot):
            self.events.append(("release", slot))
            release(slot)
        monkeypatch.setattr(sched.slots, "release", releasing)

    def on_token(self, req, token):
        self.events.append(("token", req.request_id))

    def step(self):
        """One call: (what it returned, its events, the requests it
        gave a slot)."""
        seen = len(self.events)
        waiting = list(self.sched._queue)
        out = self.sched.step()
        return (out, self.events[seen:],
                [r for r in waiting if r.slot is not None])


def assert_delivered_then_retired(events, running, ended):
    """``events`` of one call: every row of the read — ``running``, in
    the loop's order — has its tokens before the first release, and the
    releases are the ``ended`` rows' slots, in the loop's order."""
    tokens = [e for e in events if e[0] == "token"]
    releases = [e for e in events if e[0] == "release"]
    assert events == tokens + releases
    assert list(dict.fromkeys(rid for _, rid in tokens)) == [
        r.request_id for r in running]
    assert releases == [("release", slot) for slot in ended]


#: The answers of the three rows that run (admitted in this order: the
#: loop's) and how many of them end in one read.
ENDINGS = {
    "first_of_three": ((3, 9, 9), 1),
    "two_of_three": ((3, 3, 9), 2),
}
#: Layout, options, the calls before the one whose read ends the short
#: rows, and the calls after it until the freed slot 0 is handed on: the
#: SAME call where the read is an admitting call's early one, the next
#: where the scheduler is serial (it admits before it dispatches).
PATHS = {
    "paged": ("paged", {}, 3, 0),
    "slots": ("slots", {}, 3, 0),
    "spec_verify": ("paged", dict(spec_k=2), 2, 1),
}


@pytest.fixture(scope="module")
def served_alone(toy):
    """The five prompts of the cases below, each served alone by one
    serial scheduler."""
    model, params = toy
    prompts = rand_prompts(5, seed=3)
    sched = make_sched(model, params, num_slots=1, spec_k=2,
                       spec_drafter=lambda s: _NoDrafts())
    streams = []
    for p in prompts:
        req = Request(prompt=p, max_new_tokens=9)
        sched.run([req])
        streams.append(req.generated)
    return prompts, streams


@pytest.mark.parametrize("ending", sorted(ENDINGS))
@pytest.mark.parametrize("path", sorted(PATHS))
def test_every_row_has_its_token_before_any_row_is_retired(
        toy, served_alone, monkeypatch, path, ending):
    """Three rows run on four slots and two requests arrive for the
    call whose read ends the first row (the first two): the rows behind
    get their tokens before `slots.release` is first called, releases
    come in the loop's order, and the call's counts, `finish_reason`,
    `t_finish`, the freed slot's next owner and every stream are what
    the one-pass loop gave."""
    model, params = toy
    layout, kw, at, later = PATHS[path]
    news, n_end = ENDINGS[ending]
    prompts, alone = served_alone
    sched = make_sched(model, params, layout, num_slots=4, **kw)
    log = CommitLog(sched, monkeypatch)
    reqs = [Request(prompt=p, max_new_tokens=n, on_token=log.on_token)
            for p, n in zip(prompts, news + (5, 5))]
    for r in reqs[:3]:
        assert sched.submit(r)
    for _ in range(at):
        sched._clock_advance(1.0)
        out, events, _ = log.step()
        assert out["retired"] == 0 and "release" not in dict(events)
    for r in reqs[3:]:
        assert sched.submit(r)
    sched._clock_advance(1.0)
    out, events, admitted = log.step()
    # the early read of an admitting call: the fourth slot and the
    # first one freed are filled in this call; a serial call admits
    # first (its newcomer is in the read), reads last, and hands the
    # freed slot on a call later
    assert_delivered_then_retired(events, reqs[:4 if later else 3],
                                  range(n_end))
    assert out == {"admitted": 1 if later else 2,
                   "active": 4 if later else 5, "retired": n_end}
    assert admitted == (reqs[3:4] if later else reqs[3:])
    for r in reqs[:n_end]:
        assert r.finish_reason == FinishReason.LENGTH
        assert r.t_finish == sched.clock() == at + 1.0
    if later:
        _, _, admitted = log.step()
        assert admitted == reqs[4:]
    assert (reqs[3].slot, reqs[4].slot) == (3, 0)
    while sched.has_work():
        _, events, _ = log.step()
        tokens = [e for e in events if e[0] == "token"]
        assert events[:len(tokens)] == tokens
    assert [r.generated for r in reqs] == [
        s[:r.max_new_tokens] for r, s in zip(reqs, alone)]
