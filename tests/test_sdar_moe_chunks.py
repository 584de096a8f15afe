"""SDAR-MoE's long prompts by chunks, on the CPU at tiny sizes: the
chunk program (`SdarMoe.prefill_shard_suffix` — each chunk attends the
rows its predecessors left in the page pool under the block-causal
mask, `TPAttention.prefill_suffix`) against the whole prefill and the
float32 reference, and chunked, paced admissions through the pipelined
scheduler against the unchunked serial loop.  Configuration, weights,
helpers and tolerances are `tests/test_sdar_moe.py`'s; the tests stand
in a file of their own so that a tier-1 worker takes them up beside
that file, not behind it."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench import correctness
from cellbench.adapters import sdar_moe as adapter
from cellbench.references import sdar_moe as reference
from tests.test_sdar_moe import (
    DIMS, LOGIT_TOL, MASK, N, SEED, TINY, _close, _paged, _state_logits)
from triton_distributed_tpu.models import sdar_moe
from triton_distributed_tpu.serving import (
    ContinuousBatchingScheduler, Request, SchedulerConfig)
from triton_distributed_tpu.serving.engine_batched import pad_prompt
from triton_distributed_tpu.serving.pages import PagedKV


@pytest.fixture(scope="module")
def system(devices):
    """The benchmark's adapter at test size, with the cell's chunk."""
    return adapter.System(TINY, SEED, devices[:1])


# ---------------------------------------------------------------------------
# a prompt prefilled in chunks that attend the pool under the block mask
# ---------------------------------------------------------------------------

#: Tokens a chunk of the tests below (the cell's is `PREFILL_CHUNK`): a
#: multiple of the page (16) and of the block.
CHUNK = 16


@pytest.fixture(scope="module")
def programs(system):
    """The model's three programs, each jitted once for the cases
    below."""
    model = system.model
    return (jax.jit(model.make_prefill_fn()),
            jax.jit(model.make_prefill_suffix_fn()),
            jax.jit(model.make_paged_decode_fn(16)))


def _paged_in_chunks(system, suffix, prompt, poison=False):
    """The prompt prefilled by chunk calls of ``CHUNK`` tokens, each
    over the pages its predecessors filled, the last one's insert with
    the cursor at the end of the prompt's whole blocks.  ``poison``:
    the pool is NaN wherever no row of the prompt was put yet."""
    model, params = system.model, system.params
    slots = PagedKV(model, 1, max_seq=128, page_size=16,
                    prefix_cache=False)
    if poison:
        nan = lambda pools: [   # noqa: E731
            jnp.full_like(x, jnp.nan) for x in pools]
        slots.cache = dataclasses.replace(
            slots.cache, ks=nan(slots.cache.ks), vs=nan(slots.cache.vs))
    s = len(prompt)
    slot = slots.begin_prefill(s, [])
    row = model.create_cache(1, CHUNK)
    for at in range(0, s, CHUNK):
        ids, _ = pad_prompt(prompt[at:at + CHUNK], CHUNK)
        out = suffix(params, ids, jnp.int32(at), row,
                     (slots.cache.ks, slots.cache.vs),
                     slots.prefill_pages(slot))
        if at + CHUNK >= s:
            slots.insert_rows(slot, out, at, jnp.zeros((2,), jnp.uint32),
                              s // N * N)
        else:
            slots.insert_rows(slot, out, at)
    slots.finish_prefill(slot, prompt, s // N * N)
    return slots


def _pool_rows(slots, upto):
    """The slot's K and V rows of positions [0, upto), a layer each:
    (layers x 2, Hkv, upto, D) float32."""
    pages = np.asarray(slots._table[0][:-(-upto // 16)])
    return np.stack([
        np.asarray(pool[li][pages], np.float32).transpose(1, 0, 2, 3)
        .reshape(1, -1, 16)[:, :upto]
        for pool in (slots.cache.ks, slots.cache.vs) for li in range(2)])


#: Prompt lengths against a chunk of 16: exactly one chunk (the one
#: piece the scheduler would not cut), one chunk + 1 (a last piece that
#: holds the tail alone), one chunk + a block + a tail of 1, 2 and 3,
#: several chunks with a tail, and several chunks that end on a chunk's
#: edge.
LENGTHS = {"one_chunk": 16, "one_chunk_and_1": 17,
           "chunk_block_tail_1": 21, "chunk_block_tail_2": 22,
           "chunk_block_tail_3": 23, "four_chunks_tail_3": 55,
           "four_chunks_whole": 64}


@pytest.mark.parametrize("case", sorted(LENGTHS))
def test_a_prompt_prefilled_in_chunks_leaves_the_whole_prefills_rows(
        system, programs, case):
    """The pool rows of every prompt position — the whole blocks' and
    the tail's, which the last piece writes at and past the cursor —
    are the whole prefill's to bfloat16 rounding (the chunks' kernels
    cut the sequence elsewhere), over a pool that was NaN wherever the
    prompt's own rows were not put yet; the cursor stands at the end
    of the whole blocks; and the first block pass behind them — a dead
    front half, the tail revealed in the block in flight — gives the
    float32 reference's logits over the sequence as it stands."""
    _, suffix, decode = programs
    plen = LENGTHS[case]
    rng = np.random.default_rng(plen)
    prompt = rng.integers(0, 255, plen).tolist()
    whole = _paged(system, [prompt])
    slots = _paged_in_chunks(system, suffix, prompt, poison=True)
    cursor = plen // N * N
    assert int(slots.cache.offset[0]) == cursor == int(
        whole.cache.offset[0])
    got, want = _pool_rows(slots, plen), _pool_rows(whole, plen)
    assert np.isfinite(got).all()
    # (bfloat16 rows of magnitude ~1-4: a rounding step is 0.008-0.03)
    err = np.abs(got - want)
    assert err.max() < 0.07 and np.median(err) < 4e-3, (
        err.max(), np.median(err))
    tail = prompt[cursor:]
    fed = tail + [MASK] * (N - len(tail))
    assert slots.ensure(0, cursor + N)
    slots.flush()
    logits, _ = decode(system.params,
                       jnp.asarray([[MASK] * N + fed], jnp.int32),
                       slots.cache, jnp.ones((1,), bool),
                       jnp.zeros((1,), bool))
    ref = _state_logits(prompt[:cursor] + fed, cursor, N)
    ok, err = _close(logits[0], ref)
    assert ok, (case, err)
    low = _state_logits(prompt[:cursor] + fed, cursor, N, "fp8")
    assert np.abs(low - ref).max() > LOGIT_TOL


def test_a_chunk_that_misses_its_predecessors_rows_fails_the_reference(
        system, programs):
    """The control of the test above: the same chunks, the second one
    told that it starts the sequence (nothing below it is read) — its
    rows are another model's and the first block pass's logits lie
    past the tolerance."""
    _, suffix, decode = programs
    rng = np.random.default_rng(23)
    prompt = rng.integers(0, 255, 32).tolist()
    slots = _paged_in_chunks(system, suffix, prompt)
    blind = _paged_in_chunks(
        system, lambda p, ids, at, row, pools, pages: suffix(
            p, ids, at, row, pools, np.zeros_like(pages)), prompt)
    a, b = _pool_rows(slots, 32), _pool_rows(blind, 32)
    assert np.abs(a - b)[:, :, :CHUNK].max() < 1e-6      # the first chunk
    assert np.abs(a - b)[:, :, CHUNK:].max() > 0.1
    for kv in (slots, blind):
        assert kv.ensure(0, 32 + N)
        kv.flush()
    fed = jnp.asarray([[MASK] * (2 * N)], jnp.int32)
    on, off = jnp.ones((1,), bool), jnp.zeros((1,), bool)
    ref = _state_logits(prompt + [MASK] * N, 32, N)
    good, _ = decode(system.params, fed, slots.cache, on, off)
    bad, _ = decode(system.params, fed, blind.cache, on, off)
    assert _close(good[0], ref)[0]
    assert not _close(bad[0], ref)[0]


def test_the_chunk_program_names_the_prefills_kernels(system):
    """A device trace reads a chunk as a prefill: the program's name
    starts like the whole prefill's, its attention kernel is the
    rectangular grid's (a traced offset) under the block-causal mask,
    its grouped GEMMs the prefill's; no logits, so the head is not in
    it (and nothing reads the last layer's expert block: the compiler
    drops it, `tests_tpu/test_sdar_moe.py` times what is left)."""
    model = system.model
    params = jax.eval_shape(lambda: system.params)
    pool = jax.eval_shape(lambda: model.create_paged_cache(2, 9, 16, 8))
    row = jax.eval_shape(lambda: model.create_cache(1, CHUNK))
    lowered = jax.jit(model.make_prefill_suffix_fn()).lower(
        params, jnp.zeros((1, CHUNK), jnp.int32), jnp.int32(CHUNK), row,
        (pool.ks, pool.vs), jnp.zeros((8,), jnp.int32))
    text = lowered.as_text()
    assert text.splitlines()[0].startswith(
        "module @jit_prefill_shard_suffix ")
    jaxpr = str(jax.make_jaxpr(model.make_prefill_suffix_fn())(
        params, jnp.zeros((1, CHUNK), jnp.int32), jnp.int32(CHUNK), row,
        (pool.ks, pool.vs), jnp.zeros((8,), jnp.int32)))
    for name in ("flash_attention_fwd", "moe_prefill_gate_up",
                 "moe_prefill_down"):
        assert f"name={name}\n" in jaxpr or f"name={name} " in jaxpr, name
    assert "moe_decode" not in jaxpr and "flash_decode" not in jaxpr
    # no product with the head's (hidden, vocab): the whole prefill
    # has one
    head = lambda t: [line for line in t.splitlines()   # noqa: E731
                      if "stablehlo.dot_general" in line
                      and "x256xf32" in line]
    assert head(text) == []
    assert len(head(jax.jit(model.make_prefill_fn()).lower(
        params, jnp.zeros((1, CHUNK), jnp.int32), row).as_text())) == 1


# ---------------------------------------------------------------------------
# chunked, paced admissions through the scheduler
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def chunking(devices):
    """The adapter's system with the model's chunk at test size: the
    model reads `PREFILL_CHUNK` when it is built."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(sdar_moe, "PREFILL_CHUNK", CHUNK)
        built = adapter.System(TINY, SEED, devices[:1])
    return built


@pytest.fixture(scope="module")
def chunk_sched(chunking):
    """ONE scheduler over the chunking model for the tests below (its
    programs are traced once): four slots; every test leaves it
    drained."""
    sched = ContinuousBatchingScheduler(
        chunking.model, chunking.params, SchedulerConfig(
            num_slots=4, max_seq=128, kv_layout="paged"))
    assert sched._chunk == CHUNK and sched._paced and not sched._stateful
    return sched


class Enqueues:
    """Every enqueue of the scheduler's programs, in order: ("chunk",
    start, tokens), ("prefill", bucket), ("pass", rows running)."""

    def __init__(self, sched):
        self.log = []
        suffix, prefill, step = (sched._prefill_suffix, sched._prefill,
                                 sched._step)

        def chunk(p, ids, start, *a):
            self.log.append(("chunk", int(start), ids.shape[1]))
            return suffix(p, ids, start, *a)

        def whole(p, ids, row):
            self.log.append(("prefill", ids.shape[1]))
            return prefill(p, ids, row)

        def stepping(p, prev, cache, blk, fresh, active, n_reveal):
            self.log.append(("pass", int(np.sum(active))))
            return step(p, prev, cache, blk, fresh, active, n_reveal)
        sched._prefill_suffix, sched._prefill = chunk, whole
        sched._step = stepping

    def pieces(self):
        return [ev for ev in self.log if ev[0] != "pass"]


@pytest.fixture
def watched(chunk_sched):
    """(the scheduler, its enqueues): the programs wrapped for one test
    and put back after it."""
    kept = (chunk_sched._prefill_suffix, chunk_sched._prefill,
            chunk_sched._step)
    yield chunk_sched, Enqueues(chunk_sched)
    assert not chunk_sched.has_work() and chunk_sched._underway is None
    (chunk_sched._prefill_suffix, chunk_sched._prefill,
     chunk_sched._step) = kept


def _request(prompt, new):
    return Request(list(prompt), new, eos_token_ids=())


@pytest.fixture(scope="module")
def whole_sched(system):
    """ONE scheduler over the model with the cell's chunk — longer
    than any prompt here: nothing is chunked — and no page shared, for
    `_serial` (its programs are traced once); every use drains it."""
    sched = ContinuousBatchingScheduler(
        system.model, system.params, SchedulerConfig(
            num_slots=4, max_seq=128, kv_layout="paged",
            prefix_cache=False))
    assert sched._chunk == sdar_moe.PREFILL_CHUNK > 128
    return sched


def _serial(sched, prompts, new):
    """The streams of the UNCHUNKED serial loop (`whole_sched`): every
    pass is read before the next is dispatched."""
    reqs = [_request(p, n) for p, n in zip(prompts, new)]
    for r in reqs:
        assert sched.submit(r)
    while sched.has_work():
        sched.step()
        if sched._flight is not None:
            sched._read(sched._take_flight())
    return [r.generated for r in reqs]


def _scored(rows):
    """What was served against the reference, as the cell decides
    `correct` (`test_tokens_are_delivered_in_position_order_each_once`
    has the limit)."""
    sample = [{"prompt": r.prompt, "prompt_len": len(r.prompt),
               "tokens": list(r.generated), "ok": True, "index": i}
              for i, r in enumerate(rows)]
    res = correctness.score(reference, DIMS, SEED, sample, 128, 16)
    assert res["tokens"] == sum(len(r.generated) for r in rows)
    assert res["program"]["served_gap_max"] < 0.2, res


def test_the_model_names_its_chunk(system):
    """The cell's length: a multiple of the page and of the block, a
    constant of the model file that the scheduler reads — and with it
    the block model's admissions are paced."""
    assert sdar_moe.PREFILL_CHUNK % 16 == 0
    assert sdar_moe.PREFILL_CHUNK % system.model.block_length == 0
    assert system.model.prefill_chunk == sdar_moe.PREFILL_CHUNK
    sched = ContinuousBatchingScheduler(
        system.model, system.params, SchedulerConfig(
            num_slots=2, max_seq=128, kv_layout="paged"))
    assert sched._chunk == sdar_moe.PREFILL_CHUNK
    assert sched._paced and not sched._stateful


def test_chunked_paced_streams_equal_the_unchunked_serial_loops(
        whole_sched, watched):
    """The same requests through the unchunked serial loop and through
    the pipelined scheduler that admits them by chunks of 16, a piece a
    block pass behind the rows that run: token for token the same
    streams — a prompt of a chunk or less (admitted whole by both), a
    last chunk that is full, one that is padded, one that holds the
    tail alone (49 = 3 x 16 + 1: all of it at the cursor), tails of
    every length — and at most one prefill stands between two block
    passes while rows run."""
    rng = np.random.default_rng(59)
    lengths = (9, 64, 45, 49, 16, 50, 23)
    prompts = [rng.integers(0, 255, n).tolist() for n in lengths]
    new = [8, 7, 9, 8, 6, 10, 8]
    straight = _serial(whole_sched, prompts, new)
    sched, seen = watched
    reqs = [_request(p, n) for p, n in zip(prompts, new)]
    for r in reqs:
        assert sched.submit(r)
    mid = 0
    while sched.has_work():
        sched.step()
        adm = sched._underway
        if adm is not None and adm.slot is not None:
            mid += 1
            # a slot in mid-prefill is masked: its row of the page
            # table stays NULL, its cursor where its release left it
            assert not sched.slots._active[adm.slot]
            assert int(sched.slots.cache.offset[adm.slot]) == 0
    assert mid >= 8
    chunks = lambda s: [("chunk", at, CHUNK)   # noqa: E731
                        for at in range(0, s, CHUNK)]
    assert seen.pieces() == (
        [("prefill", 16)] + chunks(64) + chunks(45) + chunks(49)
        + [("prefill", 16)] + chunks(50) + chunks(23))
    # at most one enqueue between two block passes while rows run
    between, n, running = [], 0, False
    for ev in seen.log:
        if ev[0] == "pass":
            if running:
                between.append(n)
            n, running = 0, True
        else:
            n += 1
    assert max(between) == 1 and between.count(1) >= 15
    assert [r.generated for r in reqs] == straight
    _scored(reqs)


def test_a_prefix_hit_prefills_the_private_suffix_alone(whole_sched,
                                                        watched):
    """Three prompts that share their first 32 tokens, one after the
    other.  The second and third find two pages in the radix tree:
    the second's private suffix is a chunk or less and goes in as ONE
    piece at position 32 through its bucket (the `suffix` plan: new
    for this model), the third's is longer and goes in by chunks that
    start at 32.  Nothing below 32 is prefilled again, and the streams
    are those of each prompt served alone with no page shared."""
    from triton_distributed_tpu.observability import get_registry
    sched, seen = watched
    reg = get_registry()
    reg.clear()
    rng = np.random.default_rng(41)
    shared = rng.integers(0, 255, 32).tolist()
    prompts = [shared + rng.integers(0, 255, n).tolist()
               for n in (18, 11, 39)]
    new = [6, 7, 6]
    straight = [_serial(whole_sched, [p], [n])[0]
                for p, n in zip(prompts, new)]
    kept = sched.slots.cached_prefix_pages
    served = []
    for p, n in zip(prompts, new):
        req = _request(p, n)
        sched.run([req])
        served.append(req)
    assert seen.pieces() == (
        [("chunk", at, CHUNK) for at in (0, 16, 32, 48)]       # 50
        + [("chunk", 32, 16)]                                  # 43: suffix
        + [("chunk", at, CHUNK) for at in (32, 48, 64)])       # 71
    snap = reg.snapshot()["counters"]
    assert snap["serving_prefix_cache_hit_tokens_total"] == 64
    assert snap["serving_prefill_chunks_total"] == 4 + 3
    # whole pages below position s - 1 (and so below each cursor): 3
    # of the first (49 // 16), none new of the second (42 // 16), two
    # more of the third (70 // 16)
    assert sched.slots.cached_prefix_pages == kept + 3 + 0 + 2
    reg.clear()
    assert [r.generated for r in served] == straight
    _scored(served)


def test_giving_up_in_mid_prefill_returns_the_slot_and_its_pages(
        whole_sched, watched):
    """An admission under way is dropped (`_give_up_underway`: the
    pool's newest claim funds the rows that run): its slot and pages go
    back, its request to the head of the queue, and it starts over from
    position 0 — both streams are the uninterrupted ones."""
    sched, seen = watched
    rng = np.random.default_rng(53)
    prompts = [rng.integers(0, 255, n).tolist() for n in (15, 50)]
    straight = _serial(whole_sched, prompts, [12, 5])
    runner, long = _request(prompts[0], 12), _request(prompts[1], 5)
    sched.submit(runner)
    sched.step()
    sched.submit(long)
    sched.step()
    sched.step()
    adm = sched._underway
    assert adm is not None and adm.req is long and adm.done == 2
    assert adm.slot is not None
    held = sched.slots.used_pages       # the runner's and 4 of 50 tokens
    assert sched.slots.free_slots == 2
    sched._give_up_underway()
    assert sched._underway is None
    assert sched.slots.used_pages == held - 4
    assert sched.slots.free_slots == 3
    assert sched._queue[0] is long
    del seen.log[:]
    sched.drain()
    assert seen.pieces() == [("chunk", at, CHUNK)
                             for at in (0, 16, 32, 48)]
    assert long.preemptions == 0
    assert [runner.generated, long.generated] == straight


def test_preempt_and_resume_mid_block_through_a_chunked_prefill(
        whole_sched, watched):
    """`test_preempt_and_resume_mid_block` where the resume is longer
    than a chunk: the prompt and what was delivered — a finished block
    whose commit was still to come among it — go in by chunks again
    (the first pages found in the radix tree: from the hit on), and the
    resumed stream is the uninterrupted one."""
    sched, seen = watched
    rng = np.random.default_rng(12)
    pending = []
    for at in (5, 6, 7):
        # (fresh prompts: nothing of an earlier round is in the tree)
        fresh = [rng.integers(0, 255, n).tolist() for n in (9, 30)]
        want = _serial(whole_sched, fresh, [16, 16])
        reqs = [_request(p, 16) for p in fresh]
        for r in reqs:
            assert sched.submit(r)
        for _ in range(at):
            sched.step()
        sched._read(sched._take_flight())
        had = len(reqs[1].generated)
        pending.append(reqs[1].block_pending)
        del seen.log[:]
        sched._preempt(reqs[1].slot)
        sched.drain()
        assert reqs[1].preemptions == 1 and 0 < had < 16
        # the resume: 30 + had tokens, of which one whole page matched
        starts = [ev[1] for ev in seen.pieces()]
        assert starts and starts[0] == 16 and len(starts) >= 2, starts
        assert [r.generated for r in reqs] == want, (at, had)
    assert True in pending and False in pending, pending


def test_no_kind_of_chunk_argument_is_first_met_after_warm_up(
        devices, monkeypatch):
    """`tests/test_serving_pipeline.py`'s case for this family, whose
    step is a block pass: the benchmark's own `warm_up` meets a first,
    a middle and a last chunk — no pages and the slot's, the pool from
    a pass, from a scatter and from an insert — and a window's chunked
    admissions then compile nothing."""
    from tests import test_serving_pipeline as pipeline
    pipeline.chunk_arguments_are_met_in_warm_up(
        "sdar_moe", devices, pipeline.Compiled(), monkeypatch)
