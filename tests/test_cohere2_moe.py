"""Cohere2-MoE family on the CPU at tiny sizes: the program
(`models.cohere2_moe.Cohere2Moe` — a parallel block, window layers that
rotate adjacent pairs beside full layers without positions, each kind's
pages in a pool of its own, a SHARE of an expert layer with four
averaged shared experts; Pallas kernels in interpret mode) against the
plain float32 reference (`cellbench.references.cohere2_moe`, which
imports nothing of the program), on seeded weights laid in by the
benchmark's own adapter.

Tolerances.  The program computes in bfloat16 with float32 accumulation;
the reference in float32.  Errors are in units of the position's logit
spread: at these sizes (4 layers, hidden 128) the program's
worst logit of a position lies a few hundredths of a spread from the
reference's, under `LOGIT_TOL` at every
position but those a routing near-tie reaches: where bfloat16 rounding
flips one of a token's four experts — often between one held here and
one that is not — that token's logits move by several tenths.  So a
sequence passes with at most `FLIPS` positions past the tolerance.  The
same comparison on the reference's float8 control is checked to FAIL.
The window is 16 tokens and a page 16, so a row gives a page back every
16 tokens from its 32nd on.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench import correctness
from cellbench.adapters import cohere2_moe as adapter
from cellbench.references import cohere2_moe as reference
from triton_distributed_tpu.kernels.flash_attention import (
    attention_reference, flash_attention)
from triton_distributed_tpu.kernels.flash_decode import flash_decode_paged
from triton_distributed_tpu.layers.moe_mlp import SparseMoE
from triton_distributed_tpu.models import AutoLLM, cohere2_moe
from triton_distributed_tpu.models.cohere2_moe import Cohere2Moe
from triton_distributed_tpu.serving import (
    ContinuousBatchingScheduler, RejectReason, Request, SchedulerConfig)
from triton_distributed_tpu.serving.engine_batched import pad_prompt
from triton_distributed_tpu.serving.pages import PagedKV
from triton_distributed_tpu.serving.toy import ToyConfig, ToyModel

LOGIT_TOL = 0.1
FLIPS = 8
W, PS = 16, 16

#: The published `config.json` keys at test size: one period of the
#: layer pattern, top-4 of 32 experts (8 held: one chip of four) beside
#: four averaged shared experts.
TINY = {
    "model_type": "cohere2_moe", "vocab_size": 256, "hidden_size": 128,
    "intermediate_size": 64, "num_hidden_layers": 4,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
    "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16,
    "num_experts": 8, "num_experts_per_tok": 4, "num_shared_experts": 4,
    "norm_topk_prob": True, "sliding_window": W, "rope_theta": 50000,
    "layer_norm_eps": 1e-5, "logit_scale": 1, "attention_bias": False,
    "expert_selection_fn": "sigmoid", "first_k_dense_replace": 0,
    "hidden_act": "silu", "use_gated_activation": True,
    "use_parallel_block": True, "use_qk_norm": False,
    "shared_expert_combination_strategy": "average",
    "position_embedding_type": "rope_gptj", "rotary_pct": 1,
    "tie_word_embeddings": True, "torch_dtype": "bfloat16",
    "share": {"chips": 4, "experts_of_layer": 32, "experts_held": [0, 8]},
    "serving": {"num_slots": 2, "max_seq": 128,
                "prefill_buckets": [16, 32, 64],
                "kv_budget_bytes_per_chip": 1 << 20, "max_queue": 16},
}
SEED = 11
DIMS = reference.dims_of(TINY)
CHUNK = 16


@pytest.fixture(scope="module")
def system(devices):
    """The benchmark's adapter at test size — the program with the
    reference's weights behind its scheduler — chunks of 16 tokens."""
    mp = pytest.MonkeyPatch()
    mp.setattr(cohere2_moe, "PREFILL_CHUNK", CHUNK)
    try:
        yield adapter.System(TINY, SEED, devices[:1])
    finally:
        mp.undo()


def _err(got, ref):
    """The worst logit's distance a position, in logit spreads."""
    return np.abs(got - ref).max(axis=-1) / ref.std(axis=-1)


def _ref_logits(tokens, first, n_out, precision="f32"):
    pad = np.zeros(128, np.int64)
    pad[:len(tokens)] = tokens
    return np.asarray(reference.logits_at(DIMS, SEED, pad, first, n_out,
                                          precision=precision))


# ---------------------------------------------------------------------------
# the kernels' window
# ---------------------------------------------------------------------------

def _paged(rng, lens, t=8, ps=8, hkv=2, g=4, d=16):
    b = len(lens)
    pool = 1 + b * t
    k = jnp.asarray(rng.standard_normal((pool, hkv, ps, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((pool, hkv, ps, d)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((b, hkv * g, d)), jnp.float32)
    table = 1 + np.arange(b * t, dtype=np.int32).reshape(b, t)
    return q, k, v, table, jnp.asarray(lens, jnp.int32)


def _dense_decode(q, k, v, table, lens, window):
    b, h, d = q.shape
    hkv = k.shape[1]
    out = np.zeros((b, h, d), np.float32)
    for i in range(b):
        n = int(lens[i])
        rows = lambda pool: np.moveaxis(      # noqa: E731
            np.asarray(pool)[table[i]], 1, 0).reshape(hkv, -1, d)[:, :n]
        kk, vv = rows(k), rows(v)
        lo = max(n - window, 0) if window else 0
        for hh in range(h):
            sc = np.asarray(q[i, hh]) @ kk[hh // (h // hkv), lo:].T * d ** -0.5
            p = np.exp(sc - sc.max())
            out[i, hh] = (p / p.sum()) @ vv[hh // (h // hkv), lo:]
    return out


def test_paged_decode_without_a_window_is_the_program_it_was():
    """`window=None` is today's call: the same jaxpr as a call that
    never heard of the argument, and a window no row can reach computes
    the same numbers bit for bit."""
    q, k, v, table, lens = _paged(np.random.default_rng(0), (5, 33, 64, 17))
    plain, lse = flash_decode_paged(q, k, v, jnp.asarray(table), lens)
    wide, lse_w = flash_decode_paged(q, k, v, jnp.asarray(table), lens,
                                     window=1 << 20)
    assert np.array_equal(np.asarray(plain), np.asarray(wide))
    assert np.array_equal(np.asarray(lse), np.asarray(lse_w))
    call = lambda **kw: str(jax.make_jaxpr(       # noqa: E731
        lambda *a: flash_decode_paged(*a, **kw))(
            q, k, v, jnp.asarray(table), lens))
    assert call() == call(window=None)
    assert "swa_decode_paged" not in call()
    assert np.allclose(plain, _dense_decode(q, k, v, table, lens, 0),
                       atol=2e-5)


@pytest.mark.parametrize("window", [8, 12, 40])
def test_paged_decode_window(window):
    """A row sees its last ``window`` keys; the pages wholly behind it
    are never read (their table entries point at a page of NaNs)."""
    rng = np.random.default_rng(window)
    lens = (5, 33, 64, 17, 1)
    q, k, v, table, kv_len = _paged(rng, lens)
    k = k.at[0].set(jnp.nan)
    v = v.at[0].set(jnp.nan)
    want = _dense_decode(q, k, v, table, kv_len, window)
    gone = table.copy()
    for i, n in enumerate(lens):
        gone[i, :max(n - window, 0) // 8] = 0
    got, _ = flash_decode_paged(q, k, v, jnp.asarray(gone), kv_len,
                                window=window, name="swa_decode_paged")
    assert np.allclose(got, want, atol=2e-5)


@pytest.mark.parametrize("sq,sk,off,window,traced", [
    (64, 64, 0, 16, False), (32, 96, 40, 24, True), (32, 96, 64, 16, True),
    (48, 48, 0, 100, False)])
def test_flash_attention_window(sq, sk, off, window, traced):
    rng = np.random.default_rng(sq + off)
    q = jnp.asarray(rng.standard_normal((1, 4, sq, 16)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 2, sk, 16)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 2, sk, 16)), jnp.float32)
    want = attention_reference(q, k, v, kv_offset=off, window=window)
    run = lambda o: flash_attention(        # noqa: E731
        q, k, v, kv_offset=o, window=window, block_q=16, block_k=16,
        name="swa_prefill_attention")
    got = jax.jit(run)(jnp.int32(off)) if traced else run(off)
    assert np.allclose(got, want, atol=2e-5)
    plain = attention_reference(q, k, v, kv_offset=off)
    assert (window >= sq + off) == bool(np.allclose(want, plain, atol=1e-6))


# ---------------------------------------------------------------------------
# program against reference
# ---------------------------------------------------------------------------

def test_registry_knows_the_family(system):
    assert isinstance(AutoLLM(system.model_cfg, system.mesh), Cohere2Moe)
    assert system.model.window == W and system.model.prefill_chunk == CHUNK


def test_prefill_logits_match_reference(system):
    """A prompt of four windows: the prefill program's own logits."""
    prompt = np.random.default_rng(1).integers(0, 256, 64).tolist()
    ids, _ = pad_prompt(prompt, 64)
    logits, _ = jax.jit(system.model.make_prefill_fn())(
        system.params, ids, system.model.create_cache(1, 64))
    assert _err(np.asarray(logits), _ref_logits(prompt, 63, 1)
                ).max() < LOGIT_TOL


def _new_slots(model, n=2, **kw):
    return PagedKV(model, n, max_seq=128, page_size=PS, **kw)


def _visible_pages_held(slots, slot, length):
    """Every page that holds a token the row's next query sees is
    mapped in the window table, and none behind the window is."""
    row = slots._wtable[slot]
    first = max(length - W, 0) // PS
    last = (length - 1) // PS
    assert (row[first:last + 1] != 0).all(), (row, length)
    assert (row[:first] == 0).all() and (row[last + 1:] == 0).all()


def _decode(system, slots, prompts, teacher, steps):
    decode = jax.jit(system.model.make_paged_decode_fn(page_size=PS))
    got = []
    tokens = np.asarray([p[-1] for p in prompts], np.int32)
    for i in range(steps):
        for b, p in enumerate(prompts):
            assert slots.ensure(b, len(p) + i)
            _visible_pages_held(slots, b, len(p) + i)
        slots.flush()
        logits, slots.cache = decode(system.params, jnp.asarray(tokens),
                                     slots.cache)
        got.append(np.asarray(logits))
        tokens = np.asarray([t[i] for t in teacher], np.int32)
    return np.stack(got)


def _prefill_whole(system, slots, p, shared=()):
    bucket = 32 if len(p) <= 32 else 64
    ids, s = pad_prompt(p, bucket)
    _, row = jax.jit(system.model.make_prefill_fn())(
        system.params, ids, system.model.create_cache(1, bucket))
    return slots.insert_prefill(row, p, s, jnp.zeros((2,), jnp.uint32),
                                list(shared))


def _prefill_chunks(system, slots, p, shared=()):
    """The prompt in pieces of `CHUNK`, each over the pages of both
    kinds its predecessors left, from position 0 whatever is shared."""
    suffix = jax.jit(system.model.make_prefill_suffix_fn())
    c, s = slots.cache, len(p)
    slot = slots.begin_prefill(s, list(shared))
    for at in range(0, s, CHUNK):
        ids, _ = pad_prompt(p[at:at + CHUNK], CHUNK)
        c = slots.cache
        pages = np.stack([slots.prefill_pages(slot),
                          slots.prefill_window_pages(slot)])
        row = suffix(system.params, ids, jnp.int32(at),
                     system.model.create_cache(1, CHUNK),
                     (c.ks, c.vs, c.wks, c.wvs), pages)
        last = at + CHUNK >= s
        slots.insert_rows(slot, row, at,
                          jnp.zeros((2,), jnp.uint32) if last else None)
        # what the NEXT piece's first query sees is still mapped
        if not last:
            held = slots.prefill_window_pages(slot)
            lo = max(at + CHUNK - W + 1, 0) // PS
            assert (held[lo:(at + CHUNK) // PS] != 0).all()
            assert (held[:lo] == 0).all()
    slots.finish_prefill(slot, p)
    return slot


@pytest.fixture(scope="module")
def decoded(system):
    """Two requests in one batch — one prefilled whole through a padded
    bucket, one in chunks over the pool — then 56 teacher-forced decode
    steps: past three windows, across three page give-backs a row."""
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 256, n).tolist() for n in (21, 50)]
    steps = 56
    teacher = [rng.integers(0, 256, steps).tolist() for _ in prompts]
    slots = _new_slots(system.model, prefix_cache=False)
    _prefill_whole(system, slots, prompts[0])
    _prefill_chunks(system, slots, prompts[1])
    got = _decode(system, slots, prompts, teacher, steps)
    return prompts, teacher, steps, got, slots


@pytest.mark.parametrize("row", [0, 1])
def test_decode_logits_match_reference(decoded, row):
    prompts, teacher, steps, got, _ = decoded
    p = prompts[row]
    seq = p + teacher[row][:steps - 1]
    ref = _ref_logits(seq, len(p) - 1, steps)
    err = _err(got[:, row], ref)
    assert np.median(err) < LOGIT_TOL / 2, err
    assert (err > LOGIT_TOL).sum() <= FLIPS, err


def test_float8_control_fails_the_tolerance(decoded):
    prompts, teacher, steps, *_ = decoded
    p = prompts[1]
    seq = p + teacher[1][:steps - 1]
    low = _ref_logits(seq, len(p) - 1, steps, precision="fp8")
    ref = _ref_logits(seq, len(p) - 1, steps)
    err = _err(low, ref)
    assert (err > LOGIT_TOL).mean() > 0.9 and np.median(err) > 2 * LOGIT_TOL


def test_window_pool_holds_a_window_a_row_and_books_balance(decoded):
    *_, slots = decoded
    assert slots.window_pages_per_slot == W // PS + 1
    assert slots.window_usable_pages == 2 * (W // PS + 1)
    # 21 + 55 and 50 + 55 tokens: each row holds its window's pages
    assert slots.window_pages_live <= slots.window_usable_pages
    assert slots.window_released >= 2 * 3
    for slot in (0, 1):
        slots.release(slot)
    assert slots.window_pages_live == 0 == slots.used_pages
    assert (slots._wtable == 0).all()


@pytest.mark.parametrize("how", ["whole", "chunks"])
def test_whole_prefill_equals_chunks_equals_reference(system, decoded, how):
    """The 50-token prompt prefilled whole (bucket 64) and in chunks
    leaves the same rows in both pools: the first decode steps' logits
    agree with the reference either way."""
    prompts, teacher, *_ = decoded
    p = prompts[1]
    slots = _new_slots(system.model, 1, prefix_cache=False)
    (_prefill_whole if how == "whole" else _prefill_chunks)(
        system, slots, p)
    got = _decode(system, slots, [p], [teacher[1]], 6)
    ref = _ref_logits(p + teacher[1][:5], len(p) - 1, 6)
    assert (_err(got[:, 0], ref) > LOGIT_TOL).sum() <= 1


def test_prefix_hit_shares_full_pages_and_recomputes_the_window(system):
    """The sharing rule on a window model: the matched pages are the
    FULL layers' (shared, never rewritten), the prefill covers the
    prompt from position 0, and no window page is ever in the tree."""
    rng = np.random.default_rng(5)
    head = rng.integers(0, 256, 48).tolist()
    a, b = head + [1, 2, 3], head + rng.integers(0, 256, 9).tolist()
    slots = _new_slots(system.model)
    sa = _prefill_chunks(system, slots, a)
    shared = slots.match_prefix(b)
    assert len(shared) == 3
    full_before = np.asarray(slots.cache.ks[0])[[n.page for n in shared]]
    sb = _prefill_chunks(system, slots, b, shared)
    assert (slots._table[sb, :3] == slots._table[sa, :3]).all()
    assert np.array_equal(
        np.asarray(slots.cache.ks[0])[[n.page for n in shared]],
        full_before)
    # the window rows are b's own
    assert not set(slots._wtable[sa][slots._wtable[sa] != 0]) & set(
        slots._wtable[sb][slots._wtable[sb] != 0])
    slots.release(sa)
    teacher = rng.integers(0, 256, 20).tolist()
    dec = jax.jit(system.model.make_paged_decode_fn(page_size=PS))
    tok, got = b[-1], []
    for i in range(20):
        assert slots.ensure(sb, len(b) + i)
        slots.flush()
        toks = np.zeros(2, np.int32)
        toks[sb] = tok
        logits, slots.cache = dec(system.params, jnp.asarray(toks),
                                  slots.cache)
        got.append(np.asarray(logits)[sb])
        tok = teacher[i]
    ref = _ref_logits(b + teacher[:19], len(b) - 1, 20)
    err = _err(np.stack(got), ref)
    assert (err > LOGIT_TOL).sum() <= 2, err
    slots.release(sb)
    assert slots.window_pages_live == 0
    assert slots.used_pages == slots.cached_prefix_pages == 3


# ---------------------------------------------------------------------------
# through the scheduler
# ---------------------------------------------------------------------------

def _served_gap(prompt, served):
    ref = reference.logits_at(
        DIMS, SEED, np.pad(prompt + served, (0, 128 - len(prompt)
                                             - len(served))),
        len(prompt) - 1, len(served))
    return correctness.gaps(ref, served)


def test_scheduler_serves_across_give_backs_a_resume_and_a_prefix_hit(
        system):
    """Three requests on two slots, a prefix shared by two of them, a
    pool too small for both long rows: one is preempted and resumed.
    Every served token is the reference's best or a near-tie of it, the
    books of both pools balance at the end, and the counters say what
    the rule cost."""
    rng = np.random.default_rng(9)
    head = rng.integers(0, 256, 32).tolist()
    prompts = [head + rng.integers(0, 256, 17).tolist(),
               head + rng.integers(0, 256, 8).tolist(),
               rng.integers(0, 256, 20).tolist()]
    model = system.model
    page = model.create_paged_cache(1, 2, PS, 1).bytes_per_page()
    wpage = model.create_paged_cache(1, 2, PS, 1).window_bytes_per_page()
    sched = ContinuousBatchingScheduler(
        model, system.params, SchedulerConfig(
            num_slots=2, max_seq=128, kv_layout="paged",
            prefill_buckets=(16, 32),
            kv_budget_bytes=2 * 2 * wpage + 7 * page))
    assert sched.slots.usable_pages == 7 and sched._windowed
    reqs = [Request(p, 40, eos_token_ids=(), seed=0) for p in prompts]
    for r in reqs:
        assert sched.submit(r), r.reject_reason    # 49 > every bucket
    while sched.has_work():
        sched.step()
    assert all(len(r.generated) == 40 for r in reqs)
    assert sum(r.preemptions for r in reqs) >= 1
    for r, p in zip(reqs, prompts):
        gap = _served_gap(p, list(r.generated))
        assert gap.max() < 1.5 and gap.mean() < 0.1, gap
    slots = sched.slots
    assert slots.window_pages_live == 0
    assert slots.used_pages == slots.cached_prefix_pages
    assert slots.window_released > 0
    # the shared head and the resumed row's context were prefilled anew
    assert sched._window_recomputed >= 32 + 49
    sched.close()


@pytest.mark.parametrize("chunk,ok", [(16, True), (0, False)])
def test_prompt_past_the_largest_bucket(chunk, ok, devices):
    """Admitted on a model that chunks — pieces cover any length — and
    refused, as ever, on one without the program's chunks."""
    model = ToyModel(ToyConfig(max_seq_len=128, prefill_chunk=chunk))
    sched = ContinuousBatchingScheduler(
        model, model.init_params(jax.random.PRNGKey(0)), SchedulerConfig(
            num_slots=2, max_seq=128, kv_layout="paged",
            prefill_buckets=(16, 32)))
    req = Request(list(range(1, 71)), 4, eos_token_ids=(), seed=0)
    assert sched.submit(req) == ok
    if not ok:
        assert req.reject_reason == RejectReason.PROMPT_TOO_LONG
        assert sched.structural_reject(req, full_prefill=True)
        return
    assert (sched.structural_reject(req, full_prefill=True)
            == RejectReason.PROMPT_TOO_LONG)
    while sched.has_work():
        sched.step()
    assert len(req.generated) == 4
    short = ToyModel(ToyConfig(max_seq_len=128))
    ref = ContinuousBatchingScheduler(
        short, short.init_params(jax.random.PRNGKey(0)), SchedulerConfig(
            num_slots=2, max_seq=128, kv_layout="paged",
            prefill_buckets=(16, 32, 128)))
    same = Request(list(range(1, 71)), 4, eos_token_ids=(), seed=0)
    assert ref.submit(same)
    while ref.has_work():
        ref.step()
    assert list(same.generated) == list(req.generated)


# ---------------------------------------------------------------------------
# the expert layer
# ---------------------------------------------------------------------------

def _moe(held, mode="xla"):
    return SparseMoE(hidden=128, ffn=64, num_experts=32, topk=4,
                     n_shared=4, mode=mode, held=held,
                     shared_combine="average", selection_bias=False)


def test_router_choice_is_the_references_on_equal_input(system):
    x = jnp.asarray(np.random.default_rng(3).standard_normal((64, 128)),
                    jnp.bfloat16)
    w = reference.layer_weights(
        reference.layer_key(reference.base_key(SEED), 0), DIMS)
    ids, wts = _moe((0, 8)).route(x, {"router": w["router"]})
    dense = np.asarray(reference.router_weights(
        x.astype(jnp.float32), w, DIMS))
    assert "router_bias" not in _moe((0, 8)).init_params(
        jax.random.PRNGKey(0))
    for i in range(64):
        assert set(np.flatnonzero(dense[i])) == set(np.asarray(ids[i]))
        assert np.allclose(dense[i, np.asarray(ids[i])], wts[i], atol=1e-6)


def test_eight_shares_and_the_shared_experts_once_are_the_uncut_layer(
        system):
    """Program and reference alike: the routed parts of the eight
    shares of a layer's 32 experts, plus the shared experts' MEAN once,
    are the layer with every expert here."""
    x = jnp.asarray(np.random.default_rng(4).standard_normal((48, 128)),
                    jnp.float32)
    key = reference.layer_key(reference.base_key(SEED), 1)
    whole = reference.dims_of(TINY, held=(0, 32))
    routed, shared = reference.ffn_parts(x, key, whole)
    parts = [reference.ffn_parts(
        x, key, reference.dims_of(TINY, held=(lo, lo + 4)))
        for lo in range(0, 32, 4)]
    assert all(np.allclose(p[1], shared, atol=1e-6) for p in parts)
    assert np.allclose(sum(p[0] for p in parts), routed, atol=1e-4)
    # the four shared experts are AVERAGED
    w = reference.layer_weights(key, whole)
    u = reference.layer_norm(x, w["ln"], 1e-5)
    f32 = lambda t: np.asarray(t, np.float32)       # noqa: E731
    one = [f32(jax.nn.silu(u @ f32(w["shared_gate"][e]))
               * (u @ f32(w["shared_up"][e]))) @ f32(w["shared_down"][e])
           for e in range(4)]
    assert np.allclose(shared, sum(one) / 4, atol=1e-3)

    xb = x.astype(jnp.bfloat16)
    full = _moe(None)
    params = full.init_params(jax.random.PRNGKey(1))
    y_full, _ = full(xb, params)
    only_shared = (full._shared(xb, params["shared"]) / 4)
    total = 0
    for lo in range(0, 32, 4):
        share = dict(params, **{k: params[k][lo:lo + 4]
                                for k in ("gate", "up", "down")})
        y, stats = _moe((lo, lo + 4))(xb, share)
        total = total + y.astype(jnp.float32) - only_shared
        assert stats.shape == (4,)
    assert np.allclose(total + only_shared, y_full.astype(jnp.float32),
                       atol=0.06)
    summed = dict(full.__dict__, shared_combine="sum")
    y_sum, _ = SparseMoE(**summed)(xb, params)
    assert np.allclose(y_sum.astype(jnp.float32)
                       - y_full.astype(jnp.float32),
                       3 * only_shared, atol=0.06)


def test_fused_expert_layer_matches_the_golden(system):
    xb = jnp.asarray(np.random.default_rng(6).standard_normal((32, 128)),
                     jnp.bfloat16)
    params = _moe((8, 16)).init_params(jax.random.PRNGKey(2))
    want, _ = _moe((8, 16))(xb, params)
    got, _ = _moe((8, 16), mode="fused")(xb, params, phase="decode")
    assert np.allclose(got.astype(jnp.float32), want.astype(jnp.float32),
                       atol=0.05)
