"""SDAR-MoE family on the CPU at tiny sizes: the program
(`models.sdar_moe.SdarMoe` — grouped-query attention under the
block-causal mask, a dropless softmax-routed expert layer, generation
by diffusion over blocks through the pipelined scheduler and the page
pool; Pallas kernels in interpret mode) against the plain float32
reference (`cellbench.references.sdar_moe`, which imports nothing of
the program), on seeded weights laid in by the benchmark's own adapter.

Tolerances.  The program computes in bfloat16 with float32
accumulation; the reference in float32.  At these sizes (2 layers,
hidden 128) a position's logits have a spread of about 1.0 and the
program's worst logit of a position lies a median 0.02-0.03 from the
reference's (measured), under `LOGIT_TOL` = 0.08 at every position but
those a routing near-tie reaches: where bfloat16 rounding flips a
token's fourth expert that token's logits move by 0.1-0.5, and inside a
block every position attends every other, so one flip can move a
block's four positions.  So a comparison passes with at most `FLIPS`
positions past the tolerance and a median under half of it.  The same
comparison on the reference's float8 control reads 0.15-0.4 at nearly
EVERY position and is checked to FAIL both.  Kernels against dense
attention are float32 on both sides: 2e-5.
"""

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from cellbench import correctness
from cellbench.adapters import sdar_moe as adapter
from cellbench.references import sdar_moe as reference
from triton_distributed_tpu.kernels.flash_attention import (
    attention_reference, flash_attention)
from triton_distributed_tpu.kernels.flash_decode import flash_decode_paged
from triton_distributed_tpu.layers.moe_mlp import MOE_STATS, SparseMoE
from triton_distributed_tpu.models import AutoLLM, ModelConfig
from triton_distributed_tpu.models.sdar_moe import (
    HF_END_NAMES, HF_LAYER_NAMES, SdarMoe)
from triton_distributed_tpu.serving import (
    ContinuousBatchingScheduler, FinishReason, Request, SchedulerConfig)
from triton_distributed_tpu.serving.engine_batched import (
    make_block_pass_fn, pad_prompt, pick_bucket)
from triton_distributed_tpu.serving.pages import PagedKV

LOGIT_TOL = 0.08
FLIPS = 6
N, MASK = 4, 255

#: The published `config.json` keys at test size (8 query heads a KV
#: head and top-4 of 16 by softmax scores, no shared expert), with the
#: generation's assumed sizes as the configuration file states them.
TINY = {
    "model_type": "sdar_moe", "vocab_size": 256, "hidden_size": 128,
    "intermediate_size": 256, "moe_intermediate_size": 64,
    "num_hidden_layers": 2, "num_attention_heads": 8,
    "num_key_value_heads": 1, "head_dim": 16, "num_experts": 16,
    "num_experts_per_tok": 4, "norm_topk_prob": True,
    "decoder_sparse_step": 1, "mlp_only_layers": [],
    "rms_norm_eps": 1e-6, "rope_theta": 1000000,
    "tie_word_embeddings": False, "torch_dtype": "bfloat16",
    "mask_token_id": MASK,
    "generation": {"block_length": N, "denoising_steps": 2,
                   "remasking": "sequential"},
    "serving": {"num_slots": 3, "max_seq": 128,
                "kv_budget_bytes_per_chip": 3 * 128 * 2 * 2 * 16 * 2,
                "max_queue": 64},
}
SEED = 7
DIMS = reference.dims_of(TINY)


@pytest.fixture(scope="module")
def system(devices):
    """The benchmark's adapter at test size: the program with the
    reference's weights, behind its scheduler."""
    return adapter.System(TINY, SEED, devices[:1])


@pytest.fixture(scope="module")
def static_system(devices):
    """The same weights under the `low_confidence_static` schedule."""
    cfg = dict(TINY, generation=dict(TINY["generation"],
                                     remasking="low_confidence_static"))
    return adapter.System(cfg, SEED, devices[:1])


def _state_logits(state, first, n_out, precision="f32"):
    """The reference's full forward over a sequence as it stands."""
    pad = -len(state) % N
    seq = np.concatenate([np.asarray(state, np.int64),
                          np.full(pad, MASK, np.int64)])
    return np.asarray(reference.forward(DIMS, SEED, seq, first, n_out,
                                        precision=precision))


def _close(got, ref):
    err = np.abs(np.asarray(got) - ref).max(axis=-1).ravel()
    return np.median(err) < LOGIT_TOL / 2 and (err > LOGIT_TOL).sum() \
        <= FLIPS, err


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cb", [4, 8, 32])
@pytest.mark.parametrize("sq,bq,bk", [(64, 64, 64), (128, 32, 32),
                                      (128, 64, 32), (96, 32, 32)])
def test_flash_attention_block_causal_against_a_dense_mask(cb, sq, bq,
                                                           bk):
    """Single block, the packed schedule (static offset) and the
    rectangular grid (traced offset), whole and ragged."""
    key = jax.random.key(cb)
    q = jax.random.normal(key, (1, 8, sq, 16), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 2, sq, 16))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 2, sq, 16))
    pos = np.arange(sq)
    dense = attention_reference(q, k, v, causal=False)
    # an independent dense mask: j // cb <= i // cb
    s = jnp.einsum("bhqd,bhkd->bhqk", q, jnp.repeat(k, 4, axis=1)) / 4
    s = jnp.where((pos[None, :] // cb <= pos[:, None] // cb), s, -1e30)
    want = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1),
                      jnp.repeat(v, 4, axis=1))
    assert float(jnp.abs(want - dense).max()) > 0.1       # it masks
    for off in (0, jnp.int32(0)):
        got = flash_attention(q, k, v, causal=True, kv_offset=off,
                              block_q=bq, block_k=bk, causal_block=cb,
                              interpret=True)
        assert float(jnp.abs(got - want).max()) < 2e-5
    plain = flash_attention(q, k, v, causal=True, block_q=bq,
                            block_k=bk, interpret=True)
    assert float(jnp.abs(plain - want).max()) > 0.1


@pytest.mark.parametrize("folded", [False, True])
@pytest.mark.parametrize("n", [4, 8])
def test_flash_decode_paged_at_a_blocks_query_rows(n, folded):
    """8 x n query rows a KV head, all seeing one row's keys through
    the page table: against dense attention over the gathered pages.
    ``folded``: twice the rows, a finished block's in front of the
    block in flight's, the last n keys hidden from the front half —
    against the dense mask; the back half is bit for bit what the call
    without the mask returns, and a call that spells the absent mask
    out is the program it was.  The last row's length lies 2 keys past
    a gather block's edge, so its hidden keys lie in two of them."""
    hkv, g, d, ps, t = 2, 8, 16, 8, 66
    key = jax.random.key(n)
    lens = jnp.asarray([n, 3 * ps + n, 2 * ps, 64 * ps + 2], jnp.int32)
    b = lens.shape[0]
    pools = [jax.random.normal(jax.random.fold_in(key, i),
                               (1 + b * t, hkv, ps, d), jnp.float32)
             for i in (1, 2)]
    table = 1 + jnp.arange(b * t, dtype=jnp.int32).reshape(b, t)
    rows = g * n * (2 if folded else 1)
    q = jax.random.normal(key, (b, hkv * rows, d), jnp.float32)
    plain, _ = flash_decode_paged(q, *pools, table, lens, interpret=True)
    got = plain
    if folded:
        got, _ = flash_decode_paged(q, *pools, table, lens,
                                    front_hidden=n, interpret=True)
    front = (np.arange(rows) < rows // 2) & folded
    for row in range(b):
        k, v = (p[table[row]].transpose(1, 0, 2, 3).reshape(hkv, -1, d)
                [:, :int(lens[row])] for p in pools)
        qr = q[row].reshape(hkv, rows, d)
        sc = jnp.einsum("hqd,hkd->hqk", qr, k) * d ** -0.5
        hidden = front[:, None] & (np.arange(int(lens[row]))[None, :]
                                   >= int(lens[row]) - n)
        p = jax.nn.softmax(jnp.where(hidden, -1e30, sc), axis=-1)
        want = jnp.einsum("hqk,hkd->hqd", p, v)
        have = got[row].reshape(hkv, rows, d)
        if folded and int(lens[row]) == n:      # a front that sees nothing
            want, have = want[:, rows // 2:], have[:, rows // 2:]
            assert bool(jnp.isfinite(got[row]).all())
        assert float(jnp.abs(have - want).max()) < 2e-5
    if folded:
        back = np.tile(~front, hkv)
        assert (np.asarray(got)[:, back] == np.asarray(plain)[:, back]).all()
        assert float(jnp.abs(got - plain).max()) > 1e-3     # it masks
    a = jax.make_jaxpr(lambda q: flash_decode_paged(
        q, *pools, table, lens, interpret=True))(q)
    c = jax.make_jaxpr(lambda q: flash_decode_paged(
        q, *pools, table, lens, front_hidden=None, interpret=True))(q)
    assert str(a) == str(c)


# ---------------------------------------------------------------------------
# the expert layer's two routers
# ---------------------------------------------------------------------------

def test_softmax_routing_chooses_the_references_experts_exactly(system):
    """Equal input (bfloat16 values on both sides): the same four
    experts in the same order, the same weights."""
    w = reference.layer_weights(
        reference.layer_key(reference.base_key(SEED), 1), DIMS)
    x = jax.random.normal(jax.random.key(6), (96, 128)).astype(
        jnp.bfloat16)
    ids, wts = system.model.moe.route(
        x, system.params["layers"][1]["mlp"])
    combine = np.asarray(reference.router_weights(
        x.astype(jnp.float32), w, DIMS))
    for row in range(96):
        chosen = np.flatnonzero(combine[row])
        assert sorted(np.asarray(ids[row])) == sorted(chosen)
        np.testing.assert_allclose(
            np.asarray(wts[row]), combine[row][np.asarray(ids[row])],
            rtol=1e-6)
    assert "router_bias" not in system.params["layers"][1]["mlp"]
    assert "shared" not in system.params["layers"][1]["mlp"]


def test_sigmoid_scoring_with_a_shared_expert_is_todays_layer():
    """`scoring="sigmoid", n_shared=1` spelled out is the layer the
    other two families run: the same parameters from a key, the same
    program, the same numbers bit for bit."""
    plain = SparseMoE(hidden=128, ffn=128, num_experts=16, topk=4)
    spelled = SparseMoE(hidden=128, ffn=128, num_experts=16, topk=4,
                        scoring="sigmoid", n_shared=1)
    p = plain.init_params(jax.random.key(1))
    assert sorted(p) == ["down", "gate", "router", "router_bias",
                         "shared", "up"] == sorted(plain.param_specs())
    x = jax.random.normal(jax.random.key(2), (24, 128)).astype(
        jnp.bfloat16)
    for phase in ("decode", "prefill"):
        y0, s0 = plain(x, p, phase=phase)
        y1, s1 = spelled(x, p, phase=phase)
        assert (y0 == y1).all() and (s0 == s1).all()
        assert str(jax.make_jaxpr(lambda x, p: plain(x, p, phase))(
            x, p)) == str(jax.make_jaxpr(
                lambda x, p: spelled(x, p, phase))(x, p))
    # and the sigmoid router still reads its selection bias
    far = dict(p, router_bias=jnp.where(jnp.arange(16) >= 12, 10., 0.))
    assert (np.asarray(plain.route(x, far)[0]) >= 12).all()
    with pytest.raises(ValueError, match="scoring"):
        SparseMoE(hidden=8, ffn=8, num_experts=4, topk=2, scoring="x")


# ---------------------------------------------------------------------------
# the reference with itself
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("plen", [8, 9, 11])
def test_logits_at_is_the_full_forward_of_each_reveal_state(plen):
    """`logits_at` (a clean pass + a noised pass a denoise step) gives
    for every served position the logits of `forward` over the
    sequence as it stood when the program revealed that position."""
    rng = np.random.default_rng(plen)
    n_out = 8
    seq = rng.integers(0, 255, plen + n_out)
    got = np.asarray(reference.logits_at(
        DIMS, SEED, np.concatenate([seq, np.zeros(9, np.int64)]),
        plen - 1, n_out))
    when = reference.reveal_pass(DIMS, plen, np.arange(plen + n_out + N))
    assert (when[:plen] == -1).all() and set(when[plen:]) == {0, 1}
    for k in range(n_out):
        p = plen + k
        lo = p // N * N
        state = np.concatenate([seq, np.zeros(N, np.int64)])[:lo + N]
        hide = (np.arange(lo, lo + N) >= plen) & (when[lo:lo + N]
                                                   >= when[p])
        state[lo:lo + N][hide] = MASK
        naive = _state_logits(state, p, 1)[0]
        assert np.abs(naive - got[k]).max() < 1e-4


# ---------------------------------------------------------------------------
# program against reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("length", [32, 20])
def test_prefill_logits_match_reference_under_the_block_causal_mask(
        system, length):
    """The prefill program's own logits (the last position's; no
    shift) for a prompt that fills its bucket and one padded into it
    (cut to whole blocks: the padded tail lies in later blocks)."""
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, 255, 32).tolist()
    prefill = jax.jit(system.model.make_prefill_fn())
    ids = jnp.asarray(prompt[:length], jnp.int32)[None]
    logits, _ = prefill(system.params, ids,
                        system.model.create_cache(1, length))
    ref = _state_logits(prompt[:length], length - 1, 1)
    assert np.abs(np.asarray(logits) - ref).max() < LOGIT_TOL
    # the reference's float8 control fails it
    low = _state_logits(prompt[:length], length - 1, 1, "fp8")
    assert np.abs(low - ref).max() > LOGIT_TOL


def test_the_causal_mask_fails_the_reference(system):
    """The same weights under the plain causal mask are another model:
    an earlier position of a block no longer sees the later ones."""
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, 255, 32).tolist()
    model = AutoLLM(system.model_cfg, system.mesh, mode="fused")
    model.attn = dataclasses.replace(model.attn, block=0)
    ids = jnp.asarray(prompt[:30] + [MASK, MASK], jnp.int32)[None]

    def last_block(m):
        # logits at position 28 (first of the last block)
        x = jax.jit(m.make_prefill_fn())(
            system.params, ids, m.create_cache(1, 32))[1]
        return np.asarray(x.ks[1][0, 0, 28], np.float32)

    assert np.abs(last_block(model)
                  - last_block(system.model)).max() > 0.05


def _paged(system, prompts):
    """Each prompt prefilled through a padded bucket and inserted with
    the cursor at the end of its whole blocks."""
    model, params = system.model, system.params
    slots = PagedKV(model, len(prompts), max_seq=128, page_size=16,
                    prefix_cache=False)
    prefill = jax.jit(model.make_prefill_fn())
    for p in prompts:
        bucket = pick_bucket(len(p), (16, 32, 64))
        ids, s = pad_prompt(p, bucket)
        _, row = prefill(params, ids, model.create_cache(1, bucket))
        slots.insert_prefill(row, p, s, jnp.zeros((2,), jnp.uint32), [],
                             offset=s // N * N)
    return slots


#: Which positions of a block stand revealed after each denoise pass:
#: the sequential schedule's prefix, and a pattern only a
#: confidence-ordered schedule leaves.
PATTERNS = {"sequential": [(), (0, 1)], "by_confidence": [(), (1, 3)]}


@pytest.mark.parametrize("pattern", sorted(PATTERNS))
def test_block_pass_logits_match_reference_at_every_denoise_state(
        system, pattern):
    """Two rows in one batch — a prompt of whole blocks (r = 0) and one
    with a tail (r = 3) — through three consecutive blocks: the two
    denoise states of each, teacher-forced, K/V through the pages, the
    finished block riding in front of the next block's first pass (its
    commit) and a dead front half in the second; the logits of all
    four positions of the block in flight against the reference's full
    forward over the sequence as it stands."""
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 255, n).tolist() for n in (12, 23)]
    teacher = [rng.integers(0, 255, 3 * N).tolist() for _ in prompts]
    slots = _paged(system, prompts)
    decode = jax.jit(system.model.make_paged_decode_fn(16))
    active = jnp.ones((2,), bool)
    done = [list(p[:len(p) // N * N]) for p in prompts]
    tails = [p[len(p) // N * N:] for p in prompts]
    worst, ctrl = [], []
    before = None
    for blk in range(3):
        full = []
        for b in range(2):
            fill = teacher[b][blk * N:(blk + 1) * N]
            tail = tails[b] if blk == 0 else []
            full.append(list(tail) + fill[len(tail):])
        for step, shown in enumerate(PATTERNS[pattern]):
            folded = step == 0 and blk > 0
            fed = []
            for b in range(2):
                keep = [j in shown or (blk == 0 and j < len(tails[b]))
                        for j in range(N)]
                fed.append([t if k else MASK
                            for t, k in zip(full[b], keep)])
            for b in range(2):
                assert slots.ensure(b, len(done[b]) + N)
            slots.flush()
            logits, slots.cache = decode(
                system.params, jnp.asarray(
                    [(before[b] if folded else [MASK] * N) + fed[b]
                     for b in range(2)], jnp.int32), slots.cache,
                active, jnp.full((2,), folded))
            assert logits.shape == (2, N, 256)
            if folded:
                # the pass has written the finished block: the cursor
                # moves on
                slots.cache = dataclasses.replace(
                    slots.cache, offset=slots.cache.offset + N)
            for b in range(2):
                state = done[b] + fed[b]
                ref = _state_logits(state, len(done[b]), N)
                ok, err = _close(logits[b], ref)
                assert ok, (pattern, blk, shown, b, err)
                worst.append(err.max())
                low = _state_logits(state, len(done[b]), N, "fp8")
                ctrl.append(np.abs(low - ref).max(axis=-1))
        for b in range(2):
            done[b] += full[b]
        before = full
    # the control: float8 fails both halves of the tolerance
    ctrl = np.concatenate(ctrl)
    assert np.median(ctrl) > LOGIT_TOL and (ctrl > LOGIT_TOL).sum() \
        > FLIPS, ctrl
    assert system.model.STATS == MOE_STATS
    pairs, hit, load = np.asarray(slots.cache.stats)
    # (every row fed is counted, a dead half's too)
    assert pairs == 2 * 2 * N * 4 * 2 and 1 <= hit <= 32 and 0 < load <= 1


def test_one_folded_pass_is_the_commit_and_the_next_blocks_first_pass(
        system):
    """After a block's denoise passes its pages hold the K/V of a
    half-masked block.  ONE pass with the finished block in front of
    the next block leaves the pages what a pass of the finished block
    alone (a commit with a pass of its own) leaves them, and gives the
    next block the logits that a pass after such a commit gives it —
    the reference's over the clean sequence; without any commit they
    are wrong."""
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, 255, 16).tolist()
    block = rng.integers(0, 255, N).tolist()
    decode = jax.jit(system.model.make_paged_decode_fn(16))
    active, no = jnp.ones((1,), bool), jnp.zeros((1,), bool)
    dead, masked = [MASK] * N, [MASK] * N
    ref = _state_logits(prompt + block + masked, 16 + N, N)
    errs, logits, rows = {}, {}, {}

    def run(slots, front, back, folded):
        out, slots.cache = decode(
            system.params, jnp.asarray([front + back], jnp.int32),
            slots.cache, active, active if folded else no)
        return np.asarray(out[0])

    def move_on(slots):
        slots.cache = dataclasses.replace(
            slots.cache, offset=slots.cache.offset + N)

    for how in ("folded", "apart", "never"):
        slots = _paged(system, [prompt])
        assert slots.ensure(0, 16 + 2 * N)
        slots.flush()
        run(slots, dead, [block[0], block[1], MASK, MASK], False)
        if how == "folded":
            logits[how] = run(slots, block, masked, True)
        else:
            if how == "apart":
                run(slots, dead, block, False)
            move_on(slots)
            logits[how] = run(slots, dead, masked, False)
        errs[how] = np.abs(logits[how] - ref).max()
        page = int(slots._table[0][1])              # positions 16..31
        rows[how] = [np.asarray(pool[li][page, :, :2 * N], np.float32)
                     for pool in (slots.cache.ks, slots.cache.vs)
                     for li in range(2)]
    assert errs["folded"] < LOGIT_TOL and errs["apart"] < LOGIT_TOL, errs
    assert errs["never"] > 2 * LOGIT_TOL, errs
    # the finished block's rows AND the next block's provisional ones
    for a, c in zip(rows["folded"], rows["apart"]):
        assert np.abs(a - c).max() < 1e-2, np.abs(a - c).max()
    assert any(np.abs(a[:, :N] - c[:, :N]).max() > 0.05
               for a, c in zip(rows["folded"], rows["never"]))
    assert np.abs(logits["folded"] - logits["apart"]).max() < LOGIT_TOL / 4


# ---------------------------------------------------------------------------
# the block pass: phases, reveal, delivery
# ---------------------------------------------------------------------------

def _fake_decode(logits):
    """A model half that returns given logits and leaves what it got
    in the cache."""
    def decode(params, tokens, cache, active, folded):
        return logits, dataclasses.replace(cache, got=(tokens, folded))
    return decode


@dataclasses.dataclass
class _Cursor:
    offset: object
    got: object = None


jax.tree_util.register_dataclass(_Cursor, ["offset", "got"], [])


def test_the_reveal_order_under_low_confidence_static_is_by_probability():
    """On given logits the pass reveals the masked positions of the
    block in flight whose arg-max is most probable (ties to the left),
    never a revealed one, and `sequential` the leftmost.  A row with a
    finished block in front is folded: the model half is told so, its
    cursor moves on and its front half is dead afterwards; a block that
    the pass finishes moves to the front and leaves the next one all
    masked; a dead row keeps everything."""
    rng = np.random.default_rng(5)
    logits = jnp.asarray(rng.normal(size=(5, N, 16)) * 3, jnp.float32)
    prob = np.asarray(jax.nn.softmax(logits, -1).max(-1))
    best = np.asarray(logits.argmax(-1))
    blk = np.zeros((5, 2, 2 * N), np.int32)
    blk[0, :, N] = (9, 1)                     # row 0: position 0 shown
    blk[2, :, :N] = [[5, 6, 7, 8], [1, 1, 1, 1]]    # row 2: folded
    blk[3, :, :N] = [[5, 6, 7, 8], [1, 1, 1, 1]]    # row 3: not active
    blk[4, :, N:N + 2] = [[3, 4], [1, 1]]     # row 4: finishes now
    args = (jnp.asarray(blk), _Cursor(jnp.asarray([8, 8, 8, 8, 8])),
            jnp.zeros_like(blk), jnp.zeros(5, bool),
            jnp.asarray([True, True, True, False, True]),
            jnp.asarray([2, 3, 2, 2, 2], jnp.int32))
    for how in ("low_confidence_static", "sequential"):
        out, cache = make_block_pass_fn(
            _fake_decode(logits), N, MASK, how, donate=False)(None, *args)
        out = np.asarray(out)
        fed, folded = map(np.asarray, cache.got)
        assert list(folded) == [0, 0, 1, 0, 0]
        assert list(fed[2]) == [5, 6, 7, 8] + [MASK] * N
        assert list(fed[0]) == [MASK] * N + [9] + [MASK] * 3
        for row, k in ((0, 2), (1, 3), (2, 2)):
            masked = np.flatnonzero(blk[row, 1, N:] == 0)
            order = (masked if how == "sequential" else
                     masked[np.argsort(-prob[row, masked], kind="stable")])
            want = sorted(order[:k])
            new = np.flatnonzero(out[row, 1, N:]
                                 & ~blk[row, 1, N:].astype(bool))
            assert list(new) == want, (how, row)
            assert (out[row, 0, N + new] == best[row, new]).all()
            assert (out[row, 1, :N] == 0).all()
        assert out[0, 0, N] == 9 and out[0, 1, N] == 1
        assert (out[3] == blk[3]).all()
        assert list(out[4, 0, :N]) == [3, 4, best[4, 2], best[4, 3]]
        assert (out[4, 1] == [1] * N + [0] * N).all()
        assert list(np.asarray(cache.offset)) == [8, 8, 12, 8, 8]


def _run(system, prompts, new, **kw):
    sched = ContinuousBatchingScheduler(
        system.model, system.params, SchedulerConfig(
            num_slots=kw.pop("slots", 3), max_seq=128, kv_layout="paged",
            **kw))
    seen = {}
    reqs = [Request(p, n, eos_token_ids=(), on_token=lambda r, t:
                    seen.setdefault(r.request_id, []).append(
                        (len(r.generated), t)))
            for p, n in zip(prompts, new)]
    for r in reqs:
        assert sched.submit(r)
    return sched, reqs, seen


def _drain(sched):
    while sched.has_work():
        sched.step()


@pytest.mark.parametrize("which", ["system", "static_system"])
def test_tokens_are_delivered_in_position_order_each_once(which, request):
    """Both schedules, prompts with every tail, answers that are no
    multiple of the block: every request gets exactly its tokens, in
    order, each once; under `sequential` each of them is the
    reference's arg-max of the state it was revealed in (or within the
    tolerance of it)."""
    system = request.getfixturevalue(which)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, 255, n).tolist() for n in (8, 9, 14, 23)]
    new = [7, 10, 5, 8]
    sched, reqs, seen = _run(system, prompts, new)
    _drain(sched)
    for r, n in zip(reqs, new):
        assert r.finish_reason == FinishReason.LENGTH
        assert len(r.generated) == n
        assert seen[r.request_id] == list(zip(range(1, n + 1),
                                              r.generated))
    assert sched.slots.used_pages == sched.slots.cached_prefix_pages
    if which == "static_system":
        return
    sample = [{"prompt": p, "prompt_len": len(p), "tokens": r.generated,
               "ok": True, "index": i}
              for i, (p, r) in enumerate(zip(prompts, reqs))]
    res = correctness.score(reference, DIMS, SEED, sample, 128, 16)
    assert res["tokens"] == sum(new)
    assert res["program"]["served_gap_max"] < 0.2, res


def test_a_batch_whose_rows_are_in_different_phases_equals_each_alone(
        system):
    """Rows admitted at different steps run side by side in one
    program — a block's first pass, carrying the commit of the block
    before it, beside another row's second — and every request's
    stream is what it is served alone."""
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, 255, n).tolist() for n in (10, 16, 21)]
    new = [9, 12, 6]
    alone = []
    for p, n in zip(prompts, new):
        sched, reqs, _ = _run(system, [p], [n])
        _drain(sched)
        alone.append(reqs[0].generated)
    sched = ContinuousBatchingScheduler(
        system.model, system.params, SchedulerConfig(
            num_slots=3, max_seq=128, kv_layout="paged"))
    reqs = [Request(p, n, eos_token_ids=()) for p, n in zip(prompts, new)]
    phases = set()
    for r in reqs:
        assert sched.submit(r)
        sched.step()
        sched.step()
        live = sched._by_slot.values()
        phases.add(tuple(sorted((q.block_masked, q.block_pending)
                                for q in live)))
    _drain(sched)
    assert [r.generated for r in reqs] == alone
    assert any(len(set(p)) > 1 for p in phases), phases
    assert any(pending for p in phases for _, pending in p), phases


@pytest.mark.parametrize("news,n_end", [((3, 10, 10), 1),
                                        ((5, 3, 10), 2)])
def test_a_pass_delivers_every_rows_tokens_before_it_retires_a_row(
        system, monkeypatch, news, n_end):
    """`_commit_block`: in the pass that ends the first row (the first
    two) of the loop, the rows behind have the tokens it revealed
    before `slots.release` is first called, releases come in the loop's
    order with the read's own `now`, the freed slot goes to the request
    that waited, and every stream is what it is served alone.  (Rows
    are admitted a call apart: where one ends, the third is admitted by
    the call whose early read ends it.)"""
    from tests.test_serving_pipeline import (
        CommitLog, assert_delivered_then_retired)
    rng = np.random.default_rng(12)
    sched = system.sched
    prompts = [rng.integers(0, 255, n).tolist() for n in (8, 12, 16, 9)]
    news = news + (6,)

    def serve(which, on_token=None):
        reqs = []
        for i in which:
            req, why = system.submit(prompts[i], news[i], 0.0, on_token)
            assert req is not None, why
            reqs.append(req)
        return reqs

    alone = []
    for i in range(4):
        (req,) = serve([i])
        _drain(sched)
        alone.append(req.generated)
    log = CommitLog(sched, monkeypatch)
    reqs = serve(range(4), log.on_token)
    while reqs[0].finish_reason is None:
        out, events, admitted = log.step()
    assert out == {"admitted": 2 - n_end, "active": 3, "retired": n_end}
    assert admitted == reqs[2:4 - n_end]
    assert_delivered_then_retired(events, reqs[:1 + n_end],
                                  [r.slot for r in reqs[:n_end]])
    for r in reqs[:n_end]:
        assert r.finish_reason == FinishReason.LENGTH
        assert r.t_finish == r.t_last_token == reqs[n_end].t_last_token
    _, _, admitted = log.step()
    assert admitted == reqs[3:] and reqs[3].slot == reqs[0].slot
    _drain(sched)
    assert [r.generated for r in reqs] == alone


@pytest.fixture
def metrics():
    from triton_distributed_tpu.observability import get_registry
    reg = get_registry()
    reg.clear()
    yield reg
    reg.clear()


def test_pipelined_streams_equal_the_serial_loops(system, metrics):
    """The dispatch of pass t+1 goes out before pass t is read (the
    overlap counter rises), and the streams are those of a loop that
    reads every pass before the next."""
    rng = np.random.default_rng(8)
    prompts = [rng.integers(0, 255, n).tolist() for n in (9, 17, 30)]
    new = [11, 6, 9]
    sched, reqs, _ = _run(system, prompts, new)
    _drain(sched)
    snap = metrics.snapshot()["counters"]
    # (the three are admitted a call apart — one piece a pass — and a
    # call that admits behind a pass in flight reads it early)
    assert snap["serving_decode_overlapped_total"] > 3
    assert 'serving_diffusion_passes_total{phase="commit"}' not in snap
    assert snap['serving_diffusion_passes_total{phase="folded"}'] > 0
    assert (snap["serving_diffusion_blocks_committed_total"]
            == snap['serving_diffusion_passes_total{phase="folded"}'])
    assert (snap["serving_diffusion_tokens_revealed_total"]
            >= sum(new))
    serial, sreqs, _ = _run(system, prompts, new)
    while serial.has_work():
        serial.step()
        if serial._flight is not None:
            serial._read(serial._take_flight())
    assert [r.generated for r in sreqs] == [r.generated for r in reqs]


def test_a_full_block_costs_its_denoise_steps_in_passes(system, metrics):
    """Three whole blocks of two denoise steps each are six passes of
    the row — the first of the second and of the third block carry the
    commit of the block before them, the last block gets none — and
    the span says so: no pass is a commit alone, and a dead front half
    is not counted among the positions fed."""
    from triton_distributed_tpu.observability.tracing import get_tracer
    rng = np.random.default_rng(15)
    sched, reqs, _ = _run(system, [rng.integers(0, 255, 8).tolist()],
                          [3 * N])
    t0 = time.perf_counter()
    _drain(sched)
    assert len(reqs[0].generated) == 3 * N
    snap = metrics.snapshot()["counters"]
    assert snap['serving_diffusion_passes_total{phase="denoise"}'] == 4
    assert snap['serving_diffusion_passes_total{phase="folded"}'] == 2
    assert 'serving_diffusion_passes_total{phase="commit"}' not in snap
    assert snap["serving_diffusion_tokens_revealed_total"] == 3 * N
    spans = [sp.attrs for sp in get_tracer().finished()
             if sp.name == "serving.diffusion" and sp.t0 > t0]
    assert [a["rows_folded"] for a in spans] == [0, 0, 1, 0, 1, 0]
    assert all(a["rows_commit"] == 0 and a["rows_denoise"] == 1
               and a["blocks_committed"] == a["rows_folded"]
               and a["positions_fed"] == N * (1 + a["rows_folded"])
               for a in spans), spans
    assert int(sched.slots.cache.offset[0]) == 0        # released


def test_preempt_and_resume_mid_block(system):
    """The block in flight is dropped and redone from the tokens
    delivered — a finished block whose commit was still to come is
    among them, and the resume's prefill writes it: the resumed stream
    is the uninterrupted one."""
    rng = np.random.default_rng(12)
    prompts = [rng.integers(0, 255, n).tolist() for n in (9, 14)]
    sched, reqs, _ = _run(system, prompts, [16, 16])
    _drain(sched)
    straight = [r.generated for r in reqs]
    pending = []
    for at in (4, 5, 6):
        sched, reqs, _ = _run(system, prompts, [16, 16])
        for _ in range(at):
            sched.step()
        sched._read(sched._take_flight())
        had = len(reqs[1].generated)
        mid = reqs[1].block_masked
        pending.append(reqs[1].block_pending)
        sched._preempt(reqs[1].slot)
        _drain(sched)
        assert reqs[1].preemptions == 1 and 0 < had < 16
        assert [r.generated for r in reqs] == straight, (at, had, mid)
    assert True in pending and False in pending, pending


def test_a_finished_uncommitted_block_is_private_until_its_commit(system):
    """A finished block whose commit has not been dispatched is as
    provisional as the block in flight: its page is the request's
    alone, a preemption gives it back to the pool and not to the radix
    tree, and the resume — which prefills that block with the rest —
    continues the stream."""
    rng = np.random.default_rng(19)
    prompt = rng.integers(0, 255, 39).tolist()      # cursor 36: 2 pages
    sched, reqs, _ = _run(system, [prompt], [12], slots=2)
    _drain(sched)
    straight = reqs[0].generated
    sched, reqs, _ = _run(system, [prompt], [12], slots=2)
    sched.step()
    r = reqs[0]
    # its first pass, revealing the one masked position, is out: block
    # 36..39 is finished, the cursor still stands at it
    assert r.block_pending and (r.block_start, r.block_masked) == (40, N)
    assert int(sched.slots.cache.offset[r.slot]) == 36
    sched._read(sched._take_flight())
    assert len(r.generated) == 1
    own = int(sched.slots._table[r.slot][2])        # positions 32..47
    assert own in sched.slots._slot_pages[r.slot]
    assert sched.slots.cached_prefix_pages == 2
    free = sched.slots.pool.free_pages
    sched._preempt(r.slot)
    assert sched.slots.cached_prefix_pages == 2
    assert sched.slots.pool.free_pages == free + 1
    _drain(sched)
    assert r.preemptions == 1 and r.generated == straight


def test_a_blocks_provisional_rows_are_never_shared(system):
    """The radix cache holds whole committed prompt pages only: no
    node covers a position at or past a request's cursor, a second
    request with the same prompt shares those pages and no other, and
    both streams are the same."""
    rng = np.random.default_rng(9)
    prompt = rng.integers(0, 255, 39).tolist()      # cursor 36: 2 pages
    sched, reqs, _ = _run(system, [prompt], [6], slots=2)
    sched.step()
    assert sched.slots.cached_prefix_pages == 2
    assert int(sched.slots.cache.offset[reqs[0].slot]) == 36
    # (its first pass, revealing the one masked position, is out: the
    # block at the cursor is finished, its commit rides on the next)
    assert reqs[0].block_pending
    assert reqs[0].block_start == 40 and reqs[0].block_masked == N
    twin = Request(list(prompt), 6, eos_token_ids=())
    assert sched.submit(twin)
    sched.step()
    shared = set(sched.slots._table[reqs[0].slot][:2])
    assert set(sched.slots._table[twin.slot][:2]) == shared
    assert sched.slots._table[twin.slot][2] not in (
        0, sched.slots._table[reqs[0].slot][2])
    _drain(sched)
    assert twin.generated == reqs[0].generated


def test_what_is_refused(system, devices):
    cfg = system.model_cfg
    one = Mesh(np.array(devices[:1]), ("tp",))
    assert isinstance(AutoLLM(cfg, one), SdarMoe)
    with pytest.raises(AssertionError, match="one device"):
        SdarMoe(cfg, Mesh(np.array(devices[:2]), ("tp",)))
    with pytest.raises(AssertionError, match="cannot predict"):
        SdarMoe(dataclasses.replace(cfg, remasking="threshold"), one)
    with pytest.raises(ValueError, match="greedy"):
        ContinuousBatchingScheduler(
            system.model, system.params, SchedulerConfig(
                kv_layout="paged", max_seq=128, temperature=0.7))
    with pytest.raises(ValueError, match="paged"):
        ContinuousBatchingScheduler(
            system.model, system.params, SchedulerConfig(max_seq=128))
    # a request whose last BLOCK would pass the horizon
    sched = ContinuousBatchingScheduler(
        system.model, system.params, SchedulerConfig(
            kv_layout="paged", max_seq=64, prefill_buckets=(16, 32, 64)))
    assert sched.submit(Request(list(range(20)), 44))
    late = Request(list(range(21)), 44)
    assert not sched.submit(late)
    assert late.reject_reason.value == "exceeds_kv_capacity"


def test_the_published_names_load_into_the_programs_tree(system):
    """`load_state_dict`: the served weights written out under the
    published names — `(out, in)` projections, q, k and v apart, one
    entry an expert — load back into the tree they came from."""
    f32 = lambda a: np.asarray(a, np.float32)       # noqa: E731
    sd = {}
    for i, lp in enumerate(system.params["layers"]):
        q, k, v = np.split(f32(lp["attn"]["wqkv"]), [128, 144], axis=1)
        flat = {"q": q.T, "k": k.T, "v": v.T, "o": f32(lp["attn"]["wo"]).T,
                "q_norm": f32(lp["attn"]["q_norm"]),
                "k_norm": f32(lp["attn"]["k_norm"]),
                "ln1": f32(lp["ln1"]), "ln2": f32(lp["ln2"]),
                "router": f32(lp["mlp"]["router"]).T}
        for name, a in flat.items():
            sd[HF_LAYER_NAMES[name].format(i=i)] = a
        for name in ("gate", "up", "down"):
            for e in range(16):
                sd[HF_LAYER_NAMES[name].format(i=i, e=e)] = f32(
                    lp["mlp"][name][e]).T
    sd[HF_END_NAMES["embed"]] = f32(system.params["embed"])
    sd[HF_END_NAMES["ln_f"]] = f32(system.params["ln_f"])
    sd[HF_END_NAMES["lm_head"]] = f32(system.params["lm_head"]).T
    assert sd["model.layers.1.mlp.experts.3.down_proj.weight"].shape == (
        128, 64)
    got = system.model.load_state_dict(sd)
    flat = jax.tree.leaves_with_path(system.params)
    assert len(flat) == len(jax.tree.leaves(got))
    for (path, want), have in zip(flat, jax.tree.leaves(got)):
        assert want.dtype == have.dtype and (
            np.asarray(want) == np.asarray(have)).all(), path


def test_the_other_families_programs_are_what_they_were(devices):
    """No block, no flag, no new argument reaches them: the dense
    family's prefill is `causal_block` 0 (the same jaxpr with the
    option spelled out), its paged step feeds one query a head, and
    the scheduler steps it through the masked step; a chunk of a layer
    without a block is the call it was
    (`test_the_programs_beside_the_chunk_are_the_parents_text` holds
    the four families' lowered chunk programs to the parent's)."""
    from triton_distributed_tpu.models.qwen import Qwen3
    mesh = Mesh(np.array(devices[:1]), ("tp",))
    model = Qwen3(ModelConfig.tiny(), mesh)
    assert model.attn.block == 0
    q = jnp.zeros((1, 8, 64, 16), jnp.bfloat16)
    k = jnp.zeros((1, 4, 64, 16), jnp.bfloat16)
    a = jax.make_jaxpr(lambda q, k: flash_attention(
        q, k, k, causal=True, interpret=True))(q, k)
    b = jax.make_jaxpr(lambda q, k: flash_attention(
        q, k, k, causal=True, causal_block=0, interpret=True))(q, k)
    assert str(a) == str(b)
    params = model.init_params(jax.random.key(0))
    sched = ContinuousBatchingScheduler(model, params, SchedulerConfig(
        num_slots=2, max_seq=64, kv_layout="paged"))
    assert sched._block == 0 and sched.slots.block == 0
    r = Request(list(range(1, 10)), 5)
    assert sched.submit(r)
    _drain(sched)
    assert len(r.generated) == 5 and r.block_start is None
    for tiny in (ModelConfig.tiny_glm4_moe_lite(),
                 ModelConfig.tiny_solar_open2()):
        other = AutoLLM(tiny, mesh)
        assert other.moe.scoring == "sigmoid" and other.moe.n_shared == 1
        assert sorted(other.moe.param_specs()) == [
            "down", "gate", "router", "router_bias", "shared", "up"]
        assert getattr(other, "block_length", 0) == 0


#: sha256 of the lowered text of the programs PR 49 could have moved
#: and did not, at `tests/test_model_protocol.py`'s test size, AS THE
#: PARENT OF PR 49 (9afc788) LOWERED THEM: the chunk programs of the
#: four families that call `TPAttention.prefill_suffix` with no block
#: (the same call, argument for argument), and this family's own block
#: pass and whole prefill, which the benchmark reads by name — hashed
#: from a `git clone` of the parent (PERF.md section 6, PR 49;
#: `cohere2_moe`'s is `tests/test_smallthinker.py`'s of PR 48).
PARENT_PROGRAMS = {
    "solar_open2.suffix":
    "8bc9ffa60f8df74ac66680fcb1a85cfafbd41f56918b7708f03c86eaf0f43bc2",
    "nemotron_h.suffix":
    "4096f90ba9262136504cbafc5294810370d70ddc09cc17f076da616775b850d9",
    "cohere2_moe.suffix":
    "d43751aa81c1bd73d80c2180839e0670f6416019a4e897a58b3d5aa8ae3296e9",
    "smallthinker.suffix":
    "6186fc1ab653260b0ff3dd342c263c0ae3c326d0dce7f8e9d31ed0dfd27cebb1",
    "sdar_moe.block_pass":
    "360ccdf89cb19f3f2bec115e5a052b1c385509f3c64cfb8a4aae3d38671f01be",
    "sdar_moe.prefill":
    "322bba7a047e335ae2ed6af2385c0225834a5c61caa9e7f6dcb759261b1abdb4"}


def _lowered_programs(family, devices):
    """name -> the family's lowered programs at the protocol test's
    size and chunk."""
    from tests import test_model_protocol as protocol
    ch = protocol.CHUNK
    model = protocol._model(family, devices, chunk=ch)
    params = jax.eval_shape(model.init_params, jax.random.key(0))
    pool = jax.eval_shape(lambda: model.create_paged_cache(2, 9, 16, 4))
    if family == "sdar_moe":
        n = model.block_length
        blk = jnp.zeros((2, 2, 2 * n), jnp.int32)
        flag = jnp.zeros((2,), bool)
        return {
            "block_pass": make_block_pass_fn(
                model.make_paged_decode_fn(16), n,
                model.config.mask_token_id, model.config.remasking).lower(
                    params, blk, pool, blk, flag, flag,
                    jnp.zeros((2,), jnp.int32)),
            "prefill": jax.jit(model.make_prefill_fn()).lower(
                params, jnp.zeros((1, 2 * ch), jnp.int32),
                jax.eval_shape(lambda: model.create_cache(1, 2 * ch)))}
    pools, pages = (pool.ks, pool.vs), jnp.zeros((4,), jnp.int32)
    if model.window:
        pools, pages = (*pools, pool.wks, pool.wvs), jnp.stack([pages] * 2)
    return {"suffix": jax.jit(model.make_prefill_suffix_fn()).lower(
        params, jnp.zeros((1, ch), jnp.int32), jnp.int32(ch),
        jax.eval_shape(lambda: model.create_cache(1, ch)), pools, pages)}


@pytest.mark.parametrize("family", sorted(
    {k.split(".")[0] for k in PARENT_PROGRAMS}))
def test_the_programs_beside_the_chunk_are_the_parents_text(family,
                                                            devices):
    """The mask of `TPAttention.prefill_suffix` is chosen from the
    layer's own ``block``: a family built without one lowers to the
    text it had, and the block model's pass and whole prefill — what
    `decode_step_ms`, `prefill_ms` and the rooflines read — are
    untouched by its new chunk program."""
    import hashlib
    got = {f"{family}.{k}": hashlib.sha256(
        v.as_text().encode()).hexdigest()
        for k, v in _lowered_programs(family, devices).items()}
    assert got == {k: v for k, v in PARENT_PROGRAMS.items()
                   if k.startswith(family + ".")}, got


# ---------------------------------------------------------------------------
# the harness's contract with the reference, on the CPU
# ---------------------------------------------------------------------------

def test_the_adapter_and_the_score_end_to_end_at_tiny_sizes(system):
    """What `cellbench/run.py` does with a cell of this family —
    submit, step, `finished_ok`, `used_pages`, then
    `correctness.score` over what was served — walked at the tiny
    preset (`--rehearse` cannot overlay tiny keys on a new family):
    `logits_at` row k is the served token k's state, prompts with and
    without a tail, and the float8 control comes out apart."""
    rng = np.random.default_rng(21)
    rows = []
    for i, (plen, new) in enumerate(((16, 12), (21, 9), (34, 16),
                                     (7, 5), (18, 7))):
        times = []
        prompt = rng.integers(0, 256, plen).tolist()   # mask id allowed
        handle, why = system.submit(
            prompt, new, 0.0, lambda r, t, times=times: times.append(t))
        assert handle is not None, why
        rows.append((i, prompt, new, handle, times))
    peak = 0
    while system.has_work():
        out = system.step()
        assert set(out) >= {"admitted", "active", "retired"}
        peak = max(peak, system.used_pages())
    assert 0 < peak <= system.usable_pages
    sample = []
    for i, prompt, new, handle, times in rows:
        assert system.finished_ok(handle, new)
        assert times == handle.generated
        sample.append({"index": i, "prompt": prompt,
                       "prompt_len": len(prompt),
                       "tokens": list(handle.generated), "ok": True})
    picked = correctness.pick_sample(sample, SEED, 4)
    assert len(picked) == 4 and picked[0]["index"] == 2
    res = correctness.score(reference, DIMS, SEED, sample, 128, 16,
                            control=True)
    assert res["requests"] == 5 and res["tokens"] == 49
    # limits between the two readings (measured: the program 0.013 /
    # 0.00026, the float8 control 0.050 / 0.0027)
    limits = {"served_gap_max": 0.03, "served_gap_mean": 0.001}
    ok, lines = correctness.judge(res["program"], limits)
    assert ok, lines
    bad, lines = correctness.judge(res["control"], limits)
    assert not bad and not lines[1]["within"], res
    assert reference.fp8_change(DIMS, SEED) > 0.01
