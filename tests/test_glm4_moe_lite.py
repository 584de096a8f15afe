"""GLM-4-MoE-Lite family on the CPU at tiny sizes: the program
(`models.glm4_moe_lite.Glm4MoeLite` — latent attention over a paged
latent cache, a dropless expert layer; Pallas kernels in interpret
mode) against the plain float32 reference
(`cellbench.references.glm4_moe_lite`, which imports nothing of the
program), on seeded weights laid in by the benchmark's own adapter
code.

Tolerances.  The program computes in bfloat16 with float32
accumulation; the reference in float32.  At these sizes (3 layers,
hidden 128) the logits' own spread is 1.0, and bfloat16 rounding of
the activations leaves the program's worst logit of a position a
median 0.025 from the reference's (0.023-0.031 over six sequences,
measured), under `LOGIT_TOL` = 0.08 at every position but those where
the rounding flips a routing near-tie: the token then sees another
fourth expert and its logits move by 0.5-1.0 (measured: 0 or 1 of a
sequence's 34 positions, never 2).  So a sequence passes with at most
`FLIPS` = 2 positions past the tolerance.  The same comparison on the
reference's float8 control (`precision="fp8"`) reads 0.18-0.34 at
EVERY position and is checked to FAIL.  The router itself is held to
exact agreement of its choice with the reference's on the same input,
which a bfloat16 router is checked to break.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from cellbench.adapters import glm4_moe_lite as adapter
from cellbench.references import glm4_moe_lite as reference
from triton_distributed_tpu.kernels import moe_utils
from triton_distributed_tpu.kernels.mla_decode import (
    mla_decode_paged, mla_decode_reference)
from triton_distributed_tpu.layers.mla_attn import MLAttention
from triton_distributed_tpu.layers.moe_mlp import MOE_STATS, SparseMoE
from triton_distributed_tpu.models import AutoLLM, ModelConfig, Qwen3
from triton_distributed_tpu.models.glm4_moe_lite import Glm4MoeLite
from triton_distributed_tpu.models.kv_cache import KVCache, PagedKVCache
from triton_distributed_tpu.serving import Request
from triton_distributed_tpu.serving.engine_batched import (
    pad_prompt, pick_bucket)
from triton_distributed_tpu.serving.pages import PagedKV

LOGIT_TOL = 0.08
FLIPS = 2

#: The published `config.json` keys at test size: the ratios kept (one
#: leading dense layer, top-4 beside one shared expert, rope a quarter
#: of the head, nope + rope = v), widths the CPU walks.
TINY = {
    "model_type": "glm4_moe_lite", "vocab_size": 256, "hidden_size": 128,
    "intermediate_size": 256, "moe_intermediate_size": 128,
    "num_hidden_layers": 3, "num_attention_heads": 4,
    "num_key_value_heads": 4, "q_lora_rank": 64, "kv_lora_rank": 128,
    "qk_nope_head_dim": 48, "qk_rope_head_dim": 16, "v_head_dim": 64,
    "n_routed_experts": 8, "n_shared_experts": 1,
    "num_experts_per_tok": 4, "first_k_dense_replace": 1,
    "routed_scaling_factor": 1.8, "norm_topk_prob": True, "n_group": 1,
    "topk_group": 1, "rms_norm_eps": 1e-5, "rope_theta": 1e6,
    "tie_word_embeddings": False, "torch_dtype": "bfloat16",
    "serving": {"num_slots": 2, "max_seq": 128,
                "kv_budget_bytes_per_chip": 2 * 128 * 3 * 256 * 2,
                "max_queue": 16},
}
SEED = 7


@pytest.fixture(scope="module")
def system(devices):
    """The benchmark's adapter at test size: the program with the
    reference's weights, behind its scheduler."""
    return adapter.System(TINY, SEED, devices[:1])


def _ref_logits(tokens, first, n_out, precision="f32", seed=SEED):
    dims = reference.dims_of(TINY)
    pad = np.zeros(128, np.int64)
    pad[:len(tokens)] = tokens
    return np.asarray(reference.logits_at(dims, seed, pad, first, n_out,
                                          precision=precision))


def _serve_logits(system, prompts, steps, teacher):
    """Prefill each prompt through the bucketed prefill, insert it into
    the paged latent pool, then ``steps`` masked-free decode steps of
    the whole batch, feeding ``teacher[b][i]`` at step i > 0: the
    serving path's own artefacts, logits kept.  Returns
    (steps, B, vocab)."""
    model, params = system.model, system.params
    slots = PagedKV(model, len(prompts), max_seq=128, page_size=16,
                    prefix_cache=False)
    prefill = jax.jit(model.make_prefill_fn())
    decode = jax.jit(model.make_paged_decode_fn(page_size=16))
    for p in prompts:
        bucket = pick_bucket(len(p), (16, 32, 64, 128))
        ids, s = pad_prompt(p, bucket)
        _, row = prefill(params, ids, model.create_cache(1, bucket))
        slots.insert_prefill(row, p, s, jnp.zeros((2,), jnp.uint32), [])
    out = []
    tokens = np.asarray([p[-1] for p in prompts], np.int32)
    for i in range(steps):
        for b, p in enumerate(prompts):
            assert slots.ensure(b, len(p) + i)
        slots.flush()
        logits, slots.cache = decode(params, jnp.asarray(tokens),
                                     slots.cache)
        out.append(np.asarray(logits))
        tokens = np.asarray([t[i] for t in teacher], np.int32)
    return np.stack(out), slots


# ---------------------------------------------------------------------------
# program against reference
# ---------------------------------------------------------------------------

def test_prefill_logits_match_reference(system):
    """A prompt that fills its bucket: the prefill program's own
    logits (last position) against the reference's."""
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, 256, 32).tolist()
    ids, _ = pad_prompt(prompt, 32)
    logits, _ = jax.jit(system.model.make_prefill_fn())(
        system.params, ids, system.model.create_cache(1, 32))
    ref = _ref_logits(prompt, 31, 1)
    assert np.abs(np.asarray(logits) - ref).max() < LOGIT_TOL


@pytest.fixture(scope="module")
def decoded(system):
    """Two requests of different lengths in one batch, 34 decode steps
    through the paged latent cache (crossing two page boundaries),
    teacher-forced with seeded tokens."""
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 256, n).tolist() for n in (21, 50)]
    steps = 34
    teacher = [rng.integers(0, 256, steps).tolist() for _ in prompts]
    got, slots = _serve_logits(system, prompts, steps, teacher)
    return prompts, teacher, steps, got, slots


@pytest.mark.parametrize("row", [0, 1])
def test_decode_logits_match_reference(decoded, row):
    prompts, teacher, steps, got, _ = decoded
    p = prompts[row]
    seq = p + teacher[row][:steps - 1]
    ref = _ref_logits(seq, len(p) - 1, steps)
    err = np.abs(got[:, row] - ref).max(axis=1)
    assert np.median(err) < LOGIT_TOL / 2, err
    assert (err > LOGIT_TOL).sum() <= FLIPS, err


@pytest.mark.parametrize("row", [0, 1])
def test_float8_control_fails_the_tolerance(decoded, row):
    """The tolerance would catch a lower precision: the reference's own
    float8 control lies outside it."""
    prompts, teacher, steps, got, _ = decoded
    p = prompts[row]
    seq = p + teacher[row][:steps - 1]
    low = _ref_logits(seq, len(p) - 1, steps, precision="fp8")
    ref = _ref_logits(seq, len(p) - 1, steps)
    err = np.abs(low - ref).max(axis=1)
    assert (err > LOGIT_TOL).all() and np.median(err) > 2 * LOGIT_TOL


def test_router_agrees_with_the_reference_and_bf16_would_not(system):
    """On the same float32 input the program's router and the
    reference's choose the same four experts for EVERY token and give
    them the same weights; the tolerance is exact agreement of the
    choice.  A router computed in bfloat16 breaks it: near-ties flip
    (measured: ~3% of 2048 tokens)."""
    moe = system.model.moe
    p = system.params["layers"][1]["mlp"]
    dims = reference.dims_of(TINY)
    x = jax.random.normal(jax.random.key(11), (2048, 128), jnp.float32)
    dense = np.asarray(reference.router_weights(
        x, {"router": p["router"], "e_bias": p["router_bias"]}, dims))
    ids, w = (np.asarray(a) for a in moe.route(x, p))
    assert ((dense > 0).sum(axis=1) == 4).all()
    np.testing.assert_allclose(np.take_along_axis(dense, ids, 1), w,
                               rtol=1e-5)

    s = jax.nn.sigmoid(jnp.dot(x.astype(jnp.bfloat16),
                               p["router"].astype(jnp.bfloat16)))
    _, low = jax.lax.top_k(s + p["router_bias"].astype(jnp.bfloat16), 4)
    flipped = (np.take_along_axis(dense, np.asarray(low), 1) == 0).any(1)
    assert flipped.sum() >= 10, flipped.sum()


def test_decode_leaves_expert_counts_in_the_cache(decoded):
    """`PagedKVCache.stats` after a step: pairs = rows x top-k x sparse
    layers, experts hit within its bounds, the busiest expert's share
    at least an even one."""
    *_, slots = decoded
    pairs, hit, load = np.asarray(slots.cache.stats)
    assert MOE_STATS == ("pairs", "experts_hit", "expert_load_max")
    assert pairs == 2 * 4 * 2
    assert 2 * 4 <= hit <= 2 * 8
    assert 4 / 8 / 4 <= load <= 1.0


def test_scheduler_serves_it_and_counts(system):
    """Through `ContinuousBatchingScheduler` itself: greedy tokens whose
    reference logit lies within the tolerance of the reference's best
    (the benchmark's `correct`, on the CPU), and the `serving.moe`
    span carrying the step's counters."""
    from triton_distributed_tpu.observability.tracing import get_tracer
    rng = np.random.default_rng(3)
    reqs = [Request(rng.integers(0, 256, n).tolist(), 20,
                    eos_token_ids=(), seed=0) for n in (9, 40, 17)]
    # (the ring is the process's: another file's schedulers, run before
    # this one by the same worker, leave `serving.moe` spans of theirs)
    seen = {id(s) for s in get_tracer().finished()}
    for r in reqs:
        assert system.sched.submit(r)
    while system.sched.has_work():
        system.sched.step()
    for r in reqs:
        assert len(r.generated) == 20
        seq = list(r.prompt) + list(r.generated)
        ref = _ref_logits(seq, r.prompt_len - 1, 20)
        gap = ref.max(axis=1) - ref[np.arange(20), r.generated]
        # greedy in exact arithmetic serves the reference's best (gap
        # 0); rounding flips near-ties (small gaps) and, rarely, a
        # routing choice (module docstring)
        assert gap.mean() < LOGIT_TOL / 2, gap
        assert (gap > LOGIT_TOL).sum() <= FLIPS, gap
    spans = [s for s in get_tracer().finished()
             if s.name == "serving.moe" and id(s) not in seen]
    assert spans and all(
        s.attrs["pairs"] == 2 * 4 * 2 and s.attrs["experts_hit"] >= 8
        for s in spans)


# ---------------------------------------------------------------------------
# latent attention
# ---------------------------------------------------------------------------

def _attn(mode="fused"):
    return MLAttention(hidden=128, num_heads=4, q_rank=64, lat=128,
                       nope=48, rope=16, v_dim=64, mode=mode)


@pytest.mark.parametrize("mode", ["fused", "xla"])
def test_absorbed_decode_equals_nonabsorbed_attention(mode):
    """Prefill (non-absorbed: keys and values expanded per head) of S
    tokens, against prefill of S - 1 then ONE absorbed decode step over
    the cached latent rows: the same output for the last token."""
    attn = _attn(mode)
    p = jax.tree.map(lambda a: a.astype(jnp.float32),
                     attn.init_params(jax.random.key(0)))
    s, ps = 40, 16
    x = jax.random.normal(jax.random.key(1), (s, 128), jnp.float32)
    full, rows = attn.prefill(x, p, 1)
    # the first s-1 rows into pages 1..3 of a pool, shuffled
    table = np.asarray([[3, 1, 2]], np.int32)
    pool = jnp.zeros((4, 1, ps, attn.row_width), jnp.float32)
    padded = jnp.zeros((3 * ps, attn.row_width)).at[:s - 1].set(
        rows[0, 0, :s - 1])
    pool = pool.at[table[0]].set(padded.reshape(3, 1, ps, -1))
    out, pool2 = attn.decode_paged(x[s - 1:], p, pool, jnp.asarray(table),
                                   jnp.asarray([s - 1], jnp.int32))
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(full[-1]),
                               rtol=2e-4, atol=2e-4)
    # and the step wrote the row prefill would have cached
    np.testing.assert_allclose(
        np.asarray(pool2[2, 0, (s - 1) % ps]), np.asarray(rows[0, 0, -1]),
        rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("lens,t", [((1, 16, 17, 100), 8),
                                    ((0, 48, 33, 7), 8),
                                    ((513, 1100, 1, 1024), 72)])
def test_mla_decode_kernel_reads_nothing_past_a_rows_length(lens, t):
    """Pages at and past a row's length may map anywhere and hold
    anything (NaN here); a row of length 0 returns zeros.  The last
    case walks several 512-row blocks (the double-buffered gather)."""
    b, h, lat, r, ps = 4, 4, 128, 256, 16
    key = jax.random.key(5)
    q = jax.random.normal(key, (b, h, r), jnp.float32)
    pool = jax.random.normal(jax.random.fold_in(key, 1),
                             (1 + b * t, 1, ps, r), jnp.float32)
    table = np.arange(1, 1 + b * t, dtype=np.int32).reshape(b, t)
    kv_len = jnp.asarray(lens, jnp.int32)
    ref = mla_decode_reference(q, pool, jnp.asarray(table), kv_len,
                               lat=lat, scale=0.125)
    poisoned = np.asarray(pool).copy()
    for i, n in enumerate(lens):
        live = -(-n // ps)
        poisoned[table[i, live:]] = np.nan
        table[i, live:] = 0                    # NULL page, also NaN
    poisoned[0] = np.nan
    out = mla_decode_paged(q, jnp.asarray(poisoned), jnp.asarray(table),
                           kv_len, lat=lat, scale=0.125)
    for i, n in enumerate(lens):
        if n == 0:
            assert not np.asarray(out[i]).any()
        else:
            np.testing.assert_allclose(np.asarray(out[i]),
                                       np.asarray(ref[i]),
                                       rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# the expert layer
# ---------------------------------------------------------------------------

def _moe(**kw):
    d = dict(hidden=128, ffn=128, num_experts=8, topk=4, n_shared=1,
             routed_scaling=1.8, norm_topk_prob=True)
    d.update(kw)
    return SparseMoE(**d)


def _f32(params):
    return jax.tree.map(lambda a: a.astype(jnp.float32), params)


@pytest.mark.parametrize("n", [3, 32, 160])
def test_routing_drops_nothing_when_every_token_picks_the_same_four(n):
    """The worst imbalance: a bias that sends every token to experts
    0-3.  Every pair is computed (fused = the masked dense golden)."""
    moe = _moe()
    p = _f32(moe.init_params(jax.random.key(0)))
    p["router_bias"] = jnp.asarray([9.0] * 4 + [0.0] * 4)
    x = jax.random.normal(jax.random.key(1), (n, 128), jnp.float32)
    ids, _ = moe.route(x, p)
    assert set(np.asarray(ids).ravel()) == {0, 1, 2, 3}
    y, stats = moe(x, p)
    gold, _ = dataclasses.replace(moe, mode="xla")(x, p)
    np.testing.assert_allclose(np.asarray(y), np.asarray(gold),
                               rtol=2e-4, atol=2e-4)
    assert tuple(np.asarray(stats)) == (4 * n, 4, 0.25)
    plan = moe_utils.pack_by_expert(ids, jnp.ones_like(ids, jnp.float32),
                                    8, 16)
    rows = np.asarray(plan.pair_row).ravel()
    assert len(set(rows)) == 4 * n             # a row of its own each
    assert (np.asarray(plan.row_token)[rows]
            == np.repeat(np.arange(n), 4)).all()


def test_selection_bias_changes_the_choice_and_never_the_weights():
    moe = _moe()
    p = _f32(moe.init_params(jax.random.key(2)))
    x = jax.random.normal(jax.random.key(3), (64, 128), jnp.float32)
    p0 = dict(p, router_bias=jnp.zeros(8))
    ids0, w0 = moe.route(x, p0)
    bias = jnp.zeros(8).at[5].set(0.2)
    ids1, w1 = moe.route(x, dict(p, router_bias=bias))
    assert (np.asarray(ids0) != np.asarray(ids1)).any()
    # the weights are the UNBIASED scores of whatever was chosen
    s = jax.nn.sigmoid(x @ p["router"])
    picked = np.take_along_axis(np.asarray(s), np.asarray(ids1), 1)
    want = picked / picked.sum(1, keepdims=True) * 1.8
    np.testing.assert_allclose(np.asarray(w1), want, rtol=1e-5)
    # where the bias changed no choice, it changed nothing at all
    same = (np.asarray(ids0) == np.asarray(ids1)).all(axis=1)
    assert same.any()
    np.testing.assert_array_equal(np.asarray(w0)[same],
                                  np.asarray(w1)[same])


def test_shared_expert_counted_once():
    moe = _moe()
    p = _f32(moe.init_params(jax.random.key(4)))
    x = jax.random.normal(jax.random.key(5), (32, 128), jnp.float32)
    with_shared, _ = moe(x, p)
    without, _ = dataclasses.replace(moe, n_shared=0)(x, p)
    sh = p["shared"]
    g, u = jnp.split(x @ sh["gate_up"], 2, axis=1)
    once = (jax.nn.silu(g) * u) @ sh["down"]
    np.testing.assert_allclose(np.asarray(with_shared - without),
                               np.asarray(once), rtol=2e-4, atol=2e-4)


def test_scaling_factor_and_topk_normalisation_applied():
    moe = _moe()
    p = _f32(moe.init_params(jax.random.key(6)))
    x = jax.random.normal(jax.random.key(7), (16, 128), jnp.float32)
    _, w = moe.route(x, p)
    np.testing.assert_allclose(np.asarray(w.sum(1)), 1.8, rtol=1e-5)
    _, w1 = dataclasses.replace(moe, routed_scaling=1.0).route(x, p)
    np.testing.assert_allclose(np.asarray(w), 1.8 * np.asarray(w1),
                               rtol=1e-6)
    _, raw = dataclasses.replace(moe, norm_topk_prob=False,
                                 routed_scaling=1.0).route(x, p)
    assert (np.asarray(raw.sum(1)) > 1.0).all()   # four sigmoid scores
    # and the layer's routed part is linear in the factor
    routed = lambda m: (m(x, p)[0]                 # noqa: E731
                        - dataclasses.replace(m, topk=4, routed_scaling=0.0
                                              )(x, p)[0])
    np.testing.assert_allclose(
        np.asarray(routed(moe)),
        1.8 * np.asarray(routed(dataclasses.replace(
            moe, routed_scaling=1.0))), rtol=2e-3, atol=2e-4)


@pytest.mark.parametrize("n,block", [(5, 16), (64, 32), (300, 64)])
def test_packed_plan_holds_any_assignment(n, block):
    rng = np.random.default_rng(n)
    ids = np.stack([rng.permutation(8)[:4] for _ in range(n)])
    ids[: n // 2] = [0, 1, 2, 3]                   # half on four experts
    w = rng.random((n, 4)).astype(np.float32)
    plan = moe_utils.pack_by_expert(jnp.asarray(ids, jnp.int32),
                                    jnp.asarray(w), 8, block)
    t = moe_utils.packed_blocks_bound(4 * n, 8, block)
    assert plan.block_expert.shape == (t,)
    nb = int(plan.n_blocks)
    counts = np.bincount(ids.ravel(), minlength=8)
    assert nb == sum(-(-c // block) for c in counts) <= t
    rows = np.asarray(plan.pair_row)
    bexp = np.asarray(plan.block_expert)
    assert (bexp[rows // block] == ids).all()      # in its expert's block
    assert (np.asarray(plan.row_weight)[rows] == w).all()
    assert (bexp[nb:] == bexp[nb - 1]).all()       # tail repeats the last
    assert np.asarray(plan.row_weight).sum() == pytest.approx(w.sum())


# ---------------------------------------------------------------------------
# the cache and the models around it
# ---------------------------------------------------------------------------

def test_latent_pool_bytes_per_page_and_page_accounting(system):
    """One pool a layer, no V pool: a page pins layers x page x padded
    row x 2 bytes; the page manager prices admissions by it."""
    model = system.model
    cache = model.create_paged_cache(2, 5, 16, 8)
    assert cache.vs is None and len(cache.ks) == 3
    assert cache.ks[0].shape == (5, 1, 16, 256)    # 128 + 16 -> 256
    assert cache.bytes_per_page() == 3 * 16 * 256 * 2
    assert model.latent_bytes_per_token == 3 * (128 + 16) * 2
    slots = system.sched.slots
    assert slots.bytes_per_page == cache.bytes_per_page()
    assert slots.usable_pages == (TINY["serving"]["kv_budget_bytes_per_chip"]
                                  // cache.bytes_per_page())
    row = model.create_cache(1, 32)
    assert row.vs is None and row.ks[0].shape == (1, 1, 32, 256)
    assert row.bytes_per_slot() == 3 * 32 * 256 * 2
    # an ordinary cache still prices K and V
    kv = PagedKVCache.create(2, 4, 2, 4, 16, 8, 3)
    assert kv.stats is None and kv.bytes_per_page() == 2 * 2 * 4 * 16 * 8 * 2
    assert KVCache.create(2, 1, 4, 32, 8).bytes_per_slot() == (
        2 * 2 * 4 * 32 * 8 * 2)


def test_pages_follow_the_latent_rows(system):
    """Admission maps ceil(len / page) pages and release returns them."""
    slots = PagedKV(system.model, 2, max_seq=128, page_size=16,
                    prefix_cache=False)
    free = slots.free_pages
    row = system.model.create_cache(1, 64)
    slot = slots.insert_prefill(row, list(range(40)), 40,
                                jnp.zeros((2,), jnp.uint32), [])
    assert free - slots.free_pages == 3
    assert slots.ensure(slot, 49) and free - slots.free_pages == 4
    slots.release(slot)
    assert slots.free_pages == free


def test_autollm_finds_the_family_and_tp_is_refused(devices):
    cfg = ModelConfig.tiny_glm4_moe_lite()
    one = Mesh(np.array(devices[:1]), ("tp",))
    assert isinstance(AutoLLM(cfg, one), Glm4MoeLite)
    with pytest.raises(AssertionError, match="one device"):
        Glm4MoeLite(cfg, Mesh(np.array(devices[:2]), ("tp",)))


def test_qwen3_programs_are_what_they_were(devices):
    """The dense family's parameter tree, cache and decode program are
    untouched by the latent cache and the expert counters: K and V
    pools a layer, no `stats`, the paged decode kernel it always ran
    and none of the new ones."""
    mesh = Mesh(np.array(devices[:1]), ("tp",))
    model = Qwen3(ModelConfig.tiny(), mesh)
    specs = model.param_specs()
    assert sorted(specs) == ["embed", "layers", "lm_head", "ln_f"]
    assert sorted(specs["layers"][0]) == ["attn", "ln1", "ln2", "mlp"]
    assert sorted(specs["layers"][0]["attn"]) == [
        "k_norm", "q_norm", "wo", "wqkv"]
    assert sorted(specs["layers"][0]["mlp"]) == ["down", "gate_up"]
    cache = model.create_paged_cache(2, 5, 16, 4)
    assert cache.stats is None and len(cache.vs) == len(cache.ks) == 2
    params = jax.eval_shape(model.init_params, jax.random.key(0))
    jaxpr = jax.make_jaxpr(model.make_paged_decode_fn(16))(
        params, jnp.zeros((2,), jnp.int32), cache)
    names = set()

    def walk(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "pallas_call":
                names.add(eqn.params["name"])
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else [v]):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        walk(inner)
    walk(jaxpr.jaxpr)
    assert "flash_decode_paged" in names
    assert not names & {"mla_decode_paged", "moe_decode_gate_up",
                        "moe_decode_down"}


def test_glm_decode_program_names_its_kernels(system):
    cache = system.sched.slots.cache
    jaxpr = jax.make_jaxpr(system.model.make_paged_decode_fn(16))(
        system.params, jnp.zeros((2,), jnp.int32), cache)
    text = str(jaxpr)
    for name in ("mla_decode_paged", "moe_decode_gate_up",
                 "moe_decode_down"):
        assert name in text
    assert "moe_prefill" not in text


# ---------------------------------------------------------------------------
# a prompt prefilled in chunks that attend the pages already in the pool
# ---------------------------------------------------------------------------

def _serve_one_by_one(system, chunk, prompts, monkeypatch):
    """Each prompt through a scheduler of its own pool, one after the
    other (a later one may hit an earlier one's pages): the tokens
    served, the chunk starts enqueued, and every full prompt page the
    radix tree holds afterwards as (page id, its rows in every layer).
    """
    from triton_distributed_tpu.serving import (
        ContinuousBatchingScheduler, SchedulerConfig)
    monkeypatch.setattr(system.model, "prefill_chunk", chunk)
    sched = ContinuousBatchingScheduler(
        system.model, system.params,
        SchedulerConfig(num_slots=2, max_seq=128, kv_layout="paged",
                        num_pages=40, prefill_buckets=(16, 32, 64, 128)))
    starts = []
    suffix = sched._prefill_suffix
    sched._prefill_suffix = lambda p, ids, start, *a: (
        starts.append((int(start), ids.shape[1]))
        or suffix(p, ids, start, *a))
    served, pages = [], []
    for p in prompts:
        req = Request(p, 6, eos_token_ids=(), seed=0)
        sched.run([req])
        served.append(req.generated)
        held = sched.slots.match_prefix(p)
        assert len(held) == (len(p) - 1) // 16
        pages.append([(n.page, [np.asarray(
            k[n.page].astype(jnp.float32)) for k in sched.slots.cache.ks])
            for n in held])
    return served, starts, pages


def test_chunked_prefill_leaves_the_whole_prefills_rows_and_tokens(
        system, monkeypatch):
    """2, 3 and 4 chunks of 32 (the last right-padded), and one whose
    first chunk starts at 16 — a radix hit on the page an earlier
    prompt left, not a multiple of the chunk: the same latent rows in
    the same pages and the same greedy tokens as the whole prefill
    through its bucket."""
    rng = np.random.default_rng(21)
    prompts = [rng.integers(0, 256, n).tolist() for n in (100, 70, 40)]
    prompts.append(prompts[1][:16] + rng.integers(0, 256, 60).tolist())
    whole, none, want = _serve_one_by_one(system, 0, prompts, monkeypatch)
    # (the unchunked scheduler prefills a hit's suffix through the
    # same program, in one piece)
    assert none == [(16, 64)]
    got, starts, pages = _serve_one_by_one(system, 32, prompts,
                                           monkeypatch)
    assert starts == [(at, 32) for at in
                      (0, 32, 64, 96, 0, 32, 64, 0, 32, 16, 48)]
    assert got == whole
    for held, held_whole in zip(pages, want):
        assert [p for p, _ in held] == [p for p, _ in held_whole]
        for (_, rows), (_, rows_whole) in zip(held, held_whole):
            # (the first layer's rows bit for bit; behind an attention
            # the sum over keys runs in another order — a buffer past
            # the bucket's length — and a few numbers round to the
            # neighbouring bfloat16)
            np.testing.assert_array_equal(rows[0], rows_whole[0])
            for a, b in zip(rows[1:], rows_whole[1:]):
                np.testing.assert_allclose(a, b, rtol=2 ** -6, atol=2 ** -7)
                assert (a == b).mean() > 0.9


def test_a_chunk_reads_its_prefix_through_the_page_ids(system):
    """The chunk program against the whole prefill, by hand: rows at
    scattered pages, named in logical order; another page in the ids
    is seen (the rows of layers past the first differ)."""
    model, params = system.model, system.params
    rng = np.random.default_rng(22)
    prompt = rng.integers(0, 256, 64).tolist()
    ids, _ = pad_prompt(prompt, 64)
    _, whole = jax.jit(model.make_prefill_fn())(
        params, ids, model.create_cache(1, 64))
    cache = model.create_paged_cache(2, 12, 16, 8)
    order = np.asarray([7, 2, 9, 4, 0, 0, 0, 0], np.int32)
    pool = [k.at[order[:2], 0].set(w[0, 0, :32].reshape(2, 16, -1))
            for k, w in zip(cache.ks, whole.ks)]
    suffix = jax.jit(model.make_prefill_suffix_fn())
    tail, _ = pad_prompt(prompt[32:], 32)

    def rows(page_ids):
        out = suffix(params, tail, jnp.int32(32), model.create_cache(1, 32),
                     (pool, None), jnp.asarray(page_ids))
        return [np.asarray(k[0, 0].astype(jnp.float32)) for k in out.ks]
    for got, w in zip(rows(order), whole.ks):
        np.testing.assert_array_equal(
            got, np.asarray(w[0, 0, 32:].astype(jnp.float32)))
    other = order.copy()
    other[0] = 5                    # a page nobody wrote
    wrong = rows(other)
    np.testing.assert_array_equal(                # layer 0: no prefix in it
        wrong[0], np.asarray(whole.ks[0][0, 0, 32:].astype(jnp.float32)))
    assert np.abs(wrong[2] - np.asarray(
        whole.ks[2][0, 0, 32:].astype(jnp.float32))).max() > 1e-2


def test_glm_chunk_program_keeps_the_prefills_names(system):
    """The device trace reads a chunk as a prefill: the program's name
    starts like the whole prefill's, its expert kernels are the
    prefill phase's, its attention the flash kernel."""
    model = system.model
    fn = jax.jit(model.make_prefill_suffix_fn())
    cache = system.sched.slots.cache
    args = (system.params, jnp.zeros((1, 32), jnp.int32), jnp.int32(16),
            model.create_cache(1, 32), (cache.ks, cache.vs),
            jnp.zeros((cache.page_table.shape[1],), jnp.int32))
    assert fn.lower(*args).as_text().splitlines()[0].startswith(
        "module @jit_prefill_shard")
    text = str(jax.make_jaxpr(fn)(*args))
    for name in ("moe_prefill_gate_up", "moe_prefill_down",
                 "flash_attention_fwd"):
        assert name in text
    assert "moe_decode" not in text and "mla_decode" not in text
