"""Solar-Open2 family on the CPU at tiny sizes: the program
(`models.solar_open2.SolarOpen2` — gated softmax attention without
positions over paged K/V, Kimi Delta Attention over a recurrent state a
slot, a SHARE of a sparse expert layer; Pallas kernels in interpret
mode) against the plain float32 reference
(`cellbench.references.solar_open2`, which imports nothing of the
program), on seeded weights laid in by the benchmark's own adapter.

Tolerances.  The program computes in bfloat16 with float32 accumulation
(the state and its recurrence in float32); the reference in float32.
At these sizes (3 layers, hidden 128) the logits' own spread is 1.0 and
the program's worst logit of a position lies a median 0.023-0.031 from
the reference's (measured), under `LOGIT_TOL` = 0.08 at every position
but those a routing near-tie reaches: where bfloat16 rounding flips a
token's fourth expert — here often between an expert this chip holds
and one it does not — that token's logits move by 0.1-0.6, and because
the delta-rule layers carry what they wrote, a few later positions move
too (measured: 0 and 4 of two sequences' 34 positions past 0.08).  So a
sequence passes with at most `FLIPS` = 6 positions past the tolerance.
The same comparison on the reference's float8 control reads 0.19-0.34
at EVERY position and is checked to FAIL.  Kernels against the
recurrence itself are float32 on both sides: 1e-4.
"""

import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from cellbench.adapters import solar_open2 as adapter
from cellbench.references import solar_open2 as reference
from triton_distributed_tpu.kernels import kda
from triton_distributed_tpu.layers.moe_mlp import HELD_STATS, SparseMoE
from triton_distributed_tpu.models import AutoLLM, ModelConfig
from triton_distributed_tpu.models.solar_open2 import SolarOpen2
from triton_distributed_tpu.serving import Request
from triton_distributed_tpu.serving.engine_batched import (
    pad_prompt, pick_bucket)
from triton_distributed_tpu.serving.pages import PagedKV

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOGIT_TOL = 0.08
FLIPS = 6

#: One slot's recurrent state at test size: two delta-rule layers of 8
#: heads x 128 x 128 float32 and 3 convolution inputs of 3 x 1024.
STATE = 2 * (8 * 128 * 128 * 4 + 3 * 3 * 1024 * 2)
#: Bytes of a 16-token page of the ONE attention layer (K and V, 2
#: heads of 16).
PAGE = 2 * 2 * 16 * 16 * 2

#: The published `config.json` keys at test size: the pattern kept (a
#: softmax layer before the delta-rule ones, no positions, the gate,
#: top-4 beside one shared expert), the delta-rule head its published
#: 128, two chips sharing each layer's 16 experts.
TINY = {
    "model_type": "solar_open2", "vocab_size": 256, "hidden_size": 128,
    "intermediate_size": 256, "moe_intermediate_size": 128,
    "num_hidden_layers": 3, "num_attention_heads": 8,
    "num_key_value_heads": 2, "head_dim": 16,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                           "num_heads": 8, "num_kv_heads": None},
    "n_routed_experts": 8, "n_shared_experts": 1,
    "num_experts_per_tok": 4, "first_k_dense_replace": 0,
    "routed_scaling_factor": 1.0, "norm_topk_prob": True,
    "rms_norm_eps": 1e-5, "rope_theta": 10000, "use_rope": False,
    "gqa_layers": [0, 4, 8], "use_gqa_gate": True,
    "kda_use_full_proj": False, "kda_allow_neg_eigval": True,
    "tie_word_embeddings": False, "torch_dtype": "bfloat16",
    "share": {"chips": 2, "experts_of_layer": 16,
              "experts_held": [0, 8]},
    "serving": {"num_slots": 2, "max_seq": 128,
                "kv_budget_bytes_per_chip": 2 * STATE + 16 * PAGE,
                "max_queue": 16},
}
SEED = 7


@pytest.fixture(scope="module")
def system(devices):
    """The benchmark's adapter at test size: the program with the
    reference's weights, behind its scheduler."""
    return adapter.System(TINY, SEED, devices[:1])


def _ref_logits(tokens, first, n_out, precision="f32", config=TINY):
    dims = reference.dims_of(config)
    pad = np.zeros(128, np.int64)
    pad[:len(tokens)] = tokens
    return np.asarray(reference.logits_at(dims, SEED, pad, first, n_out,
                                          precision=precision))


def _row_for(model, bucket, length):
    """The prefill's input row: the state absorbs ``length`` tokens."""
    return dataclasses.replace(
        model.create_cache(1, bucket),
        length=np.full((1,), length, np.int32))


def _serve_logits(system, prompts, steps, teacher):
    """Prefill each prompt through a PADDED bucket, insert it into the
    paged pool and the state pool, then ``steps`` decode steps of the
    whole batch, feeding ``teacher[b][i]`` at step i > 0: the serving
    path's own artefacts, logits kept.  Returns (steps, B, vocab)."""
    model, params = system.model, system.params
    slots = PagedKV(model, len(prompts), max_seq=128, page_size=16,
                    prefix_cache=False)
    prefill = jax.jit(model.make_prefill_fn())
    decode = jax.jit(model.make_paged_decode_fn(page_size=16))
    for p in prompts:
        bucket = pick_bucket(len(p), (16, 32, 64, 128))
        ids, s = pad_prompt(p, bucket)
        _, row = prefill(params, ids, _row_for(model, bucket, s - 1))
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


def test_gated_attention_without_positions_matches_reference(devices):
    """`TPAttention(rope=False, gate=True)` alone: the family cut to
    its first (softmax) layer, prefill logits against the reference."""
    one = dict(TINY, num_hidden_layers=1)
    sys1 = adapter.System(one, SEED, devices[:1])
    assert sys1.model.num_kda == 0 and not sys1.model.attn.rope
    wqkv = sys1.params["layers"][0]["attn"]["wqkv"]
    assert wqkv.shape[1] == (2 * 8 + 2 * 2) * 16      # q | k | v | gate
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, 256, 32).tolist()
    ids, _ = pad_prompt(prompt, 32)
    logits, _ = jax.jit(sys1.model.make_prefill_fn())(
        sys1.params, ids, sys1.model.create_cache(1, 32))
    ref = _ref_logits(prompt, 31, 1, config=one)
    assert np.abs(np.asarray(logits) - ref).max() < LOGIT_TOL
    # and nothing is rotated
    jaxpr = str(jax.make_jaxpr(sys1.model.make_prefill_fn())(
        sys1.params, ids, sys1.model.create_cache(1, 32)))
    assert " cos " not in jaxpr and " sin " not in jaxpr


@pytest.fixture(scope="module")
def decoded(system):
    """Two requests of different lengths in one batch, each prefilled
    through a padded bucket, 34 decode steps through the pages and the
    state (crossing two page boundaries), teacher-forced."""
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
    float8 control lies outside it at every position."""
    prompts, teacher, steps, got, _ = decoded
    p = prompts[row]
    seq = p + teacher[row][:steps - 1]
    low = _ref_logits(seq, len(p) - 1, steps, precision="fp8")
    ref = _ref_logits(seq, len(p) - 1, steps)
    err = np.abs(low - ref).max(axis=1)
    assert (err > LOGIT_TOL).all() and np.median(err) > 2 * LOGIT_TOL


def test_decode_leaves_its_counts_in_the_cache(decoded, system):
    """`PagedKVCache.stats` after a step, in `SolarOpen2.STATS` order:
    the HELD experts' pairs and those routed elsewhere add up to rows x
    top-k x layers; both rows were live."""
    *_, slots = decoded
    assert SolarOpen2.STATS == HELD_STATS + ("live_slots",)
    pairs, hit, load, elsewhere, live = np.asarray(slots.cache.stats)
    assert pairs + elsewhere == 2 * 4 * 3
    assert 1 <= hit <= min(pairs, 8 * 3) and 0 < load <= 1
    assert live == 2


def test_programs_name_their_kernels(system):
    cache = system.sched.slots.cache
    text = str(jax.make_jaxpr(system.model.make_paged_decode_fn(16))(
        system.params, jnp.zeros((2,), jnp.int32), cache))
    for name in ("kda_decode_step", "flash_decode_paged",
                 "moe_decode_gate_up", "moe_decode_down"):
        assert name in text
    assert "kda_prefill_chunk" not in text
    ids = jnp.zeros((1, 64), jnp.int32)
    text = str(jax.make_jaxpr(system.model.make_prefill_fn())(
        system.params, ids, system.model.create_cache(1, 64)))
    assert "kda_prefill_chunk" in text and "kda_decode_step" not in text


def test_autollm_finds_the_family_and_tp_is_refused(devices):
    cfg = ModelConfig.tiny_solar_open2()
    one = Mesh(np.array(devices[:1]), ("tp",))
    assert isinstance(AutoLLM(cfg, one), SolarOpen2)
    with pytest.raises(AssertionError, match="one device"):
        SolarOpen2(cfg, Mesh(np.array(devices[:2]), ("tp",)))


# ---------------------------------------------------------------------------
# the delta rule's kernels against the recurrence
# ---------------------------------------------------------------------------

def _delta_inputs(t, b=2, h=8, d=128, seed=0):
    ks = jax.random.split(jax.random.key(seed), 6)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (b, h, t, d))) * d ** -0.5
    k = jax.random.normal(ks[1], (b, h, t, d))
    # neighbours alike, as real keys are: A is far from small
    k = unit(k.at[:, :, 1::2].set(0.7 * k[:, :, 0::2][:, :, :t // 2]
                                  + 0.3 * k[:, :, 1::2]))
    v = jax.random.normal(ks[2], (b, h, t, d))
    fast = jnp.log(jax.random.uniform(ks[3], (h,), minval=1, maxval=16))
    g = -jnp.exp(fast)[None, :, None, None] * jax.random.uniform(
        ks[4], (b, h, t, d), minval=0.001, maxval=0.3)
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[5], (b, h, t)))
    return q, k, v, g, beta


def test_chunked_prefill_equals_the_recurrence_at_chunk_boundaries():
    """Two chunks: the outputs of every position, the state after the
    last, and — cut after the first chunk — the state AT the boundary."""
    q, k, v, g, beta = _delta_inputs(2 * kda.CHUNK)
    o_ref, s_ref = kda.kda_recurrent_reference(q, k, v, g, beta)
    o, s = kda.kda_prefill_chunk(q, k, v, g, beta)
    assert float(jnp.abs(o - o_ref).max()) < 1e-4
    assert float(jnp.abs(s - s_ref).max()) < 1e-4
    c = kda.CHUNK
    _, s1 = kda.kda_prefill_chunk(*(a[:, :, :c] for a in (q, k, v, g)),
                                  beta[:, :, :c])
    _, s1_ref = kda.kda_recurrent_reference(
        *(a[:, :, :c] for a in (q, k, v, g)), beta[:, :, :c])
    assert float(jnp.abs(s1 - s1_ref).max()) < 1e-4


def test_a_length_that_is_no_multiple_of_the_chunk(system):
    """Through the layer, which pads to whole chunks and masks what
    lies past each row's length: 100 tokens of which the state absorbs
    77 — kernels against the recurrence, and against the recurrence
    over those 77 tokens alone."""
    layer = dataclasses.replace(system.model.kda, mode="fused")
    golden = dataclasses.replace(layer, mode="xla")
    p = system.params["layers"][1]["attn"]
    x = jax.random.normal(jax.random.key(5), (100, 128)).astype(
        jnp.bfloat16)
    n = jnp.asarray([77], jnp.int32)
    y, s, c = layer.prefill(x, p, 1, n)
    y_ref, s_ref, c_ref = golden.prefill(x, p, 1, n)
    assert float(jnp.abs(s - s_ref).max()) < 1e-4
    assert float(jnp.abs(y.astype(jnp.float32)
                         - y_ref.astype(jnp.float32))[:77].max()) < 2e-2
    assert (c == c_ref).all()
    _, s77, c77 = golden.prefill(x[:77], p, 1, n)
    assert float(jnp.abs(s - s77).max()) < 1e-4 and (c == c77).all()


def test_a_prefill_that_continues_into_decode():
    """The chunked kernel's state handed to the one-token kernel: ten
    more tokens equal the recurrence over all of them."""
    t = kda.CHUNK
    q, k, v, g, beta = _delta_inputs(t + 16, b=1)
    o_ref, s_ref = kda.kda_recurrent_reference(q, k, v, g, beta)
    _, s = kda.kda_prefill_chunk(*(a[:, :, :t] for a in (q, k, v, g)),
                                 beta[:, :, :t])
    step = jax.jit(kda.kda_decode_step)
    live = jnp.asarray([True])
    for i in range(t, t + 10):
        o, s = step(q[:, :, i], k[:, :, i], v[:, :, i],
                    jnp.exp(g[:, :, i]), beta[:, :, i], s, live)
        assert float(jnp.abs(o - o_ref[:, :, i]).max()) < 1e-4
    _, s10 = kda.kda_recurrent_reference(
        *(a[:, :, :t + 10] for a in (q, k, v, g)), beta[:, :, :t + 10])
    assert float(jnp.abs(s - s10).max()) < 1e-4


@pytest.mark.parametrize("live", [(True, False, True, False),
                                  (False, False, False, True),
                                  (False, False, False, False)])
def test_the_decode_kernel_leaves_rows_that_are_not_live_untouched(live):
    q, k, v, g, beta = _delta_inputs(2, b=4, seed=3)
    state = jax.random.normal(jax.random.key(9), (4, 8, 128, 128))
    args = (q[:, :, 0], k[:, :, 0], v[:, :, 0], jnp.exp(g[:, :, 0]),
            beta[:, :, 0])
    o, new = kda.kda_decode_step(*args, state, jnp.asarray(live))
    o_ref, s_ref = kda.kda_recurrent_reference(
        *(a[:, :, :1] for a in (q, k, v, g)), beta[:, :, :1], state)
    for row, alive in enumerate(live):
        if alive:
            assert float(jnp.abs(new[row] - s_ref[row]).max()) < 1e-4
            assert float(jnp.abs(o[row] - o_ref[row, :, 0]).max()) < 1e-4
        else:
            assert (new[row] == state[row]).all()
            assert (o[row] == 0).all()


def test_write_strength_is_in_0_2_and_the_2_goes_with_the_option(system):
    layer = system.model.kda
    p = system.params["layers"][1]["attn"]
    x = jax.random.normal(jax.random.key(4), (256, 128)).astype(
        jnp.bfloat16) * 4
    conved = jnp.zeros((256, 3 * layer.width), jnp.float32)
    beta2 = layer._features(x, conved, p)[4]
    beta1 = dataclasses.replace(layer, neg_eigval=False)._features(
        x, conved, p)[4]
    assert 0 < float(beta2.min()) and float(beta2.max()) < 2
    assert float(beta2.max()) > 1 > float(beta1.max())
    np.testing.assert_allclose(np.asarray(beta2), 2 * np.asarray(beta1))


# ---------------------------------------------------------------------------
# the share of the expert layer
# ---------------------------------------------------------------------------

def test_all_the_shares_add_up_to_the_uncut_layer(system):
    """Guide section 4's test: the routed parts the two chips' layers
    give, plus the shared expert counted once, are the reference's
    UNCUT layer (router over 16, all 16 experts)."""
    dims = reference.dims_of(TINY)
    whole = reference.dims_of(TINY, held=(0, 16))
    key = reference.layer_key(reference.base_key(SEED), 1)
    w = reference.layer_weights(key, dims, False)
    x = jax.random.normal(jax.random.key(6), (48, 128), jnp.float32)
    xb = x.astype(jnp.bfloat16)
    x = xb.astype(jnp.float32)        # one input for both sides
    combine = reference.router_weights(x, w, whole)
    uncut = (reference.routed_part(x, combine, key, whole)
             + reference._swiglu(x, w["shared_gate"], w["shared_up"],
                                 w["shared_down"], False))
    mlp = system.params["layers"][1]["mlp"]
    shared = system.model.moe._shared(xb, mlp["shared"])
    total = shared.astype(jnp.float32)
    elsewhere = 0
    for lo, hi in ((0, 8), (8, 16)):
        part = reference.dims_of(TINY, held=(lo, hi))
        blocks = [reference.expert_weights(key, part, b)
                  for b in reference.held_blocks(part)]
        params = dict(mlp, **{k: jnp.concatenate([b[k] for b in blocks])
                              for k in ("gate", "up", "down")})
        layer = dataclasses.replace(system.model.moe, held=(lo, hi))
        y, stats = layer(xb, params, phase="decode")
        total = total + (y.astype(jnp.float32)
                         - shared.astype(jnp.float32))
        elsewhere += float(stats[3])
        assert float(stats[0]) + float(stats[3]) == 48 * 4
    assert elsewhere == 48 * 4        # each pair is held exactly once
    err = float(jnp.abs(total - uncut).max())
    assert err < 0.05, (err, float(jnp.abs(uncut).max()))


def test_a_step_whose_pairs_all_went_elsewhere(system):
    """No pair for a held expert (a few rows, most experts elsewhere):
    the plan still names one block — the grouped GEMMs map the blocks
    past the last to `n_blocks - 1`, and -1 would read outside the
    array (on the chip: a hang) — and the layer gives the shared
    expert alone."""
    from triton_distributed_tpu.kernels import moe_utils
    ids = jnp.asarray([[8, 9, 10, 11], [12, 13, 14, 15]], jnp.int32)
    plan = moe_utils.pack_by_expert(ids, jnp.ones((2, 4)), 16, 16,
                                    held=(0, 8))
    assert int(plan.n_blocks) == 1 and int(plan.counts[-1]) == 8
    assert (np.asarray(plan.block_expert) < 8).all()
    assert (np.asarray(plan.row_weight) == 0).all()
    assert (np.asarray(plan.row_token) == 2).all()     # zero rows
    mlp = system.params["layers"][1]["mlp"]
    moe = system.model.moe
    x = jax.random.normal(jax.random.key(3), (2, 128)).astype(
        jnp.bfloat16)
    # a router that sends everything to experts 8..15
    router = jnp.zeros_like(mlp["router"]).at[:, 8:].set(1.0)
    far = dict(mlp, router=router * jnp.sign(x[0].astype(
        jnp.float32))[:, None], router_bias=jnp.where(
            jnp.arange(16) >= 8, 10.0, -10.0))
    y, stats = moe(x, far, phase="decode")
    assert float(stats[0]) == 0 and float(stats[3]) == 8
    want = moe._shared(x, mlp["shared"])
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(want.astype(jnp.bfloat16),
                                          np.float32), atol=1e-6)


def test_holding_every_expert_is_todays_layer_bit_for_bit():
    plain = SparseMoE(hidden=128, ffn=128, num_experts=16, topk=4)
    held = dataclasses.replace(plain, held=(0, 16))
    p = plain.init_params(jax.random.key(1))
    x = jax.random.normal(jax.random.key(2), (24, 128)).astype(
        jnp.bfloat16)
    for phase in ("decode", "prefill"):
        y0, s0 = plain(x, p, phase=phase)
        y1, s1 = held(x, p, phase=phase)
        assert (y0 == y1).all()
        np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1)[:3])
        assert float(s1[3]) == 0


# ---------------------------------------------------------------------------
# the state pool behind the scheduler
# ---------------------------------------------------------------------------

def test_the_state_pool_is_priced_in_the_kv_budget(system):
    slots = system.sched.slots
    assert slots.state_bytes_per_slot == STATE
    assert slots.bytes_per_page == PAGE and slots.usable_pages == 16
    assert slots.kv_budget_bytes == 2 * STATE + 16 * PAGE
    kv = PagedKV(system.model, 2, max_seq=128, page_size=16,
                 prefix_cache=False)
    row = system.model.create_cache(1, 64)
    slot = kv.insert_prefill(row, list(range(40)), 40,
                             jnp.zeros((2,), jnp.uint32), [])
    assert kv.bytes_in_use == 3 * PAGE + STATE
    kv.release(slot)
    assert kv.bytes_in_use == 0 and kv.state_resets == 1


def test_a_reset_slot_starts_from_zero(system):
    """Release zeroes the slot's state and convolution inputs where
    they lie, and the other slot's are left as they were."""
    model = system.model
    kv = PagedKV(model, 2, max_seq=128, page_size=16, prefix_cache=False)
    rng = np.random.default_rng(8)
    prefill = jax.jit(model.make_prefill_fn())
    for n in (30, 20):
        ids, s = pad_prompt(rng.integers(0, 256, n).tolist(), 32)
        _, row = prefill(system.params, ids, _row_for(model, 32, s - 1))
        kv.insert_prefill(row, ids[0, :s].tolist(), s,
                          jnp.zeros((2,), jnp.uint32), [])
    before = [np.asarray(x) for x in kv.cache.states + kv.cache.convs]
    assert all(np.abs(x[0]).max() > 0 and np.abs(x[1]).max() > 0
               for x in before)
    kv.release(0)
    after = [np.asarray(x) for x in kv.cache.states + kv.cache.convs]
    for a, b in zip(after, before):
        assert (a[0] == 0).all() and (a[1] == b[1]).all()


def _greedy(system, prompts, new, preempt_at=None):
    sched = system.sched
    before = sched._state_recomputed
    reqs = [Request(p, new, eos_token_ids=(), seed=0) for p in prompts]
    for r in reqs:
        assert sched.submit(r)
    steps, had = 0, None
    while sched.has_work():
        sched.step()
        steps += 1
        if steps == preempt_at:
            sched._read(sched._take_flight())
            had = len(reqs[1].generated)
            sched._preempt(reqs[1].slot)
    return ([r.generated for r in reqs], reqs,
            sched._state_recomputed - before, had)


def test_a_preempted_request_resumes_with_the_tokens_it_would_have_had(
        system):
    """No snapshot: the resumed request's state is recomputed by the
    prefill over its prompt and what it had generated, and its stream
    goes on as if nothing had happened."""
    rng = np.random.default_rng(12)
    prompts = [rng.integers(0, 256, n).tolist() for n in (9, 14)]
    straight, *_ = _greedy(system, prompts, 16)
    resumed, reqs, redone, had = _greedy(system, prompts, 16,
                                         preempt_at=7)
    assert reqs[1].preemptions == 1 and 0 < had < 16
    assert resumed == straight
    # what a snapshot of the state would have saved
    assert redone == 14 + had


def test_other_families_set_up_without_the_delta_rule_kernels():
    """`import triton_distributed_tpu` and building a Qwen and a GLM
    system import nothing of `kernels/kda.py` (nor the layer or the
    model over it): their set-up pays nothing for this family."""
    code = """
import sys
import jax, numpy as np
from jax.sharding import Mesh
import triton_distributed_tpu
from triton_distributed_tpu.models import AutoLLM, ModelConfig
from triton_distributed_tpu.serving import (
    ContinuousBatchingScheduler, SchedulerConfig)
mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
for cfg in (ModelConfig.tiny(), ModelConfig.tiny_glm4_moe_lite()):
    model = AutoLLM(cfg, mesh)
    params = model.init_params(jax.random.key(0))
    ContinuousBatchingScheduler(model, params, SchedulerConfig(
        num_slots=2, max_seq=64, kv_layout="paged"))
bad = [m for m in sys.modules if m.endswith((".kda", ".kda_attn",
                                             ".solar_open2"))]
assert not bad, bad
print("clean")
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0 and "clean" in out.stdout, out.stderr[-2000:]
