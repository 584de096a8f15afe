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
import functools
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from cellbench import correctness
from cellbench.adapters import solar_open2 as adapter
from cellbench.references import solar_open2 as reference
from triton_distributed_tpu.kernels import kda
from triton_distributed_tpu.layers.moe_mlp import HELD_STATS, SparseMoE
from triton_distributed_tpu.models import AutoLLM, ModelConfig, solar_open2
from triton_distributed_tpu.models.solar_open2 import SolarOpen2
from triton_distributed_tpu.serving import Request
from triton_distributed_tpu.serving.engine_batched import (
    pad_prompt, pick_bucket)
from triton_distributed_tpu.serving.pages import PagedKV
from tests.test_nemotron_h import Enqueues

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


@functools.cache
def _decode_fn(model):
    """The jitted paged decode step, traced once for the file."""
    return jax.jit(model.make_paged_decode_fn(page_size=16))


def _serve_logits(system, prompts, steps, teacher):
    """Prefill each prompt through a PADDED bucket, insert it into the
    paged pool and the state pool, then ``steps`` decode steps of the
    whole batch, feeding ``teacher[b][i]`` at step i > 0: the serving
    path's own artefacts, logits kept.  Returns (steps, B, vocab)."""
    model, params = system.model, system.params
    slots = PagedKV(model, len(prompts), max_seq=128, page_size=16,
                    prefix_cache=False)
    prefill = jax.jit(model.make_prefill_fn())
    decode = _decode_fn(model)
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


#: Tokens a chunk of the tests below: the 21-token prompt goes in two
#: pieces, the 50-token one in four — the last two tokens long, of
#: which the state absorbs ONE.
CHUNK = 16


@pytest.fixture(scope="module")
def decoded_in_chunks(system, decoded):
    """`decoded`'s two prompts prefilled by suffix calls — each piece
    over the pages its predecessors filled, from the state and tail
    they returned — then the same teacher-forced decode steps."""
    model, params = system.model, system.params
    prompts, teacher, steps, *_ = decoded
    slots = PagedKV(model, len(prompts), max_seq=128, page_size=16,
                    prefix_cache=False)
    suffix = jax.jit(model.make_prefill_suffix_fn())
    decode = _decode_fn(model)
    rows = []
    for p in prompts:
        s = len(p)
        slot = slots.begin_prefill(s, [])
        row = model.create_cache(1, CHUNK)
        for at in range(0, s, CHUNK):
            ids, _ = pad_prompt(p[at:at + CHUNK], CHUNK)
            row = suffix(
                params, ids, jnp.int32(at), dataclasses.replace(
                    row, length=np.full(
                        (1,), min(max(s - 1 - at, 0), CHUNK), np.int32)),
                (slots.cache.ks, slots.cache.vs),
                slots.prefill_pages(slot))
            last = at + CHUNK >= s
            slots.insert_rows(slot, row, at, *(
                [jnp.zeros((2,), jnp.uint32)] if last else []))
        slots.finish_prefill(slot, p)
        rows.append(row)
    got = []
    tokens = np.asarray([p[-1] for p in prompts], np.int32)
    for i in range(steps):
        for b, p in enumerate(prompts):
            assert slots.ensure(b, len(p) + i)
        slots.flush()
        logits, slots.cache = decode(params, jnp.asarray(tokens),
                                     slots.cache)
        got.append(np.asarray(logits))
        tokens = np.asarray([t[i] for t in teacher], np.int32)
    return np.stack(got), rows


@pytest.mark.parametrize("row", [0, 1])
def test_a_prompt_prefilled_in_chunks_decodes_like_the_whole_prefill(
        system, decoded, decoded_in_chunks, row):
    """State, tail and K/V rows after the last chunk are the whole
    prefill's (the chunks cut the sequence elsewhere: float32 rounding
    in the state, bfloat16 in what the later layers read), and the
    decode steps behind them give the whole prefill's logits and the
    float32 reference's within the file's tolerance — which the float8
    control fails at every position
    (`test_float8_control_fails_the_tolerance`, the same sequences)."""
    prompts, teacher, steps, whole, _ = decoded
    got, rows = decoded_in_chunks
    p = prompts[row]
    ids, s = pad_prompt(p, 64)
    _, want = jax.jit(system.model.make_prefill_fn())(
        system.params, ids, _row_for(system.model, 64, s - 1))
    for a, b in zip(rows[row].states, want.states):
        assert _close(a, b, 2e-2), float(jnp.abs(a - b).max())
    for a, b in zip(rows[row].convs, want.convs):
        assert float(jnp.abs(a.astype(jnp.float32)
                             - b.astype(jnp.float32)).max()) < 0.1
    err = np.abs(got[:, row] - whole[:, row]).max(axis=1)
    assert np.median(err) < LOGIT_TOL / 2, err
    assert (err > LOGIT_TOL).sum() <= FLIPS, err
    seq = p + teacher[row][:steps - 1]
    ref = _ref_logits(seq, len(p) - 1, steps)
    err = np.abs(got[:, row] - ref).max(axis=1)
    assert np.median(err) < LOGIT_TOL / 2, err
    assert (err > LOGIT_TOL).sum() <= FLIPS, err


def test_the_chunk_program_names_the_prefills_kernels(system):
    """A device trace reads a chunk as a prefill: the program's name
    starts like the whole prefill's and its kernels are the prefill's;
    no logits, so the head is not in it."""
    model = system.model
    fn = jax.jit(model.make_prefill_suffix_fn())
    cache = system.sched.slots.cache
    args = (system.params, jnp.zeros((1, 32), jnp.int32), jnp.int32(32),
            model.create_cache(1, 32), (cache.ks, cache.vs),
            jnp.zeros((8,), jnp.int32))
    assert fn.lower(*args).as_text().splitlines()[0].startswith(
        "module @jit_prefill_shard")
    text = str(jax.make_jaxpr(fn)(*args))
    for name in ("kda_prefill_chunk", "moe_prefill_gate_up",
                 "moe_prefill_down", "flash_attention_fwd"):
        assert name in text
    assert "kda_decode_step" not in text
    assert " cos " not in text and " sin " not in text


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


def _close(got, want, tol=1e-4):
    return float(jnp.abs(got - want).max()) < tol * max(
        1.0, float(jnp.abs(want).max()))


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


def test_the_chunked_kernel_starts_from_a_carried_state():
    """From a state that is not zero: outputs and final state are the
    recurrence's continued from it; and cut in two calls, the second
    starting from what the first returned, the kernel gives bit for bit
    what it gives in one.  Tokens with g = 0 and beta = 0 hand the
    state back as it went in."""
    c = kda.CHUNK
    q, k, v, g, beta = _delta_inputs(2 * c, seed=4)
    s0 = jax.random.normal(jax.random.key(11), (2, 8, 128, 128))
    o_ref, s_ref = kda.kda_recurrent_reference(q, k, v, g, beta, s0)
    o, s = kda.kda_prefill_chunk(q, k, v, g, beta, s0)
    assert _close(o, o_ref) and _close(s, s_ref)
    # and it is not the zero state's answer
    o_zero, _ = kda.kda_recurrent_reference(q, k, v, g, beta)
    assert not _close(o, o_zero, 1e-2)
    cut = lambda lo, hi: (  # noqa: E731
        *(a[:, :, lo:hi] for a in (q, k, v, g)), beta[:, :, lo:hi])
    o1, s1 = kda.kda_prefill_chunk(*cut(0, c), s0)
    o2, s2 = kda.kda_prefill_chunk(*cut(c, 2 * c), s1)
    assert (jnp.concatenate([o1, o2], axis=2) == o).all()
    assert (s2 == s).all()
    _, same = kda.kda_prefill_chunk(q[:, :, :c], k[:, :, :c], v[:, :, :c],
                                    0 * g[:, :, :c], 0 * beta[:, :, :c],
                                    s1)
    assert (same == s1).all()


@pytest.mark.parametrize("carried", [False, True])
def test_the_state_is_an_operand_only_where_one_is_carried(carried):
    """``state=None`` is the whole prefill's program as it was, operand
    for operand: five inputs, none of the state's shape (a zero state is
    made in the kernel's scratch).  With a state there is one more, the
    sixth, of a block of heads' states."""
    shape = jax.ShapeDtypeStruct((1, 8, 2 * kda.CHUNK, 128), jnp.float32)
    args = [shape] * 4 + [jax.ShapeDtypeStruct(shape.shape[:3],
                                               jnp.float32)]
    if carried:
        args.append(jax.ShapeDtypeStruct((1, 8, 128, 128), jnp.float32))
    jaxpr = jax.make_jaxpr(kda.kda_prefill_chunk)(*args)
    (call,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert call.params["name"] == "kda_prefill_chunk"
    shapes = [v.aval.shape for v in call.invars]
    assert len(shapes) == 5 + carried
    assert shapes[:5] == [(1, 8, 128, 128)] * 5
    mapping = call.params["grid_mapping"]
    assert mapping.num_inputs == 5 + carried and mapping.num_outputs == 2
    blocks = [bm.block_shape for bm in mapping.block_mappings]
    assert len(blocks) == 7 + carried
    # the estimate counts the state's read where there is one
    cost = call.params["cost_estimate"]
    seqs = 4 * 8 * 128 * 5 * 128
    assert cost.bytes_accessed == seqs + carried * 4 * 8 * 128 * 128


#: (tokens a piece, the prompt's tokens): the state absorbs all but the
#: prompt's last token, so the last piece absorbs 35 of 64 (the rest a
#: padded tail); 33 of 48 in three pieces — pieces that are no whole
#: kernel chunks; 63 (a last piece that is full); then fewer than the
#: convolution's 3 kept inputs — 2, 1 and 0.
PIECES = {"two": (64, 100), "three": (48, 130), "full": (64, 128),
          "absorbs_2": (64, 67), "absorbs_1": (64, 66),
          "absorbs_0": (64, 65)}


@pytest.mark.parametrize("mode", ["fused", "xla"])
@pytest.mark.parametrize("case", sorted(PIECES))
def test_a_prefill_in_pieces_equals_the_prefill_in_one(system, case, mode):
    """`KDAttention.prefill` piece by piece — each from the state and
    the convolution's tail the one before it returned — against one
    prefill over the same rows: output, state and tail.  A last piece
    that absorbs fewer tokens than the convolution keeps hands on
    inputs of the piece BEFORE it."""
    size, t = PIECES[case]
    layer = dataclasses.replace(system.model.kda, mode=mode)
    p = system.params["layers"][1]["attn"]
    rows = -(-t // size) * size
    x = jax.random.normal(jax.random.key(t), (rows, 128)).astype(
        jnp.bfloat16)
    n = t - 1
    y_ref, s_ref, c_ref = layer.prefill(x, p, 1,
                                        jnp.asarray([n], jnp.int32))
    ys, kept = [], ()
    for at in range(0, rows, size):
        took = jnp.asarray([min(max(n - at, 0), size)], jnp.int32)
        y, *kept = layer.prefill(x[at:at + size], p, 1, took, *kept)
        ys.append(y)
        if at >= n:                   # absorbed nothing: handed on
            assert (kept[0] == before[0]).all()
            assert (kept[1] == before[1]).all()
        before = kept
    s, c = kept
    assert s.dtype == jnp.float32 and c.dtype == jnp.bfloat16
    assert _close(s, s_ref)
    assert (c == c_ref).all()
    err = jnp.abs(jnp.concatenate(ys).astype(jnp.float32)
                  - y_ref.astype(jnp.float32))
    assert float(err.max()) < 2e-2, float(err.max())
    # and the tail is the inputs at positions n-3 .. n-1, which for the
    # short last pieces lie in the piece before
    want = jnp.dot(x, p["wqkv"], preferred_element_type=jnp.float32
                   ).astype(x.dtype)[n - 3:n]
    assert (c.reshape(3, -1) == want).all()


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


# ---------------------------------------------------------------------------
# a long prompt goes in by chunks that start from the carried state
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def chunking(devices):
    """The adapter's system with the model's chunk at test size: the
    model reads `PREFILL_CHUNK` when it is built."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(solar_open2, "PREFILL_CHUNK", CHUNK)
        built = adapter.System(TINY, SEED, devices[:1])
    return built


@pytest.fixture(scope="module")
def chunk_sched(chunking):
    """ONE scheduler over the chunking model for the tests below (its
    programs are traced once): four slots; every test leaves it
    drained."""
    from triton_distributed_tpu.serving import (
        ContinuousBatchingScheduler, SchedulerConfig)
    sched = ContinuousBatchingScheduler(
        chunking.model, chunking.params, SchedulerConfig(
            num_slots=4, max_seq=128, kv_layout="paged"))
    assert sched._chunk == CHUNK and sched._stateful and sched._paced
    return sched


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
    return Request(list(prompt), new, eos_token_ids=(), seed=0)


def _scored(rows):
    """What was served against the reference (`cellbench.correctness`,
    as the cell decides `correct`): limits between the two readings
    (measured over the four tests that call this, 9-16 tokens each: the
    program at most 0.0049 / 0.00044 — in three of them every served
    token is the reference's first — the float8 control at least 0.068
    / 0.0043)."""
    sample = [{"index": i, "prompt": r.prompt, "prompt_len": len(r.prompt),
               "tokens": list(r.generated), "ok": True}
              for i, r in enumerate(rows)]
    res = correctness.score(reference, reference.dims_of(TINY), SEED,
                            sample, 128, 16, control=True)
    limits = {"served_gap_max": 0.02, "served_gap_mean": 0.0015}
    assert correctness.judge(res["program"], limits)[0], res
    assert not correctness.judge(res["control"], limits)[0], res


def test_the_model_names_its_chunk(system):
    """The cell's length: a multiple of the kernel's chunk and of the
    page, a constant of the model file the scheduler reads."""
    from triton_distributed_tpu.serving import (
        ContinuousBatchingScheduler, SchedulerConfig)
    assert solar_open2.PREFILL_CHUNK % kda.CHUNK == 0
    assert solar_open2.PREFILL_CHUNK % 16 == 0
    assert system.model.prefill_chunk == solar_open2.PREFILL_CHUNK
    sched = ContinuousBatchingScheduler(
        system.model, system.params, SchedulerConfig(
            num_slots=2, max_seq=128, kv_layout="paged"))
    assert sched._chunk == solar_open2.PREFILL_CHUNK
    assert sched._paced and sched._stateful


def test_chunks_start_at_zero_despite_a_prefix_hit_and_carry_the_state(
        watched):
    """Two prompts that share their first 32 tokens, one after the
    other.  The second finds two pages in the radix tree and shares
    them — for storage: its chunks still cover the prompt from position
    0 (a state has no snapshot; the tokens a snapshot would have saved
    are counted), each starts from the state and tail the one before it
    returned, the reusable zero row is never written, and what is
    served is the reference's."""
    from triton_distributed_tpu.observability import get_registry
    sched, seen = watched
    reg = get_registry()
    reg.clear()
    rng = np.random.default_rng(41)
    shared = rng.integers(0, 256, 32).tolist()
    prompts = [shared + rng.integers(0, 256, n).tolist() for n in (18, 9)]
    kept = sched.slots.cached_prefix_pages
    served = []
    for p in prompts:
        req = _request(p, 6)
        sched.run([req])
        served.append(req)
    a, b = seen.chunks()[:4], seen.chunks()[4:]
    assert [ev[1] for ev in a] == [0, 16, 32, 48]          # 50 tokens
    assert [ev[1] for ev in b] == [0, 16, 32]              # 41, hit 32
    # all but the prompt's last token, never a padded tail
    assert [ev[2] for ev in a] == [16, 16, 16, 1]
    assert [ev[2] for ev in b] == [16, 16, 8]
    zero = sched._row_cache(CHUNK)
    for pieces in (a, b):
        assert pieces[0][3].states[0] is zero.states[0]
        for before, after in zip(pieces, pieces[1:]):
            for kind in ("states", "convs"):
                for x, y in zip(getattr(before[4], kind),
                                getattr(after[3], kind)):
                    assert x is y
    assert all(not np.asarray(x).any() for x in zero.states + zero.convs)
    assert sum(ev == ("prefill",) for ev in seen.log) == 0
    # the hit shared its pages and saved no compute
    snap = reg.snapshot()["counters"]
    assert snap["serving_state_recomputed_tokens_total"] == 32
    assert snap["serving_prefix_cache_hit_tokens_total"] == 32
    assert snap["serving_prefill_chunks_total"] == 7
    # 49 // 16 pages of the first, none new of the second (40 // 16)
    assert sched.slots.cached_prefix_pages == kept + 3 + 0
    reg.clear()
    _scored(served)


def test_chunked_streams_equal_unchunked_ones(system, watched):
    """The same requests through a scheduler that admits them whole
    (the cell's chunk is longer than any of them) and through one that
    admits them by chunks of 16, a piece a decode dispatch behind the
    rows that run: token for token the same streams — a last chunk
    that is full, one that is padded, one that absorbs nothing (49 =
    3 x 16 + 1), a prompt of a chunk or less (admitted whole by both) —
    the running rows get their token every call, and a slot in
    mid-prefill is masked: the steps in between leave its state row as
    its release left it."""
    from triton_distributed_tpu.serving import (
        ContinuousBatchingScheduler, SchedulerConfig)
    rng = np.random.default_rng(59)
    prompts = [rng.integers(0, 256, n).tolist()
               for n in (9, 64, 45, 49, 16, 50)]
    assert system.model.prefill_chunk > 64
    whole = ContinuousBatchingScheduler(
        system.model, system.params, SchedulerConfig(
            num_slots=4, max_seq=128, kv_layout="paged"))
    assert whole._chunk == system.model.prefill_chunk
    reqs = [_request(p, 8) for p in prompts]
    whole.run(reqs)
    sched, seen = watched
    again = [_request(p, 8) for p in prompts]
    for r in again:
        sched.submit(r)
    mid = 0
    while sched.has_work():
        sched.step()
        adm = sched._underway
        if adm is not None and adm.slot is not None:
            mid += 1
            cache = sched.slots.cache
            assert all(not np.asarray(x[adm.slot]).any()
                       for x in cache.states + cache.convs)
            assert adm.carry is not None
    assert mid >= 8
    assert [ev[:2] for ev in seen.log if ev[0] != "step"] == (
        [("prefill",)] + [("chunk", at) for at in (0, 16, 32, 48)]
        + [("chunk", at) for at in (0, 16, 32)]
        + [("chunk", at) for at in (0, 16, 32, 48)] + [("prefill",)]
        + [("chunk", at) for at in (0, 16, 32, 48)])
    # at most one enqueue between two decode dispatches while rows run
    between, n, running = [], 0, False
    for ev in seen.log:
        if ev[0] == "step":
            if running:
                between.append(n)
            n, running = 0, True
        else:
            n += 1
    assert max(between) == 1
    assert [r.generated for r in again] == [r.generated for r in reqs]
    _scored(again)


def test_giving_up_in_mid_prefill_returns_slot_pages_and_carry(watched):
    sched, seen = watched
    rng = np.random.default_rng(53)
    runner = _request(rng.integers(0, 256, 15).tolist(), 12)
    long = _request(rng.integers(0, 256, 50).tolist(), 4)
    sched.submit(runner)
    sched.step()
    sched.submit(long)
    sched.step()
    sched.step()
    adm = sched._underway
    assert adm is not None and adm.req is long and adm.done == 2
    assert adm.carry is not None and adm.slot is not None
    held = sched.slots.used_pages       # the runner's and 4 of 50 tokens
    assert sched.slots.free_slots == 2
    resets = sched.slots.state_resets
    sched._give_up_underway()
    assert sched._underway is None and adm.carry is None
    assert sched.slots.used_pages == held - 4
    assert sched.slots.free_slots == 3
    assert sched.slots.state_resets == resets + 1
    assert sched._queue[0] is long
    # and it starts over, from position 0 and a zero state
    del seen.log[:]
    sched.drain()
    assert [ev[1] for ev in seen.chunks()] == [0, 16, 32, 48]
    assert seen.chunks()[0][3].states[0] is sched._row_cache(
        CHUNK).states[0]
    assert long.finish_reason == runner.finish_reason
    assert len(long.generated) == 4 and long.preemptions == 0
    _scored([runner, long])


def test_no_kind_of_chunk_argument_is_first_met_after_warm_up(
        devices, monkeypatch):
    """`tests/test_serving_pipeline.py`'s case for this family, whose
    chunks carry the delta rule's state: the benchmark's own `warm_up`
    meets every kind of argument of the chunk program — the zero row
    and a carried one, no pages and the slot's — and of the scatter and
    the insert behind it; a window's chunked admissions then compile
    nothing."""
    from tests import test_serving_pipeline as pipeline
    pipeline.chunk_arguments_are_met_in_warm_up(
        "solar_open2", devices, pipeline.Compiled(), monkeypatch)


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
