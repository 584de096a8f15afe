"""Nemotron-H family on the CPU at tiny sizes: the program
(`models.nemotron_h.NemotronH` — one mixer a layer: Mamba-2 over a
recurrent state a slot, softmax attention without positions over paged
K/V, a SHARE of a latent expert layer of two-matrix squared-ReLU
experts; Pallas kernels in interpret mode) against the plain float32
reference (`cellbench.references.nemotron_h`, which imports nothing of
the program), on seeded weights laid in by the benchmark's own adapter.

Tolerances.  The program computes in bfloat16 with float32 accumulation
(the state and its recurrence in float32); the reference in float32.
At these sizes (5 layers, hidden 128) the logits' own spread is 1.0 and
the program's worst logit of a position lies a median 0.025-0.027
from the reference's (measured), under `LOGIT_TOL` = 0.08 at every
position but those a routing near-tie reaches: where bfloat16 rounding
flips one of a token's six experts — here often between an expert this
chip holds and one it does not — that token's logits move by up to 1.2
(the routed sum is scaled by 5), and because the state-space layers
carry what they absorbed, a few later positions move too (measured: 2
and 0 of two sequences' 34 positions past 0.08).  So a sequence passes
with at most `FLIPS` = 6 positions past the tolerance.  The same
comparison on the reference's float8 control reads 0.24-0.6 (median
0.36-0.39) at EVERY position and is checked to FAIL.  Kernels against
the recurrence itself are float32 on both sides: 1e-4 of the values'
scale.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from cellbench import correctness
from cellbench.adapters import nemotron_h as adapter
from cellbench.references import nemotron_h as reference
from triton_distributed_tpu.kernels import mamba2, moe_utils
from triton_distributed_tpu.layers.moe_mlp import HELD_STATS, SparseMoE
from triton_distributed_tpu.models import AutoLLM, ModelConfig, nemotron_h
from triton_distributed_tpu.models.nemotron_h import NemotronH
from triton_distributed_tpu.serving.engine_batched import (
    pad_prompt, pick_bucket)
from triton_distributed_tpu.serving.pages import PagedKV

LOGIT_TOL = 0.08
FLIPS = 6

#: One slot's recurrent state at test size: two state-space layers of 8
#: heads x 64 x 128 float32 and 3 convolution inputs of 512 + 2 x 2 x
#: 128 channels.
STATE = 2 * (8 * 64 * 128 * 4 + 3 * 1024 * 2)
#: Bytes of a 16-token page of the ONE attention layer (K and V, 2
#: heads of 16).
PAGE = 2 * 2 * 16 * 16 * 2

#: The published `config.json` keys at test size: the pattern's kinds
#: kept (state-space, experts, attention without positions, one mixer a
#: layer), top-6 of 32 two-matrix experts in a latent half the hidden
#: size beside an ungated shared expert, the state-space head its
#: published 64 x 128, four chips sharing each layer's 32 experts.
TINY = {
    "model_type": "nemotron_h", "vocab_size": 256, "hidden_size": 128,
    "intermediate_size": 96, "num_hidden_layers": 5,
    "hybrid_override_pattern": "MEM*E", "num_attention_heads": 8,
    "num_key_value_heads": 2, "head_dim": 16, "mamba_num_heads": 8,
    "mamba_head_dim": 64, "n_groups": 2, "ssm_state_size": 128,
    "conv_kernel": 4, "chunk_size": 128, "moe_intermediate_size": 96,
    "moe_latent_size": 64, "moe_shared_expert_intermediate_size": 192,
    "n_routed_experts": 8, "n_shared_experts": 1,
    "num_experts_per_tok": 6, "routed_scaling_factor": 5.0,
    "norm_topk_prob": True, "n_group": 1, "topk_group": 1,
    "mlp_hidden_act": "relu2", "use_conv_bias": True, "use_bias": False,
    "mamba_proj_bias": False, "attention_bias": False, "mlp_bias": False,
    "layer_norm_epsilon": 1e-5, "rope_theta": 10000,
    "tie_word_embeddings": False, "torch_dtype": "bfloat16",
    "share": {"chips": 4, "experts_of_layer": 32,
              "experts_held": [0, 8]},
    "serving": {"num_slots": 2, "max_seq": 128,
                "kv_budget_bytes_per_chip": 2 * STATE + 16 * PAGE,
                "max_queue": 16},
}
SEED = 7
DIMS = reference.dims_of(TINY)


@pytest.fixture(scope="module")
def system(devices):
    """The benchmark's adapter at test size: the program with the
    reference's weights, behind its scheduler."""
    return adapter.System(TINY, SEED, devices[:1])


def _ref_logits(tokens, first, n_out, precision="f32"):
    pad = np.zeros(128, np.int64)
    pad[:len(tokens)] = tokens
    return np.asarray(reference.logits_at(DIMS, SEED, pad, first, n_out,
                                          precision=precision))


def _row_for(model, bucket, length):
    """The prefill's input row: the state absorbs ``length`` tokens."""
    return dataclasses.replace(
        model.create_cache(1, bucket),
        length=np.full((1,), length, np.int32))


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
    """Two requests of different lengths in one batch, each prefilled
    through a PADDED bucket, inserted into the paged pool and the state
    pool, then 34 decode steps through state, convolution tail and
    pages (crossing two page boundaries), teacher-forced: the serving
    path's own artefacts, logits kept."""
    model, params = system.model, system.params
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 256, n).tolist() for n in (21, 50)]
    steps = 34
    teacher = [rng.integers(0, 256, steps).tolist() for _ in prompts]
    slots = PagedKV(model, len(prompts), max_seq=128, page_size=16,
                    prefix_cache=False)
    prefill = jax.jit(model.make_prefill_fn())
    decode = jax.jit(model.make_paged_decode_fn(page_size=16))
    for p in prompts:
        bucket = pick_bucket(len(p), (16, 32, 64, 128))
        ids, s = pad_prompt(p, bucket)
        _, row = prefill(params, ids, _row_for(model, bucket, s - 1))
        slots.insert_prefill(row, p, s, jnp.zeros((2,), jnp.uint32), [])
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
    return prompts, teacher, steps, np.stack(got), slots


@pytest.mark.parametrize("row", [0, 1])
def test_decode_logits_match_reference(decoded, row):
    prompts, teacher, steps, got, _ = decoded
    p = prompts[row]
    seq = p + teacher[row][:steps - 1]
    ref = _ref_logits(seq, len(p) - 1, steps)
    err = np.abs(got[:, row] - ref).max(axis=1)
    assert np.median(err) < LOGIT_TOL / 2, err
    assert (err > LOGIT_TOL).sum() <= FLIPS, err


def test_float8_control_fails_the_tolerance(decoded):
    """The tolerance would catch a lower precision: the reference's own
    float8 control lies outside it."""
    prompts, teacher, steps, *_ = decoded
    p = prompts[1]
    seq = p + teacher[1][:steps - 1]
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
    decode = jax.jit(model.make_paged_decode_fn(page_size=16))
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
    prefill's (the chunks' kernels cut the sequence elsewhere: float32
    rounding), and the decode steps behind them give the whole
    prefill's logits and the float32 reference's within the file's
    tolerance."""
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
    for name in ("mamba2_prefill_chunk", "moe_prefill_relu2_up",
                 "moe_prefill_relu2_down", "flash_attention_fwd"):
        assert name in text
    assert "mamba2_decode_step" not in text
    assert " cos " not in text and " sin " not in text


def test_decode_leaves_its_counts_in_the_cache(decoded):
    """`PagedKVCache.stats` after a step, in `NemotronH.STATS` order:
    the HELD experts' pairs and those routed elsewhere add up to rows x
    top-k x EXPERT layers (two of the five); both rows were live."""
    *_, slots = decoded
    assert NemotronH.STATS == HELD_STATS + ("live_slots",)
    pairs, hit, load, elsewhere, live = np.asarray(slots.cache.stats)
    assert pairs + elsewhere == 2 * 6 * 2
    assert 1 <= hit <= min(pairs, 8 * 2) and 0 < load <= 1
    assert live == 2


def test_programs_name_their_kernels(system):
    cache = system.sched.slots.cache
    text = str(jax.make_jaxpr(system.model.make_paged_decode_fn(16))(
        system.params, jnp.zeros((2,), jnp.int32), cache))
    for name in ("mamba2_decode_step", "flash_decode_paged",
                 "moe_decode_relu2_up", "moe_decode_relu2_down"):
        assert name in text
    for name in ("mamba2_prefill_chunk", "moe_decode_gate_up",
                 "moe_decode_down"):
        assert name not in text
    ids = jnp.zeros((1, 64), jnp.int32)
    text = str(jax.make_jaxpr(system.model.make_prefill_fn())(
        system.params, ids, system.model.create_cache(1, 64)))
    for name in ("mamba2_prefill_chunk", "moe_prefill_relu2_up",
                 "moe_prefill_relu2_down"):
        assert name in text
    assert "mamba2_decode_step" not in text
    # and nothing is rotated
    assert " cos " not in text and " sin " not in text


def test_autollm_finds_the_family_and_tp_is_refused(devices):
    cfg = ModelConfig.tiny_nemotron_h()
    one = Mesh(np.array(devices[:1]), ("tp",))
    model = AutoLLM(cfg, one)
    assert isinstance(model, NemotronH)
    assert (model.num_ssm, model.num_attn) == (2, 1)
    # a layer is one mixer: an expert layer's parameters hold no cache
    params = jax.eval_shape(model.init_params, jax.random.key(0))
    assert [sorted(lp) for lp in params["layers"]] == [["ln", "mixer"]] * 5
    assert "gate" not in params["layers"][1]["mixer"]
    with pytest.raises(AssertionError, match="one device"):
        NemotronH(cfg, Mesh(np.array(devices[:2]), ("tp",)))


# ---------------------------------------------------------------------------
# the state-space kernels against the recurrence
# ---------------------------------------------------------------------------

H, P, G, N = 8, 64, 2, 128


def _ssm_inputs(t, b=2, seed=0):
    ks = jax.random.split(jax.random.key(seed), 5)
    x = jax.random.normal(ks[0], (b, t, H * P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, t, H)) - 2.0)
    a = -jax.random.uniform(ks[2], (H,), minval=1.0, maxval=16.0)
    bm = jax.random.normal(ks[3], (b, t, G * N))
    cm = jax.random.normal(ks[4], (b, t, G * N))
    return x, dt, a, bm, cm


def _recurrence(x, dt, a, bm, cm, state=None):
    b, t = dt.shape[:2]
    y, s = mamba2.mamba2_recurrent_reference(
        x.reshape(b, t, H, P), dt, a, bm.reshape(b, t, G, N),
        cm.reshape(b, t, G, N), state)
    return y.reshape(b, t, H * P), s


def _close(got, want, tol=1e-4):
    return float(jnp.abs(got - want).max()) < tol * max(
        1.0, float(jnp.abs(want).max()))


def test_the_pool_keeps_two_heads_side_by_side():
    s = jax.random.normal(jax.random.key(0), (3, H, P, N))
    pooled = mamba2.pair_state(s)
    assert pooled.shape == (3, H // 2, N, 2 * P)
    assert (pooled[1, 2, 5, P + 9] == s[1, 5, 9, 5]).all()
    assert (mamba2.unpair_state(pooled) == s).all()


def test_chunked_prefill_equals_the_recurrence_across_a_chunk_edge():
    """Two chunks: the outputs of every position, the state after the
    last, and — cut after the first chunk — the state AT the edge."""
    x, dt, a, bm, cm = _ssm_inputs(2 * mamba2.CHUNK)
    y_ref, s_ref = _recurrence(x, dt, a, bm, cm)
    y, s = mamba2.mamba2_prefill_chunk(x, dt, a, bm, cm)
    assert _close(y, y_ref) and _close(mamba2.unpair_state(s), s_ref)
    c = mamba2.CHUNK
    _, s1 = mamba2.mamba2_prefill_chunk(x[:, :c], dt[:, :c], a,
                                        bm[:, :c], cm[:, :c])
    _, s1_ref = _recurrence(x[:, :c], dt[:, :c], a, bm[:, :c], cm[:, :c])
    assert _close(mamba2.unpair_state(s1), s1_ref)


def test_a_padded_row_moves_neither_state_nor_tail(system):
    """Through the layer, which pads to whole chunks and masks what
    lies past each row's length: 100 tokens of which the state absorbs
    77 — kernels against the recurrence, and against the recurrence
    over those 77 tokens alone."""
    layer = dataclasses.replace(system.model.ssm, mode="fused")
    golden = dataclasses.replace(layer, mode="xla")
    p = system.params["layers"][0]["mixer"]
    x = jax.random.normal(jax.random.key(5), (100, 128)).astype(
        jnp.bfloat16)
    n = jnp.asarray([77], jnp.int32)
    y, s, c = layer.prefill(x, p, 1, n)
    y_ref, s_ref, c_ref = golden.prefill(x, p, 1, n)
    assert s.shape == (1, *layer.state_shapes[0])
    assert _close(s, s_ref)
    assert float(jnp.abs(y.astype(jnp.float32)
                         - y_ref.astype(jnp.float32))[:77].max()) < 2e-2
    assert (c == c_ref).all()
    _, s77, c77 = golden.prefill(x[:77], p, 1, n)
    assert _close(s, s77) and (c == c77).all()


def test_the_chunked_kernel_starts_from_a_carried_state():
    """From a state that is not zero: outputs and final state are the
    recurrence's from that state; and cut in two calls, the second
    starting from what the first returned, the kernel gives bit for bit
    what it gives in one."""
    c = mamba2.CHUNK
    x, dt, a, bm, cm = _ssm_inputs(2 * c, seed=4)
    s0 = jax.random.normal(jax.random.key(11), (2, H, P, N))
    y_ref, s_ref = _recurrence(x, dt, a, bm, cm, s0)
    y, s = mamba2.mamba2_prefill_chunk(x, dt, a, bm, cm,
                                       mamba2.pair_state(s0))
    assert _close(y, y_ref) and _close(mamba2.unpair_state(s), s_ref)
    y1, s1 = mamba2.mamba2_prefill_chunk(
        x[:, :c], dt[:, :c], a, bm[:, :c], cm[:, :c],
        mamba2.pair_state(s0))
    y2, s2 = mamba2.mamba2_prefill_chunk(
        x[:, c:], dt[:, c:], a, bm[:, c:], cm[:, c:], s1)
    assert (jnp.concatenate([y1, y2], axis=1) == y).all()
    assert (s2 == s).all()
    # dt = 0 everywhere: the state comes back as it went in
    _, same = mamba2.mamba2_prefill_chunk(x[:, :c], 0 * dt[:, :c], a,
                                          bm[:, :c], cm[:, :c], s1)
    assert (same == s1).all()


#: (tokens a piece, the prompt's tokens): the state absorbs all but the
#: prompt's last token, so the last piece absorbs 35 of 64 (the rest a
#: padded tail); 33 of 48 in three pieces; 63 (a last piece that is
#: full); then fewer than the convolution's 3 kept inputs — 2, 1 and 0.
PIECES = {"two": (64, 100), "three": (48, 130), "full": (64, 128),
          "absorbs_2": (64, 67), "absorbs_1": (64, 66),
          "absorbs_0": (64, 65)}


@pytest.mark.parametrize("mode", ["fused", "xla"])
@pytest.mark.parametrize("case", sorted(PIECES))
def test_a_prefill_in_pieces_equals_the_prefill_in_one(system, case, mode):
    """`Mamba2Mixer.prefill` piece by piece — each from the state and
    the convolution's tail the one before it returned — against one
    prefill over the same rows: output, state and tail.  A last piece
    that absorbs fewer tokens than the convolution keeps hands on
    inputs of the piece BEFORE it."""
    size, t = PIECES[case]
    layer = dataclasses.replace(system.model.ssm, mode=mode)
    p = system.params["layers"][0]["mixer"]
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
    assert _close(s, s_ref)
    assert (c == c_ref).all()
    err = jnp.abs(jnp.concatenate(ys).astype(jnp.float32)
                  - y_ref.astype(jnp.float32))
    assert float(err.max()) < 2e-2, float(err.max())
    # and the tail is the inputs at positions n-3 .. n-1, which for the
    # short last pieces lie in the piece before
    want = layer._split(jnp.dot(
        x, p["w_in"], preferred_element_type=jnp.float32).astype(
            x.dtype))[1][n - 3:n]
    assert (c.reshape(3, -1) == want).all()


def test_a_prefill_that_continues_into_decode():
    """The chunked kernel's state handed to the one-token kernel: ten
    more tokens equal the recurrence over all of them."""
    t = mamba2.CHUNK
    x, dt, a, bm, cm = _ssm_inputs(t + 16, b=1)
    y_ref, _ = _recurrence(x, dt, a, bm, cm)
    _, s = mamba2.mamba2_prefill_chunk(x[:, :t], dt[:, :t], a, bm[:, :t],
                                       cm[:, :t])
    step = jax.jit(mamba2.mamba2_decode_step)
    live = jnp.asarray([True])
    for i in range(t, t + 10):
        y, s = step(x[:, i], dt[:, i], a, bm[:, i], cm[:, i], s, live)
        assert _close(y, y_ref[:, i])
    _, s10 = _recurrence(x[:, :t + 10], dt[:, :t + 10], a, bm[:, :t + 10],
                         cm[:, :t + 10])
    assert _close(mamba2.unpair_state(s), s10)


@pytest.mark.parametrize("live", [(True, False, True, False),
                                  (False, False, False, True),
                                  (False, False, False, False)])
def test_the_decode_kernel_leaves_rows_that_are_not_live_untouched(live):
    x, dt, a, bm, cm = _ssm_inputs(1, b=4, seed=3)
    state = jax.random.normal(jax.random.key(9), (4, H // 2, N, 2 * P))
    y, new = mamba2.mamba2_decode_step(
        x[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0], state, jnp.asarray(live))
    y_ref, s_ref = _recurrence(x, dt, a, bm, cm,
                               mamba2.unpair_state(state))
    for row, alive in enumerate(live):
        if alive:
            assert _close(mamba2.unpair_state(new)[row], s_ref[row])
            assert _close(y[row], y_ref[row, 0])
        else:
            assert (new[row] == state[row]).all()
            assert (y[row] == 0).all()


# ---------------------------------------------------------------------------
# the latent expert layer and its share
# ---------------------------------------------------------------------------

def test_two_matrix_experts_in_a_latent_match_the_dense_form(system):
    """`SparseMoE(act="relu2", latent=...)`: the packed kernels against
    every expert over every token (mode "xla"), decode- and
    prefill-shaped, and the weights it has: no gate matrix anywhere,
    experts `latent` wide behind the two shared projections."""
    moe = system.model.moe
    assert (moe.act, moe.latent, moe.shared_ffn) == ("relu2", 64, 192)
    mlp = system.params["layers"][1]["mixer"]
    assert sorted(mlp) == ["down", "latent_down", "latent_up", "router",
                           "router_bias", "shared", "up"]
    assert mlp["up"].shape == (8, 64, 96) and sorted(
        mlp["shared"]) == ["down", "up"]
    golden = dataclasses.replace(moe, mode="xla")
    for rows, phase in ((3, "decode"), (40, "prefill")):
        x = jax.random.normal(jax.random.key(rows), (rows, 128)).astype(
            jnp.bfloat16)
        y, stats = moe(x, mlp, phase=phase)
        y_ref, stats_ref = golden(x, mlp, phase=phase)
        np.testing.assert_array_equal(np.asarray(stats),
                                      np.asarray(stats_ref))
        err = jnp.abs(y.astype(jnp.float32) - y_ref.astype(jnp.float32))
        assert float(err.max()) < 0.05, float(err.max())
    with pytest.raises(ValueError, match="expert form"):
        SparseMoE(hidden=8, ffn=8, num_experts=2, topk=1, act="gelu")


def test_all_the_shares_add_up_to_the_uncut_layer(system):
    """Guide section 4's test: the routed parts the FOUR chips' layers
    give (experts 0-7, 8-15, 16-23, 24-31), plus the shared expert
    counted once, are the reference's UNCUT layer (router over 32, all
    32 experts, the latent projections around them)."""
    whole = reference.dims_of(TINY, held=(0, 32))
    key = reference.layer_key(reference.base_key(SEED), 1)
    w = reference.layer_weights(key, DIMS, "E")
    x = jax.random.normal(jax.random.key(6), (48, 128), jnp.float32)
    xb = x.astype(jnp.bfloat16)
    x = xb.astype(jnp.float32)
    # the reference's layer takes the residual stream and norms it; the
    # program's layer is handed the normed input
    ub = reference._rms(x, w["ln"], 1e-5).astype(jnp.bfloat16)
    uncut = sum(reference.moe_parts(x, key, whole))
    mlp = system.params["layers"][1]["mixer"]
    shared = system.model.moe._shared(ub, mlp["shared"]).astype(
        jnp.bfloat16).astype(jnp.float32)
    total, elsewhere = shared, 0
    for lo in (0, 8, 16, 24):
        part = reference.dims_of(TINY, held=(lo, lo + 8))
        blocks = [reference.expert_weights(key, part, b)
                  for b in reference.held_blocks(part)]
        params = dict(mlp, **{k: jnp.concatenate([b[k] for b in blocks])
                              for k in ("up", "down")})
        layer = dataclasses.replace(system.model.moe, held=(lo, lo + 8))
        y, stats = layer(ub, params, phase="decode")
        total = total + (y.astype(jnp.float32) - shared)
        elsewhere += float(stats[3])
        assert float(stats[0]) + float(stats[3]) == 48 * 6
    assert elsewhere == 3 * 48 * 6    # each pair is held exactly once
    err = float(jnp.abs(total - uncut).max())
    assert err < 0.05 * float(jnp.abs(uncut).max()), (
        err, float(jnp.abs(uncut).max()))


def test_a_step_whose_pairs_all_went_elsewhere_at_top_22():
    """No pair for a held expert, at this family's 22 pairs a row: the
    plan still names one block (-1 would read outside the array: on the
    chip, a hang) and the layer gives the shared expert alone."""
    ids = jnp.broadcast_to(jnp.arange(8, 30, dtype=jnp.int32), (2, 22))
    plan = moe_utils.pack_by_expert(ids, jnp.ones((2, 22)), 64, 16,
                                    held=(0, 8))
    assert int(plan.n_blocks) == 1 and int(plan.counts[-1]) == 44
    assert (np.asarray(plan.block_expert) < 8).all()
    assert (np.asarray(plan.row_weight) == 0).all()
    assert (np.asarray(plan.row_token) == 2).all()     # zero rows
    moe = SparseMoE(hidden=128, ffn=96, num_experts=64, topk=22,
                    held=(0, 8), act="relu2", latent=64, shared_ffn=192,
                    routed_scaling=5.0)
    p = moe.init_params(jax.random.key(1))
    x = jax.random.normal(jax.random.key(3), (2, 128)).astype(
        jnp.bfloat16)
    # a selection bias that sends everything to experts 8..63
    far = dict(p, router_bias=jnp.where(jnp.arange(64) >= 8, 10.0, -10.0))
    y, stats = moe(x, far, phase="decode")
    assert float(stats[0]) == 0 and float(stats[3]) == 44
    want = moe._shared(x, p["shared"])
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(want.astype(jnp.bfloat16),
                                          np.float32), atol=1e-6)


# ---------------------------------------------------------------------------
# the state pool behind the scheduler
# ---------------------------------------------------------------------------

def test_the_state_pool_is_priced_in_the_kv_budget(system):
    slots = system.sched.slots
    assert slots.state_bytes_per_slot == STATE
    assert slots.bytes_per_page == PAGE and slots.usable_pages == 16
    assert slots.kv_budget_bytes == 2 * STATE + 16 * PAGE
    # an expert layer owns no cache: two states, one pair of pools
    cache = slots.cache
    assert (len(cache.states), len(cache.convs), len(cache.ks)) == (2, 2, 1)
    assert cache.states[0].shape == (2, 4, 128, 128)
    assert cache.convs[0].shape == (2, 3 * 1024)


def test_churn_hands_a_zeroed_state_row_to_the_next_owner(system):
    """Seven requests over two slots through the scheduler's own
    admit / retire path: every slot is handed on several times.  What
    was served is what the reference puts first (a state row that kept
    anything of its last owner would not be), every release zeroed its
    row, and a drained pool holds zeros."""
    rng = np.random.default_rng(21)
    sched = system.sched
    resets = sched.slots.state_resets
    rows = []
    for i, (plen, new) in enumerate(((16, 6), (21, 9), (34, 5), (7, 8),
                                     (18, 7), (40, 4), (12, 6))):
        prompt = rng.integers(0, 256, plen).tolist()
        handle, why = system.submit(prompt, new, 0.0, None)
        assert handle is not None, why
        rows.append((i, prompt, new, handle))
    while system.has_work():
        system.step()
    sample = []
    for i, prompt, new, handle in rows:
        assert system.finished_ok(handle, new)
        sample.append({"index": i, "prompt": prompt,
                       "prompt_len": len(prompt),
                       "tokens": list(handle.generated), "ok": True})
    assert sched.slots.state_resets - resets == 7
    cache = sched.slots.cache
    assert all(not np.asarray(x).any()
               for x in cache.states + cache.convs)
    res = correctness.score(reference, DIMS, SEED, sample, 128, 16,
                            control=True)
    assert res["requests"] == 7 and res["tokens"] == 45
    # limits between the two readings (measured: the program 0.0 /
    # 0.0 — every served token is the reference's first — the float8
    # control 0.61 / 0.045)
    limits = {"served_gap_max": 0.2, "served_gap_mean": 0.01}
    assert correctness.judge(res["program"], limits)[0], res
    assert not correctness.judge(res["control"], limits)[0], res
    assert reference.fp8_change(DIMS, SEED) > 0.01


def test_a_row_behind_a_retiring_one_has_its_token_before_the_reset(
        system, monkeypatch):
    """Releasing a slot of this model dispatches a program (the state
    row's zeroing).  In the read that ends the first row of the loop
    the second row has its token before `slots.release` is called; the
    row ends with the read's own `now`, its state row is zeroed in
    that call, and the slot's next owner and both neighbours are
    served what each is served alone."""
    from tests.test_serving_pipeline import (
        CommitLog, assert_delivered_then_retired)
    rng = np.random.default_rng(33)
    sched = system.sched
    prompts = [rng.integers(0, 256, n).tolist() for n in (11, 19, 14)]
    news = (3, 8, 5)

    def serve(which, on_token=None):
        reqs = []
        for i in which:
            req, why = system.submit(prompts[i], news[i], 0.0, on_token)
            assert req is not None, why
            reqs.append(req)
        return reqs

    alone = []
    for i in range(3):
        (req,) = serve([i])
        while system.has_work():
            system.step()
        alone.append(req.generated)
    log = CommitLog(sched, monkeypatch)
    reqs = serve(range(3), log.on_token)
    first, second, third = reqs
    resets = sched.slots.state_resets
    while first.finish_reason is None:
        out, events, _ = log.step()
    # paced: the second row was admitted a call after the first, so it
    # goes on; the third waits for the slot
    assert out == {"admitted": 0, "active": 2, "retired": 1}
    assert_delivered_then_retired(events, [first, second], [first.slot])
    assert sched.slots.state_resets == resets + 1
    assert system.finished_ok(first, news[0])
    assert first.t_finish == first.t_last_token == second.t_last_token
    _, _, admitted = log.step()
    assert admitted == [third] and third.slot == first.slot
    while system.has_work():
        log.step()
    assert [r.generated for r in reqs] == alone


def test_admissions_are_paced_while_rows_run(system):
    """A model with recurrent layers is paced: with rows running at
    most ONE prefill — here a short prompt's whole prefill, the chunk
    being 512 tokens — is enqueued between two decode dispatches:
    three requests due at once go in a call apart, and so does whatever
    follows the first admission into an idle server."""
    from triton_distributed_tpu.serving import (
        ContinuousBatchingScheduler, Request, SchedulerConfig)
    sched = ContinuousBatchingScheduler(
        system.model, system.params, SchedulerConfig(
            num_slots=4, max_seq=128, kv_layout="paged"))
    assert sched._paced and sched._stateful
    assert sched._chunk == nemotron_h.PREFILL_CHUNK == 512
    rng = np.random.default_rng(31)

    def send(n, new):
        req = Request(rng.integers(0, 256, n).tolist(), new,
                      eos_token_ids=(), seed=0)
        assert sched.submit(req)
        return req

    send(9, 24)
    sched.step()
    sched.step()
    late = [send(n, 3) for n in (5, 12, 7)]
    assert [sched.step()["admitted"] for _ in range(3)] == [1, 1, 1]
    while sched.has_work():
        sched.step()
    assert all(len(r.generated) == 3 for r in late)
    for n in (6, 11, 8):
        send(n, 2)
    assert [sched.step()["admitted"] for _ in range(3)] == [1, 1, 1]
    while sched.has_work():
        sched.step()


# ---------------------------------------------------------------------------
# a long prompt goes in by chunks that start from the carried state
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def chunking(devices):
    """The adapter's system with the model's chunk at test size: the
    model reads `PREFILL_CHUNK` when it is built."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(nemotron_h, "PREFILL_CHUNK", CHUNK)
        yield adapter.System(TINY, SEED, devices[:1])


def _chunk_sched(chunking, **kw):
    from triton_distributed_tpu.serving import (
        ContinuousBatchingScheduler, SchedulerConfig)
    kw.setdefault("num_slots", 4)
    sched = ContinuousBatchingScheduler(
        chunking.model, chunking.params, SchedulerConfig(
            max_seq=128, kv_layout="paged", **kw))
    assert sched._chunk == CHUNK and sched._stateful and sched._paced
    return sched


class Enqueues:
    """Every enqueue of the scheduler's programs, in order: ("chunk",
    start, tokens the state absorbs, the row cache that went in, the
    row that came out), ("prefill",), ("step", rows running)."""

    def __init__(self, sched):
        self.log = []
        suffix, prefill, step = (sched._prefill_suffix, sched._prefill,
                                 sched._step)

        def chunk(p, ids, start, row, pools, pages):
            assert ids.shape == (1, CHUNK)
            out = suffix(p, ids, start, row, pools, pages)
            self.log.append(("chunk", int(start), int(row.length[0]),
                             row, out))
            return out

        def whole(*a):
            self.log.append(("prefill",))
            return prefill(*a)

        def stepping(p, tokens, cache, keys, active):
            self.log.append(("step", int(np.sum(active))))
            return step(p, tokens, cache, keys, active)
        sched._prefill_suffix, sched._prefill = chunk, whole
        sched._step = stepping

    def chunks(self):
        return [ev for ev in self.log if ev[0] == "chunk"]


def _request(prompt, new):
    from triton_distributed_tpu.serving import Request
    return Request(list(prompt), new, eos_token_ids=(), seed=0)


def _scored(rows, limits=None):
    """What was served against the reference, as the churn test."""
    sample = [{"index": i, "prompt": r.prompt, "prompt_len": len(r.prompt),
               "tokens": list(r.generated), "ok": True}
              for i, r in enumerate(rows)]
    res = correctness.score(reference, DIMS, SEED, sample, 128, 16,
                            control=True)
    limits = limits or {"served_gap_max": 0.2, "served_gap_mean": 0.01}
    assert correctness.judge(res["program"], limits)[0], res
    assert not correctness.judge(res["control"], limits)[0], res


def test_chunks_start_at_zero_despite_a_prefix_hit_and_carry_the_state(
        chunking):
    """Two prompts that share their first 32 tokens, one after the
    other.  The second finds two pages in the radix tree and shares
    them — for storage: its chunks still cover the prompt from position
    0 (a state has no snapshot; the tokens a snapshot would have saved
    are counted), each starts from the state and tail the one before it
    returned, the reusable zero row is never written, and what is
    served is the reference's."""
    from triton_distributed_tpu.observability import get_registry
    reg = get_registry()
    reg.clear()
    rng = np.random.default_rng(41)
    shared = rng.integers(0, 256, 32).tolist()
    prompts = [shared + rng.integers(0, 256, n).tolist() for n in (18, 9)]
    sched = _chunk_sched(chunking)
    seen = Enqueues(sched)
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
    assert sched.slots.cached_prefix_pages == 3 + 0   # 49 // 16, 40 // 16
    reg.clear()
    _scored(served)


def test_a_prefix_hit_on_a_short_prompt_keeps_the_whole_prefill(chunking):
    """At most a chunk long: today's whole prefill, from position 0 —
    the suffix branch stays closed to a model with a state."""
    rng = np.random.default_rng(43)
    prompt = rng.integers(0, 256, 16).tolist()
    sched = _chunk_sched(chunking)
    seen = Enqueues(sched)
    plans = []
    plan = sched._plan
    sched._plan = lambda *a: plans.append(plan(*a)) or plans[-1]
    served = [_request(prompt[:n], 4) for n in (16, 14)]
    for req in served:
        sched.run([req])
    assert [(adm.mode, adm.pieces, len(adm.shared)) for adm in plans] == [
        ("local", [(0, 16)], 0), ("local", [(0, 16)], 0)]
    # (a page is shared once a token lies beyond it)
    late = _request(prompt + [5], 3)
    sched.run([late])
    assert (plans[-1].mode, plans[-1].pieces, len(plans[-1].shared)) == (
        "chunk", [(0, 16), (16, 16)], 0)
    assert not seen.chunks()[-1][2]      # 17 tokens: the last absorbs 0
    _scored(served + [late])


def test_one_enqueue_between_two_decode_dispatches_while_rows_run(
        chunking):
    """Long and short prompts queued behind a running row: a chunk or
    a short prompt's whole prefill a decode dispatch, first come first;
    the running row gets its token every call, and a slot in
    mid-prefill is masked — the steps in between leave its state row
    as its release left it."""
    rng = np.random.default_rng(47)
    sched = _chunk_sched(chunking)
    runner = _request(rng.integers(0, 256, 9).tolist(), 30)
    sched.submit(runner)
    sched.step()
    sched.step()
    seen = Enqueues(sched)
    late = [_request(rng.integers(0, 256, n).tolist(), 3)
            for n in (50, 7, 33)]
    for r in late:
        sched.submit(r)
    mid = 0
    while any(r.finish_reason is None for r in late):
        before = len(runner.generated)
        sched.step()
        assert len(runner.generated) == before + 1
        adm = sched._underway
        if adm is not None and adm.slot is not None:
            mid += 1
            cache = sched.slots.cache
            assert all(not np.asarray(x[adm.slot]).any()
                       for x in cache.states + cache.convs)
            assert adm.carry is not None
    assert mid >= 4
    between, n = [], 0
    for ev in seen.log:
        if ev[0] == "step":
            between.append(n)
            n = 0
        else:
            n += 1
    assert max(between) == 1
    assert [ev[:2] for ev in seen.log if ev[0] != "step"] == (
        [("chunk", at) for at in (0, 16, 32, 48)] + [("prefill",)]
        + [("chunk", at) for at in (0, 16, 32)])
    sched.drain()
    assert len(runner.generated) == 30
    _scored(late)


def test_giving_up_in_mid_prefill_returns_slot_pages_and_carry(chunking):
    rng = np.random.default_rng(53)
    sched = _chunk_sched(chunking, prefix_cache=False)
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
    seen = Enqueues(sched)
    sched.drain()
    assert [ev[1] for ev in seen.chunks()] == [0, 16, 32, 48]
    assert seen.chunks()[0][3].states[0] is sched._row_cache(
        CHUNK).states[0]
    assert long.finish_reason == runner.finish_reason
    assert len(long.generated) == 4 and long.preemptions == 0
    _scored([runner, long])


def test_no_kind_of_chunk_argument_is_first_met_after_warm_up(
        devices, monkeypatch):
    """`tests/test_serving_pipeline.py`'s case for a model whose chunks
    carry a recurrent state: the benchmark's own `warm_up` meets every
    kind of argument of the chunk program — the zero row and a carried
    one, no pages and the slot's — and of the scatter and the insert
    behind it; a window's chunked admissions then compile nothing."""
    from tests import test_serving_pipeline as pipeline
    pipeline.chunk_arguments_are_met_in_warm_up(
        "nemotron_h", devices, pipeline.Compiled(), monkeypatch)


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
    assert kv.state_resets == 1
