"""SmallThinker family on the CPU at tiny sizes: the program
(`models.smallthinker.SmallThinker` — a sequential block whose router
scores the stream as it ENTERS the layer, a full layer without
positions before three window layers that rotate, 7 query heads a KV
head, gated-ReLU experts routed by softmax; Pallas kernels in interpret
mode) against the plain float32 reference
(`cellbench.references.smallthinker`, which imports nothing of the
program), on seeded weights laid in by the benchmark's own adapter.

Tolerances.  The program computes in bfloat16 with float32 accumulation;
the reference in float32.  Errors are in units of the position's logit
spread: at these sizes (4 layers, hidden 128) the program's worst logit
of a position lies a few hundredths of a spread from the reference's,
under `LOGIT_TOL` at every position but those a routing near-tie
reaches: where bfloat16 rounding of the stream flips one of a token's
six experts that token's logits move by tenths.  So a sequence passes
with at most `FLIPS` positions past the tolerance and a median under
half of it.  The same comparison on the program SERVING float8-rounded
weights, and on a twin whose router reads what the experts read, is
checked to FAIL.  The window is 16 tokens and a page 16, so a row gives
a page back every 16 tokens from its 32nd on.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench import model_math_smallthinker as math
from cellbench.adapters import smallthinker as adapter
from cellbench.references import smallthinker as reference
from triton_distributed_tpu.kernels.flash_attention import (
    attention_reference, flash_attention)
from triton_distributed_tpu.kernels.flash_decode import flash_decode_paged
from triton_distributed_tpu.layers.moe_mlp import SparseMoE
from triton_distributed_tpu.models import (
    AutoLLM, cohere2_moe, smallthinker, window_layers)
from triton_distributed_tpu.models.config import ModelConfig
from triton_distributed_tpu.models.smallthinker import SmallThinker
from triton_distributed_tpu.observability.tracing import get_tracer
from triton_distributed_tpu.serving import Request
from triton_distributed_tpu.serving.engine_batched import pad_prompt
from triton_distributed_tpu.serving.pages import PagedKV
# the dense decode over pages and the window table's invariant are the
# other window family's (the same window and page: 16, 16)
from tests.test_cohere2_moe import _dense_decode, _visible_pages_held

LOGIT_TOL = 0.1
FLIPS = 8
W, PS = 16, 16

#: The published `config.json` keys at test size: one period of the
#: layer pattern (full first), 14 query heads over 2 KV heads (7 a KV
#: head, as published), top-6 of 16 experts.
TINY = {
    "model_name": "smallthinker_tiny", "vocab_size": 256,
    "hidden_size": 128, "num_hidden_layers": 4,
    "sliding_window_layout": [0, 1, 1, 1], "rope_layout": [0, 1, 1, 1],
    "num_attention_heads": 14, "num_key_value_heads": 2, "head_dim": 16,
    "moe_ffn_hidden_size": 64, "moe_num_primary_experts": 16,
    "moe_num_active_primary_experts": 6,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "sliding_window_size": W, "rope_theta": 1500000, "rope_scaling": None,
    "rms_norm_eps": 1e-6, "max_position_embeddings": 128,
    "tie_word_embeddings": False, "torch_dtype": "bfloat16",
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
    mp.setattr(smallthinker, "PREFILL_CHUNK", CHUNK)
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
# 7 query rows a KV head through every attention kernel form
# ---------------------------------------------------------------------------

G = 7


@pytest.mark.parametrize("window,name", [
    (None, "flash_decode_paged"), (12, "swa_decode_paged"),
    (40, "swa_decode_paged")])
def test_paged_decode_takes_seven_rows_a_kv_head(window, name):
    """Both names of the paged decode kernel: every one of the 14 heads
    reads its own KV head's rows (head h reads KV head h // 7) — none
    dropped, none doubled."""
    rng = np.random.default_rng(7)
    lens, t, ps, hkv, d = (5, 33, 64, 17), 8, 8, 2, 16
    pool = 1 + len(lens) * t
    k = jnp.asarray(rng.standard_normal((pool, hkv, ps, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((pool, hkv, ps, d)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((len(lens), hkv * G, d)),
                    jnp.float32)
    table = 1 + np.arange(len(lens) * t, dtype=np.int32).reshape(-1, t)
    kv_len = jnp.asarray(lens, jnp.int32)
    got, _ = flash_decode_paged(
        q, k, v, jnp.asarray(table), kv_len,
        **(dict(window=window, name=name) if window else {}))
    want = _dense_decode(q, k, v, table, kv_len, window or 0)
    assert np.allclose(got, want, atol=2e-5)
    # each head's answer is its own: no two heads of a group agree
    assert len({np.asarray(got)[1, h].tobytes() for h in range(14)}) == 14


@pytest.mark.parametrize("sq,sk,off,window,traced", [
    (64, 64, 0, 0, False),        # whole, causal (packed schedule)
    (64, 64, 0, 16, False),       # whole, windowed
    (32, 96, 64, 0, True),        # a chunk over its prefix (kv_offset)
    (32, 96, 40, 24, True)])      # a chunk of a window layer
def test_flash_attention_takes_seven_rows_a_kv_head(sq, sk, off, window,
                                                    traced):
    rng = np.random.default_rng(sq + off + window)
    q = jnp.asarray(rng.standard_normal((1, 2 * G, sq, 16)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 2, sk, 16)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 2, sk, 16)), jnp.float32)
    kw = dict(window=window, name="swa_prefill_attention") if window else {}
    want = attention_reference(q, k, v, kv_offset=off, window=window)
    run = lambda o: flash_attention(        # noqa: E731
        q, k, v, kv_offset=o, block_q=16, block_k=16, **kw)
    got = jax.jit(run)(jnp.int32(off)) if traced else run(off)
    assert np.allclose(got, want, atol=2e-5)


# ---------------------------------------------------------------------------
# the expert layer: the gate's ReLU and the route's source
# ---------------------------------------------------------------------------

def _moe(mode="xla", act="relu"):
    return SparseMoE(hidden=128, ffn=64, num_experts=16, topk=6,
                     n_shared=0, mode=mode, scoring="softmax", act=act)


@pytest.mark.parametrize("phase,rows", [("decode", 8), ("prefill", 96)])
def test_fused_relu_experts_match_the_golden(phase, rows):
    xb = jnp.asarray(np.random.default_rng(6).standard_normal((rows, 128)),
                     jnp.bfloat16)
    params = _moe().init_params(jax.random.PRNGKey(2))
    assert set(params) == {"router", "gate", "up", "down"}
    want, _ = _moe()(xb, params)
    got, stats = _moe("fused")(xb, params, phase=phase)
    assert np.allclose(got.astype(jnp.float32), want.astype(jnp.float32),
                       atol=0.05)
    assert stats[0] == rows * 6
    # the gate is a ReLU and no SiLU: the two forms differ on the same
    # weights, in the golden and in the kernel alike
    silu, _ = _moe("fused", act="silu")(xb, params, phase=phase)
    assert np.abs(silu.astype(jnp.float32)
                  - got.astype(jnp.float32)).max() > 0.1
    # by hand, one token
    ids, w = _moe().route(xb[:1], params)
    f32 = lambda t: np.asarray(t, np.float32)       # noqa: E731
    x0 = f32(xb[0])
    by_hand = sum(
        float(wi) * (np.maximum(x0 @ f32(params["gate"][e]), 0)
                     * (x0 @ f32(params["up"][e]))) @ f32(params["down"][e])
        for e, wi in zip(np.asarray(ids[0]), np.asarray(w[0])))
    assert np.allclose(f32(want[0]), by_hand, atol=0.05)


def test_the_gated_kernels_keep_their_names_whatever_the_gate():
    xb = jnp.zeros((8, 128), jnp.bfloat16)
    params = _moe().init_params(jax.random.PRNGKey(2))
    text = str(jax.make_jaxpr(
        lambda x, p: _moe("fused")(x, p, phase="decode"))(xb, params))
    assert "moe_decode_gate_up" in text and "moe_decode_down" in text
    assert "relu2" not in text


def test_route_is_the_references_on_equal_input_and_is_the_early_one(
        system):
    """On equal input the program's route IS the reference's (the same
    six experts, the same weights); and the reference's route changes
    for most tokens when it is taken from the normed stream or from the
    post-attention stream instead of the layer's input (a norm only
    rescales a token and reweighs channels by 1 +- 0.1, so it moves the
    fewest: a quarter) — so a router that silently read either would
    not pass the comparisons below."""
    rng = np.random.default_rng(3)
    key = reference.layer_key(reference.base_key(SEED), 1)
    w = reference.layer_weights(key, DIMS)
    h = jnp.asarray(rng.standard_normal((64, 128)) * 3, jnp.bfloat16)
    ids, wts = system.model.moe.route(h, {"router": w["router"]})
    hf = h.astype(jnp.float32)
    dense = np.asarray(reference.router_weights(hf, w, DIMS))
    for i in range(64):
        assert set(np.flatnonzero(dense[i])) == set(np.asarray(ids[i]))
        assert np.allclose(dense[i, np.asarray(ids[i])], wts[i], atol=1e-6)
        assert abs(dense[i].sum() - 1) < 1e-5
    normed = reference.rms_norm(hf, w["ln1"], 1e-6)
    after = hf + reference.attention(normed, w, DIMS, True)
    post = reference.rms_norm(after, w["ln2"], 1e-6)
    for other in (normed, after, post):
        elsewhere = np.asarray(reference.router_weights(other, w, DIMS))
        changed = ((elsewhere > 0) != (dense > 0)).any(axis=1)
        assert changed.mean() > 0.25, changed.mean()
    # the layer hands the route's source to the call
    xb = h[:8]
    params = _moe().init_params(jax.random.PRNGKey(4))
    mine, _ = _moe()(xb, params)
    early, _ = _moe()(xb, params, route_from=h[8:16])
    same, _ = _moe()(xb, params, route_from=xb)
    assert np.array_equal(np.asarray(mine), np.asarray(same))
    assert not np.allclose(mine.astype(jnp.float32),
                           early.astype(jnp.float32), atol=0.05)


# ---------------------------------------------------------------------------
# program against reference
# ---------------------------------------------------------------------------

def test_registry_and_config_reader_know_the_family(system):
    cfg = system.model_cfg
    assert isinstance(AutoLLM(cfg, system.mesh), SmallThinker)
    assert cfg.layer_types == ("full_attention",) + (
        "sliding_attention",) * 3
    assert (cfg.moe_act, cfg.moe_scoring) == ("relu", "softmax")
    assert cfg.num_heads // cfg.num_kv_heads == 7
    assert system.model.window == W and system.model.prefill_chunk == CHUNK
    assert system.model.layer_kinds[0] == window_layers.FULL
    assert "lm_head" in system.params and system.model.STATS == (
        "pairs", "experts_hit", "expert_load_max")


def test_prefill_logits_match_reference(system):
    """Prompts of four windows: the prefill program's own logits, ONE
    position a prompt — so a routing near-tie at that position (module
    docstring) is the whole reading: the median of three prompts keeps
    the tolerance, and none is wrong by a spread."""
    prefill = jax.jit(system.model.make_prefill_fn())
    errs = []
    for seed in (1, 4, 5):
        prompt = np.random.default_rng(seed).integers(0, 256, 64).tolist()
        ids, _ = pad_prompt(prompt, 64)
        logits, _ = prefill(system.params, ids,
                            system.model.create_cache(1, 64))
        errs.append(float(_err(np.asarray(logits),
                               _ref_logits(prompt, 63, 1)).max()))
    assert np.median(errs) < LOGIT_TOL and max(errs) < 1.0, errs


def _decode(model, params, slots, prompts, teacher, steps):
    decode = jax.jit(model.make_paged_decode_fn(page_size=PS))
    got = []
    tokens = np.asarray([p[-1] for p in prompts], np.int32)
    for i in range(steps):
        for b, p in enumerate(prompts):
            assert slots.ensure(b, len(p) + i)
            _visible_pages_held(slots, b, len(p) + i)
        slots.flush()
        logits, slots.cache = decode(params, jnp.asarray(tokens),
                                     slots.cache)
        got.append(np.asarray(logits))
        tokens = np.asarray([t[i] for t in teacher], np.int32)
    return np.stack(got)


def _prefill_whole(model, params, slots, p):
    bucket = 32 if len(p) <= 32 else 64
    ids, s = pad_prompt(p, bucket)
    _, row = jax.jit(model.make_prefill_fn())(
        params, ids, model.create_cache(1, bucket))
    return slots.insert_prefill(row, p, s, jnp.zeros((2,), jnp.uint32), [])


def _prefill_chunks(model, params, slots, p):
    """The prompt in pieces of `CHUNK`, each over the pages of both
    kinds its predecessors left."""
    suffix = jax.jit(model.make_prefill_suffix_fn())
    s = len(p)
    slot = slots.begin_prefill(s, [])
    for at in range(0, s, CHUNK):
        ids, _ = pad_prompt(p[at:at + CHUNK], CHUNK)
        c = slots.cache
        pages = np.stack([slots.prefill_pages(slot),
                          slots.prefill_window_pages(slot)])
        row = suffix(params, ids, jnp.int32(at),
                     model.create_cache(1, CHUNK),
                     (c.ks, c.vs, c.wks, c.wvs), pages)
        last = at + CHUNK >= s
        slots.insert_rows(slot, row, at,
                          jnp.zeros((2,), jnp.uint32) if last else None)
    slots.finish_prefill(slot, p)
    return slot


def _two_rows(model, params, steps=40):
    """Two requests in one batch — one prefilled whole through a padded
    bucket, one in chunks over the pool — then teacher-forced decode
    steps: past two windows, across two page give-backs a row."""
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 256, n).tolist() for n in (21, 50)]
    teacher = [rng.integers(0, 256, steps).tolist() for _ in prompts]
    slots = PagedKV(model, 2, max_seq=128, page_size=PS,
                    prefix_cache=False)
    _prefill_whole(model, params, slots, prompts[0])
    _prefill_chunks(model, params, slots, prompts[1])
    got = _decode(model, params, slots, prompts, teacher, steps)
    return prompts, teacher, steps, got, slots


@pytest.fixture(scope="module")
def decoded(system):
    return _two_rows(system.model, system.params)


def _row_errors(run, row):
    prompts, teacher, steps, got, _ = run
    p = prompts[row]
    seq = p + teacher[row][:steps - 1]
    return _err(got[:, row], _ref_logits(seq, len(p) - 1, steps))


@pytest.mark.parametrize("row", [0, 1])
def test_decode_logits_match_reference(decoded, row):
    """Prefill (whole: row 0; chunked over the pool: row 1), then paged
    decode across the window's edge, against the reference's FULL
    forward pass over the same tokens."""
    err = _row_errors(decoded, row)
    assert np.median(err) < LOGIT_TOL / 2, err
    assert (err > LOGIT_TOL).sum() <= FLIPS, err


def test_window_pages_went_back_and_books_balance(decoded):
    *_, slots = decoded
    assert slots.window_pages_per_slot == W // PS + 1
    assert slots.window_released >= 2 * 2
    for slot in (0, 1):
        slots.release(slot)
    assert slots.window_pages_live == 0 == slots.used_pages


def test_serving_float8_rounded_weights_fails_the_tolerance(system,
                                                            devices):
    """The control: the same program over float8-rounded weights lies
    outside the tolerance the served weights keep."""
    low = adapter.System(TINY, SEED, devices[:1], weights="fp8")
    err = _row_errors(_two_rows(system.model, low.params, steps=24), 1)
    assert np.median(err) > LOGIT_TOL, err
    low.sched.close()


def test_a_router_that_reads_what_the_experts_read_fails(system):
    """The twin the early router could silently become: the same
    weights, the route taken from the post-attention norm's output."""
    class Late(SmallThinker):
        def _ffn(self, entered, h, lp, phase):
            return super()._ffn(None, h, lp, phase)
    twin = Late(system.model_cfg, system.mesh, mode="fused")
    err = _row_errors(_two_rows(twin, system.params, steps=24), 1)
    assert np.median(err) > LOGIT_TOL, err


# ---------------------------------------------------------------------------
# through the scheduler
# ---------------------------------------------------------------------------

def test_scheduler_serves_in_chunks_and_counts_the_rows_it_routed(system):
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, 256, n).tolist() for n in (49, 20)]
    sched = system.sched
    # (the ring is the process's: another file's schedulers leave
    # `serving.moe` spans of theirs)
    seen = {id(s) for s in get_tracer().finished()}
    reqs = [Request(p, 24, eos_token_ids=(), seed=0) for p in prompts]
    for r in reqs:
        assert sched.submit(r), r.reject_reason
    while sched.has_work():
        sched.step()
    assert all(len(r.generated) == 24 for r in reqs)
    from cellbench import correctness
    for r, p in zip(reqs, prompts):
        served = list(r.generated)
        ref = reference.logits_at(
            DIMS, SEED, np.pad(p + served, (0, 128 - len(p) - len(served))),
            len(p) - 1, len(served))
        gap = correctness.gaps(ref, served)
        assert gap.max() < 1.5 and gap.mean() < 0.1, gap
    moe = [s.attrs for s in get_tracer().finished()
           if s.name == "serving.moe" and id(s) not in seen]
    assert moe and all(a["rows"] in (1, 2) for a in moe)
    assert any(a["rows"] == 2 for a in moe)
    # the step routes every slot's row: the live ones are `rows`
    assert all(a["pairs"] == 2 * 6 * 4 for a in moe)


# ---------------------------------------------------------------------------
# one home for the window plumbing; the cut's arithmetic
# ---------------------------------------------------------------------------

def test_the_window_plumbing_has_one_home():
    """`Cohere2Moe` and `SmallThinker` walk their layers, keep their
    pools by kind and chunk through the SAME functions
    (`models.window_layers`), and neither module writes its own."""
    import inspect
    shared = window_layers.WindowAndFullLayers
    for cls in (cohere2_moe.Cohere2Moe, SmallThinker):
        assert issubclass(cls, shared)
        for name in ("prefill_shard", "prefill_shard_suffix",
                     "decode_shard", "cache_layout", "_put",
                     "_set_layer_kinds"):
            assert getattr(cls, name) is getattr(shared, name), (cls, name)
    for module in (cohere2_moe, smallthinker):
        text = inspect.getsource(module)
        for word in ("write_window", "set_window_layer", "window_table",
                     "wks", "window_layers="):
            assert word not in text, (module.__name__, word)


def test_the_configuration_files_parameter_count_by_hand():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "cellbench", "configs",
                           "smallthinker-21b-1c.json")) as f:
        cfg = json.load(f)
    attention = 2 * 2560 * 128 * (28 + 4)
    layer = attention + 2560 * 64 + 2 * 2560 + 64 * 3 * 2560 * 768
    assert attention == 20_971_520 and layer == 398_627_840
    by_hand = 8 * layer + 2 * 151_936 * 2560 + 2560
    assert by_hand == 3_966_937_600 == math.total_params(cfg)
    # the published model: 52 layers
    assert 52 * layer + 2 * 151_936 * 2560 + 2560 == 21_506_562_560
    served = ModelConfig.from_smallthinker(cfg)
    assert (served.num_experts, served.num_experts_per_tok,
            served.num_heads // served.num_kv_heads) == (64, 6, 7)


def test_no_kind_of_chunk_argument_is_first_met_after_warm_up(
        devices, monkeypatch):
    """`tests/test_serving_pipeline.py`'s case for a model whose chunks
    read TWO kinds of pool: the benchmark's own `warm_up` meets every
    kind of argument of the chunk program, of the scatter into both
    pools and of the insert behind it; a window's chunked admissions —
    bursts of them, as the open-loop cell sends — then compile
    nothing."""
    from tests import test_serving_pipeline as pipeline

    def build(devs):
        serving = dict(TINY["serving"], num_slots=8)
        return adapter.System(dict(TINY, serving=serving), SEED, devs[:1])
    monkeypatch.setitem(pipeline.SYSTEMS, "smallthinker", build)
    pipeline.chunk_arguments_are_met_in_warm_up(
        "smallthinker", devices, pipeline.Compiled(), monkeypatch)


#: sha256 of the lowered text of `Cohere2Moe`'s prefill, chunk program
#: and decode step at `tests/test_model_protocol.py`'s test size, AS THE
#: PARENT OF PR 48 (67fa37d) LOWERED THEM — before the walk over the
#: layers moved to `models.window_layers`: hashed from the files a
#: `git archive` of the parent wrote (PERF.md section 6, PR 48).
COHERE_PROGRAMS = {
    "prefill": 
    "2b1accc4992c00ff3aee8281ea9ac0b91f8ffa7420a9bd421a25817e872da3c5", "suffix": 
    "d43751aa81c1bd73d80c2180839e0670f6416019a4e897a58b3d5aa8ae3296e9",
    "decode": 
    "26aa3a973947e66e4ea87341ccc44e64e2dec2b3c264d59add8bdaba36650d7e"}


def test_the_shared_walk_left_cohere2_moes_programs_as_they_were(devices):
    import hashlib
    from tests import test_model_protocol as protocol
    model = protocol._model("cohere2_moe", devices, chunk=protocol.CHUNK)
    ch = protocol.CHUNK
    params = jax.eval_shape(model.init_params, jax.random.key(0))
    ids = jnp.zeros((1, 2 * ch), jnp.int32)
    pool = jax.eval_shape(lambda: model.create_paged_cache(2, 9, 16, 4))
    pages = jnp.zeros((2, 4), jnp.int32)
    texts = {
        "prefill": jax.jit(model.make_prefill_fn()).lower(
            params, ids, jax.eval_shape(
                lambda: model.create_cache(1, 2 * ch))),
        "suffix": jax.jit(model.make_prefill_suffix_fn()).lower(
            params, ids[:, :ch], jnp.int32(ch),
            jax.eval_shape(lambda: model.create_cache(1, ch)),
            (pool.ks, pool.vs, pool.wks, pool.wvs), pages),
        "decode": jax.jit(model.make_paged_decode_fn(page_size=16)).lower(
            params, jnp.zeros((2,), jnp.int32), pool)}
    got = {k: hashlib.sha256(v.as_text().encode()).hexdigest()
           for k, v in texts.items()}
    assert got == COHERE_PROGRAMS, got
