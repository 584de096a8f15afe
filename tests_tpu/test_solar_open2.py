"""Solar-Open2-250B at its PUBLISHED widths on the chip: the tier-1
comparison (`tests/test_solar_open2.py`) repeated where the Mosaic
kernels are real — a padded-bucket prefill, then decode through the
state pool and the softmax layer's pages, against the float32
reference's full forward pass, logits — a long prompt prefilled in the
model's chunks beside the same prompt through its bucket, and the two
delta-rule kernels at the cell's own shapes against the recurrence.

The configuration's own cut (layers 0-3: one whole period; 40 of 320
experts held; 24 576 rows of the vocabulary), two slots.  Readings are
printed (`-s`).

Tolerance, in units of a position's logit spread: see the readings in
PERF.md section 4; the float8 control has to lie past it at every
position, the program at no more than `FLIPS` of a sequence's
positions (a routing near-tie that bfloat16 flips between an expert
held here and one held elsewhere moves a token's logits, and the
delta-rule state carries it on).
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench.adapters import solar_open2 as adapter
from cellbench.references import solar_open2 as reference
from triton_distributed_tpu.kernels import kda
from triton_distributed_tpu.serving.engine_batched import (
    pad_prompt, pick_bucket)
from triton_distributed_tpu.serving.pages import PagedKV

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOGIT_TOL, FLIPS = 0.2, 8
SEED = 2790000133            # past 2**31, as the driver's are


@pytest.fixture(scope="module")
def system():
    with open(os.path.join(ROOT, "cellbench", "configs",
                           "solar-open2-250b-1c.json")) as f:
        cfg = json.load(f)
    cfg = dict(cfg, serving=dict(
        cfg["serving"], num_slots=2, max_seq=4096,
        kv_budget_bytes_per_chip=2 * (13025280 + 4096 * 4096)))
    return cfg, adapter.System(cfg, SEED, jax.devices()[:1])


def test_prefill_then_decode_through_state_and_pages(system):
    cfg, sysm = system
    model, params = sysm.model, sysm.params
    dims = reference.dims_of(cfg)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg["vocab_size"], n).tolist()
               for n in (1700, 150)]
    steps = 24
    teacher = [rng.integers(0, cfg["vocab_size"], steps).tolist()
               for _ in prompts]
    slots = PagedKV(model, 2, max_seq=4096, page_size=16,
                    prefix_cache=False)
    prefill = jax.jit(model.make_prefill_fn())
    decode = jax.jit(model.make_paged_decode_fn(page_size=16))
    for p in prompts:
        bucket = pick_bucket(len(p), sysm.buckets)
        ids, s = pad_prompt(p, bucket)
        row_in = dataclasses.replace(
            model.create_cache(1, bucket),
            length=np.full((1,), s - 1, np.int32))
        _, row = prefill(params, ids, row_in)
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
    got = np.stack(got)
    print("counters of the last step", model.STATS,
          np.asarray(slots.cache.stats))
    bad = []
    for row, p in enumerate(prompts):
        seq = np.zeros(2048, np.int64)
        full = p + teacher[row][:steps - 1]
        seq[:len(full)] = full
        ref = np.asarray(reference.logits_at(dims, SEED, seq, len(p) - 1,
                                             steps))
        low = np.asarray(reference.logits_at(dims, SEED, seq, len(p) - 1,
                                             steps, precision="fp8"))
        spread = ref.std(axis=1, keepdims=True)
        err = (np.abs(got[:, row] - ref) / spread).max(axis=1)
        ctl = (np.abs(low - ref) / spread).max(axis=1)
        print(f"row {row} (prompt {len(p)}): program worst logit off by "
              f"median {np.median(err):.4f} max {err.max():.4f} of the "
              f"spread, {int((err > LOGIT_TOL).sum())} of {steps} past "
              f"{LOGIT_TOL}; float8 control median {np.median(ctl):.4f} "
              f"min {ctl.min():.4f}; spread {spread.mean():.3f}; "
              f"sorted {np.sort(err)[-8:]}")
        bad.append((row, np.median(err) < LOGIT_TOL / 2,
                    (err > LOGIT_TOL).sum() <= FLIPS,
                    (ctl > LOGIT_TOL).all()))
    assert all(all(b[1:]) for b in bad), bad


def test_a_long_prompt_in_chunks_against_the_same_prompt_whole(system):
    """A 2000-token prompt prefilled in the model's chunks (each over
    the pages the ones before it filled, from the delta-rule state and
    the convolution's tail the last of them returned; the last one
    right-padded) beside the same prompt through the 2048 bucket, then
    24 decode steps of both slots in one batch.  State and tail of
    every delta-rule layer are printed against the whole prefill's and
    held to the same order of magnitude (the kernel cuts the sequence
    at the same 64 tokens and carries float32; behind the first expert
    layer the two programs round differently — a chunk's rows a program
    against 2048 — and a token's routing may flip, as between program
    and reference: at the model's chunks of 256 the three layers' states
    read 8e-5, 4e-3 and 6e-3 of their largest apart and the tails 0,
    3e-3 and 7e-3, chunked against whole a median 0.031 of the spread
    where either lies 0.040-0.042 from the reference and the float8
    control 0.42; my chip run, PR 45).  The chunked slot's logits are held to the
    reference by the file's tolerance, as the whole prefill's are; the
    float8 control fails BOTH halves of it (its median and its count of
    positions past `LOGIT_TOL`); chunked against whole is held to half
    the tolerance's median."""
    cfg, sysm = system
    model, params = sysm.model, sysm.params
    dims = reference.dims_of(cfg)
    rng = np.random.default_rng(45)
    p = rng.integers(0, cfg["vocab_size"], 2000).tolist()
    steps = 24
    teacher = rng.integers(0, cfg["vocab_size"], steps).tolist()
    chunk = model.prefill_chunk
    assert chunk and len(p) > chunk and chunk % kda.CHUNK == 0
    slots = PagedKV(model, 2, max_seq=4096, page_size=16,
                    prefix_cache=False)
    key = jnp.zeros((2,), jnp.uint32)
    bucket = pick_bucket(len(p), sysm.buckets)
    ids, s = pad_prompt(p, bucket)
    _, whole = jax.jit(model.make_prefill_fn())(
        params, ids, dataclasses.replace(
            model.create_cache(1, bucket),
            length=np.full((1,), s - 1, np.int32)))
    assert slots.insert_prefill(whole, p, s, key, []) == 0
    suffix = jax.jit(model.make_prefill_suffix_fn())
    slot = slots.begin_prefill(s, [])
    row = model.create_cache(1, chunk)
    for at in range(0, s, chunk):
        ids, _ = pad_prompt(p[at:at + chunk], chunk)
        row = suffix(
            params, ids, jnp.int32(at), dataclasses.replace(
                row, length=np.full(
                    (1,), min(max(s - 1 - at, 0), chunk), np.int32)),
            (slots.cache.ks, slots.cache.vs), slots.prefill_pages(slot))
        slots.insert_rows(slot, row, at,
                          *([key] if at + chunk >= s else []))
    slots.finish_prefill(slot, p)
    for i in range(model.num_kda):
        a, b = row.states[i], whole.states[i]
        assert a.dtype == jnp.float32 and row.convs[i].dtype == jnp.bfloat16
        es = float(jnp.abs(a - b).max() / jnp.abs(b).max())
        ca, cb = (x.convs[i].astype(jnp.float32) for x in (row, whole))
        ec = float(jnp.abs(ca - cb).max() / jnp.abs(cb).max())
        print(f"delta-rule layer {i}: state off by {es:.2e} of its "
              f"largest, tail by {ec:.2e}")
        assert max(es, ec) < 0.25, (i, es, ec)
    decode = jax.jit(model.make_paged_decode_fn(page_size=16))
    got = []
    tokens = np.asarray([p[-1]] * 2, np.int32)
    for i in range(steps):
        for b in range(2):
            assert slots.ensure(b, len(p) + i)
        slots.flush()
        logits, slots.cache = decode(params, jnp.asarray(tokens),
                                     slots.cache)
        got.append(np.asarray(logits))
        tokens = np.asarray([teacher[i]] * 2, np.int32)
    got = np.stack(got)
    seq = np.zeros(2048, np.int64)
    full = p + teacher[:steps - 1]
    seq[:len(full)] = full
    ref = np.asarray(reference.logits_at(dims, SEED, seq, len(p) - 1,
                                         steps))
    low = np.asarray(reference.logits_at(dims, SEED, seq, len(p) - 1,
                                         steps, precision="fp8"))
    spread = ref.std(axis=1, keepdims=True)
    ctl = (np.abs(low - ref) / spread).max(axis=1)
    apart = (np.abs(got[:, 1] - got[:, 0]) / spread).max(axis=1)
    print(f"chunked against whole: median {np.median(apart):.4f} max "
          f"{apart.max():.4f} of the spread, first step {apart[0]:.4f}; "
          f"float8 control median {np.median(ctl):.4f} min "
          f"{ctl.min():.4f}")
    assert np.median(ctl) > LOGIT_TOL / 2, ctl
    assert (ctl > LOGIT_TOL).sum() > FLIPS, ctl
    for b, name in enumerate(("whole", "chunked")):
        err = (np.abs(got[:, b] - ref) / spread).max(axis=1)
        print(f"{name}: worst logit off by median {np.median(err):.4f} "
              f"max {err.max():.4f} of the spread, first step "
              f"{err[0]:.4f}, {int((err > LOGIT_TOL).sum())} of {steps} "
              f"past {LOGIT_TOL}")
        assert np.median(err) < LOGIT_TOL / 2, (name, err)
        assert (err > LOGIT_TOL).sum() <= FLIPS, (name, err)
    assert np.median(apart) < LOGIT_TOL / 4 and apart[0] < LOGIT_TOL, apart


def _inputs(b, h, t, d=128, seed=0):
    ks = jax.random.split(jax.random.key(seed), 6)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (b, h, t, d))) * d ** -0.5
    k = unit(jax.random.normal(ks[1], (b, h, t, d)))
    v = jax.random.normal(ks[2], (b, h, t, d))
    fast = jnp.log(jax.random.uniform(ks[3], (h,), minval=1, maxval=16))
    g = -jnp.exp(fast)[None, :, None, None] * jax.random.uniform(
        ks[4], (b, h, t, d), minval=0.001, maxval=0.3)
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[5], (b, h, t)))
    return q, k, v, g, beta


def test_chunked_prefill_kernel_at_published_sizes():
    """64 heads of 128 over a 1024-token bucket of which 777 count."""
    q, k, v, g, beta = _inputs(1, 64, 1024)
    seen = jnp.arange(1024) < 777
    g = jnp.where(seen[None, None, :, None], g, 0.0)
    beta = jnp.where(seen[None, None, :], beta, 0.0)
    o, s = jax.jit(kda.kda_prefill_chunk)(q, k, v, g, beta)
    o_ref, s_ref = jax.jit(kda.kda_recurrent_reference)(q, k, v, g, beta)
    eo = float(jnp.abs(o - o_ref)[:, :, :777].max())
    es = float(jnp.abs(s - s_ref).max())
    print("kda_prefill_chunk: output off by", eo, "of",
          float(jnp.abs(o_ref).max()), "state off by", es, "of",
          float(jnp.abs(s_ref).max()))
    assert eo < 1e-3 and es < 1e-3


def test_chunked_prefill_kernel_from_a_carried_state_at_published_sizes():
    """64 heads of 128 over a 512-token chunk of which 300 count, from
    a state that is not zero: the recurrence continued from it."""
    q, k, v, g, beta = _inputs(1, 64, 512, seed=2)
    seen = jnp.arange(512) < 300
    g = jnp.where(seen[None, None, :, None], g, 0.0)
    beta = jnp.where(seen[None, None, :], beta, 0.0)
    s0 = jax.random.normal(jax.random.key(7), (1, 64, 128, 128))
    o, s = jax.jit(kda.kda_prefill_chunk)(q, k, v, g, beta, s0)
    o_ref, s_ref = jax.jit(kda.kda_recurrent_reference)(q, k, v, g, beta,
                                                        s0)
    eo = float(jnp.abs(o - o_ref)[:, :, :300].max())
    es = float(jnp.abs(s - s_ref).max())
    print("kda_prefill_chunk from a state: output off by", eo, "of",
          float(jnp.abs(o_ref).max()), "state off by", es, "of",
          float(jnp.abs(s_ref).max()))
    assert eo < 1e-3 and es < 1e-3


def test_decode_kernel_at_published_sizes():
    """32 rows of 64 heads, a third of them not live."""
    b = 32
    q, k, v, g, beta = _inputs(b, 64, 1, seed=1)
    state = jax.random.normal(jax.random.key(9), (b, 64, 128, 128))
    live = jnp.arange(b) % 3 != 1
    args = (q[:, :, 0], k[:, :, 0], v[:, :, 0], jnp.exp(g[:, :, 0]),
            beta[:, :, 0])
    o_ref, s_ref = jax.jit(kda.kda_recurrent_reference)(
        q, k, v, g, beta, state)
    o, new = jax.jit(kda.kda_decode_step, donate_argnums=5)(
        *args, state + 0.0, live)
    want = jnp.where(live[:, None, None, None], s_ref, state)
    es = float(jnp.abs(new - want).max())
    eo = float(jnp.abs(o - jnp.where(live[:, None, None],
                                     o_ref[:, :, 0], 0.0)).max())
    print("kda_decode_step: state off by", es, "output off by", eo)
    assert es < 1e-3 and eo < 1e-3
    assert bool((new[1] == state[1]).all())
