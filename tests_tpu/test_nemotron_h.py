"""NVIDIA-Nemotron-3-Super-120B-A12B at its PUBLISHED widths on the
chip: the tier-1 comparison (`tests/test_nemotron_h.py`) repeated where
the Mosaic kernels are real — a padded-bucket prefill, then decode
through the state pool, the convolution tail and the attention layer's
pages, against the float32 reference's full forward pass, logits — and
the two state-space kernels at the cell's own shapes against the
recurrence.

The configuration's own cut (layers 0-10, `MEMEMEM*EME`; 128 of 512
experts held; 32 768 rows of the vocabulary), two slots.  Readings are
printed (`-s`).

Tolerance, in units of a position's logit spread (readings: my chip
run, PR 41, PERF.md section 4).  A token takes 22 of 512 experts in
each of five layers, a quarter of them held here, and the routed sum
is scaled by 5: where bfloat16 rounding flips a near-tie between an
expert held here and one held elsewhere that token's logits move, and
the state-space layers carry it on — so the program's worst logit of a
position lies a median 0.29-0.35 of the spread from the reference's
(solar-open2's top-8 reads 0.09), with 2-3 of 24 positions past 0.7
and none past 1.4; the float8 control reads a median 1.24-1.36 and
never under 0.79.  `LOGIT_TOL` = 0.7 lies between the program's usual
position and the control's best, `MEDIAN_TOL` = 0.55 between the two
medians: the program has to keep its median under the one and at most
`FLIPS` positions past the other, the control has to lie past
`LOGIT_TOL` at every position.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench.adapters import nemotron_h as adapter
from cellbench.references import nemotron_h as reference
from triton_distributed_tpu.kernels import mamba2
from triton_distributed_tpu.serving.engine_batched import (
    pad_prompt, pick_bucket)
from triton_distributed_tpu.serving.pages import PagedKV

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOGIT_TOL, MEDIAN_TOL, FLIPS = 0.7, 0.55, 8
SEED = 2790000141            # past 2**31, as the driver's are
H, P, G, N = 128, 64, 8, 128


@pytest.fixture(scope="module")
def system():
    with open(os.path.join(ROOT, "cellbench", "configs",
                           "nemotron-3-super-120b-1c.json")) as f:
        cfg = json.load(f)
    cfg = dict(cfg, serving=dict(
        cfg["serving"], num_slots=2,
        kv_budget_bytes_per_chip=2 * (21278720 + 1024 * 2560)))
    return cfg, adapter.System(cfg, SEED, jax.devices()[:1])


def test_prefill_then_decode_through_state_tail_and_pages(system):
    cfg, sysm = system
    model, params = sysm.model, sysm.params
    dims = reference.dims_of(cfg)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg["vocab_size"], n).tolist()
               for n in (1700, 150)]
    steps = 24
    teacher = [rng.integers(0, cfg["vocab_size"], steps).tolist()
               for _ in prompts]
    slots = PagedKV(model, 2, max_seq=sysm.max_seq, page_size=16,
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
        bad.append((row, np.median(err) < MEDIAN_TOL,
                    (err > LOGIT_TOL).sum() <= FLIPS,
                    (ctl > LOGIT_TOL).all()))
    assert all(all(b[1:]) for b in bad), bad


def test_a_long_prompt_in_chunks_against_the_same_prompt_whole(system):
    """A 2000-token prompt prefilled in the model's chunks (three of
    512 and a right-padded fourth: each over the pages the ones before
    it filled, from the state and the convolution's tail the last of
    them returned) beside the same
    prompt through the 2048 bucket, then 24 decode steps of both slots
    in one batch.  The FIRST layer's state and tail are the whole
    prefill's bit for bit (the kernel cuts the sequence at the same 128
    tokens and carries float32; my chip run, PR 43: 0.0 and 0.0); from
    the first expert layer on the two programs round differently (512
    rows a program against 2048: the second state-space layer's state
    read 2.1e-4 of its largest apart, its tail 0) and a token's routing
    may flip, as between program and reference: printed, and held only
    to the same order of magnitude.  The
    chunked slot's logits are held to the reference by the file's
    tolerance, as the whole prefill's are, the float8 control stays
    outside it (its median and its count of positions past
    `LOGIT_TOL`: one of this prompt's 24 reads 0.676), and chunked
    against whole — an order of magnitude closer than either to the
    reference: a median 0.034 of the spread — is held to a quarter of
    the tolerance."""
    cfg, sysm = system
    model, params = sysm.model, sysm.params
    dims = reference.dims_of(cfg)
    rng = np.random.default_rng(43)
    p = rng.integers(0, cfg["vocab_size"], 2000).tolist()
    steps = 24
    teacher = rng.integers(0, cfg["vocab_size"], steps).tolist()
    chunk = model.prefill_chunk
    assert chunk and len(p) > chunk
    slots = PagedKV(model, 2, max_seq=sysm.max_seq, page_size=16,
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
    for li, kind in enumerate(model.pattern):
        if kind != "M":
            continue
        i = model._index[li]
        a, b = row.states[i], whole.states[i]
        es = float(jnp.abs(a - b).max() / jnp.abs(b).max())
        ca, cb = (x.convs[i].astype(jnp.float32) for x in (row, whole))
        ec = float(jnp.abs(ca - cb).max() / jnp.abs(cb).max())
        print(f"layer {li}: state off by {es:.2e} of its largest, tail "
              f"by {ec:.2e}")
        assert max(es, ec) < (1e-6 if li == 0 else 0.25), (li, es, ec)
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
    # the control fails BOTH halves of the tolerance the program is
    # held to (this prompt's reads a median 1.15 with 23 of 24 past
    # 0.7 and one position at 0.676: my chip run, PR 43)
    assert np.median(ctl) > MEDIAN_TOL, ctl
    assert (ctl > LOGIT_TOL).sum() > 2 * FLIPS, ctl
    for b, name in enumerate(("whole", "chunked")):
        err = (np.abs(got[:, b] - ref) / spread).max(axis=1)
        print(f"{name}: worst logit off by median {np.median(err):.4f} "
              f"max {err.max():.4f} of the spread, first step "
              f"{err[0]:.4f}, {int((err > LOGIT_TOL).sum())} of {steps} "
              f"past {LOGIT_TOL}")
        assert np.median(err) < MEDIAN_TOL, (name, err)
        assert (err > LOGIT_TOL).sum() <= FLIPS, (name, err)
    assert np.median(apart) < MEDIAN_TOL / 4 and apart[0] < LOGIT_TOL, apart


def _inputs(b, t, seed=0):
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


def test_chunked_prefill_kernel_at_published_sizes():
    """128 heads of 64 over a 1024-token bucket of which 777 count."""
    x, dt, a, bm, cm = _inputs(1, 1024)
    dt = jnp.where((jnp.arange(1024) < 777)[None, :, None], dt, 0.0)
    y, s = jax.jit(mamba2.mamba2_prefill_chunk)(x, dt, a, bm, cm)
    y_ref, s_ref = jax.jit(_recurrence)(x, dt, a, bm, cm)
    ey = float(jnp.abs(y - y_ref)[:, :777].max())
    es = float(jnp.abs(mamba2.unpair_state(s) - s_ref).max())
    print("mamba2_prefill_chunk: output off by", ey, "of",
          float(jnp.abs(y_ref).max()), "state off by", es, "of",
          float(jnp.abs(s_ref).max()))
    assert ey < 1e-4 * float(jnp.abs(y_ref).max())
    assert es < 1e-4 * float(jnp.abs(s_ref).max())


def test_decode_kernel_at_published_sizes():
    """32 rows of 128 heads, a third of them not live."""
    b = 32
    x, dt, a, bm, cm = _inputs(b, 1, seed=1)
    state = jax.random.normal(jax.random.key(9), (b, H // 2, N, 2 * P))
    live = jnp.arange(b) % 3 != 1
    y_ref, s_ref = jax.jit(_recurrence)(x, dt, a, bm, cm,
                                        mamba2.unpair_state(state))
    y, new = jax.jit(mamba2.mamba2_decode_step, donate_argnums=5)(
        x[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0], state + 0.0, live)
    want = jnp.where(live[:, None, None, None],
                     mamba2.pair_state(s_ref), state)
    es = float(jnp.abs(new - want).max())
    ey = float(jnp.abs(y - jnp.where(live[:, None], y_ref[:, 0],
                                     0.0)).max())
    print("mamba2_decode_step: state off by", es, "output off by", ey,
          "of", float(jnp.abs(y_ref).max()))
    assert es < 1e-3 and ey < 1e-3
    assert bool((new[1] == state[1]).all())
