"""GLM-4.7-Flash at its PUBLISHED widths on the chip: the tier-1
comparison (`tests/test_glm4_moe_lite.py`) repeated where the Mosaic
kernels are real — prefill, then decode through the paged latent
cache, against the float32 reference's full forward pass, logits.

Depth is cut to 3 layers (the dense one and two expert layers) so that
the reference's float32 work stays short; every width, all 64 experts
and the whole vocabulary are the configuration's.  Readings are
printed (`-s`).

Tolerance, in units of a position's logit spread: the program's worst
logit of a position (over 154 880 of them) lies a median 0.036-0.052
from the reference's (my chip run, PR 28), the float8 control's a
median 0.38-0.73 and never under 0.33: `LOGIT_TOL` = 0.12 lies 2.3x
over the one and 2.7x under the other.  Where bfloat16 rounding flips
a routing near-tie the token sees another expert and its logits move
by 1.0-2.0 (1 to 3 of a sequence's 24 positions over two runs): at
most `FLIPS` = 6 positions of a sequence may lie past the tolerance;
the control lies past it at every one of the 24.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench.adapters import glm4_moe_lite as adapter
from cellbench.references import glm4_moe_lite as reference
from triton_distributed_tpu.kernels.mla_decode import (
    mla_decode_paged, mla_decode_reference)
from triton_distributed_tpu.serving.engine_batched import (
    pad_prompt, pick_bucket)
from triton_distributed_tpu.serving.pages import PagedKV

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOGIT_TOL, FLIPS = 0.12, 6
#: Latent rows of a prompt prefilled in chunks against the whole
#: prefill's: a token is "moved" where a number of its row lies more
#: than `ROW_TOL` (four roundings of a bfloat16 in [4, 8)) from the
#: whole prefill's, and at most `MOVED_MAX` of a chunk's tokens may be.
#: Read (my chip run, PR 40): the first layer's rows bit for bit; behind
#: an attention a token's worst number off by a median 0 / 0.0156 (one
#: rounding), ONE token of 4000 moved (by 0.87: another expert).
ROW_TOL, MOVED_MAX = 0.125, 0.01
SEED = 2790000123            # past 2**31, as the driver's are


@pytest.fixture(scope="module")
def system():
    with open(os.path.join(ROOT, "cellbench", "configs",
                           "glm-4.7-flash-1c.json")) as f:
        cfg = json.load(f)
    cfg = dict(cfg, num_hidden_layers=3, serving=dict(
        cfg["serving"], num_slots=2, max_seq=4096,
        kv_budget_bytes_per_chip=2 * 4096 * 3 * 1280))
    return cfg, adapter.System(cfg, SEED, jax.devices()[:1])


def test_prefill_then_paged_decode_match_the_reference(system):
    cfg, sysm = system
    model, params = sysm.model, sysm.params
    dims = reference.dims_of(cfg)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg["vocab_size"], n).tolist()
               for n in (2600, 90)]
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
        _, row = prefill(params, ids, model.create_cache(1, bucket))
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
    print("expert counters of the last step:",
          np.asarray(slots.cache.stats))
    for row, p in enumerate(prompts):
        seq = np.zeros(3072, np.int64)
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
              f"min {ctl.min():.4f}; spread {spread.mean():.3f}")
        assert np.median(err) < LOGIT_TOL, err
        assert (err > LOGIT_TOL).sum() <= FLIPS, err
        assert (ctl > LOGIT_TOL).all(), ctl


def test_a_4000_token_prompt_in_chunks_matches_the_reference(system):
    """The chunk program at published widths: a 4000-token prompt in
    chunks of `prefill_chunk` (two of 2048, the last right-padded), each
    attending the rows its predecessors put into the pool, then 24
    decode steps through the pages — against the float32 reference,
    and the rows against the whole prefill's through the 4096 bucket.
    """
    cfg, sysm = system
    model, params = sysm.model, sysm.params
    dims = reference.dims_of(cfg)
    rng = np.random.default_rng(40)
    prompt = rng.integers(0, cfg["vocab_size"], 4000).tolist()
    steps, chunk = 24, model.prefill_chunk
    teacher = rng.integers(0, cfg["vocab_size"], steps).tolist()
    slots = PagedKV(model, 2, max_seq=4096, page_size=16,
                    prefix_cache=False)
    suffix = jax.jit(model.make_prefill_suffix_fn())
    decode = jax.jit(model.make_paged_decode_fn(page_size=16))
    slot = slots.begin_prefill(len(prompt), [])
    pages = slots.prefill_pages(slot)
    for start in range(0, len(prompt), chunk):
        ids, _ = pad_prompt(prompt[start:start + chunk], chunk)
        row = suffix(params, ids, jnp.int32(start),
                     model.create_cache(1, chunk),
                     (slots.cache.ks, None), jnp.asarray(pages))
        if start + chunk < len(prompt):
            slots.insert_rows(slot, row, start)
        else:
            slots.insert_rows(slot, row, start,
                              jnp.zeros((2,), jnp.uint32),
                              len(prompt) - 1)
    slots.finish_prefill(slot, prompt)
    ids, _ = pad_prompt(prompt, 4096)
    _, whole = jax.jit(model.make_prefill_fn())(
        params, ids, model.create_cache(1, 4096))
    for li, (pool, w) in enumerate(zip(slots.cache.ks, whole.ks)):
        got = np.asarray(pool[pages[:250], 0].astype(jnp.float32)
                         ).reshape(4000, -1)
        want = np.asarray(w[0, 0, :4000].astype(jnp.float32))
        off = np.abs(got - want)
        tok = off.max(axis=1)              # a token's worst number
        moved = [float((t > ROW_TOL).mean())
                 for t in np.array_split(tok, range(chunk, 4000, chunk))]
        print(f"layer {li}: chunked rows against the whole prefill's: "
              f"max {off.max():.4f}, {float((off > 0).mean()):.4f} of "
              f"the numbers differ, a token's worst median "
              f"{np.median(tok):.4f}, tokens past {ROW_TOL} by chunk "
              f"{[round(m, 4) for m in moved]}, values up to "
              f"{np.abs(want).max():.2f}")
        # The same bfloat16 rows up to the order attention sums in: a
        # token's numbers lie within a rounding or two of the whole
        # prefill's, in every chunk alike — but for the few tokens
        # whose routing near-tie that rounding flips in an expert
        # layer below (module docstring), which see another expert.
        assert np.median(tok) <= ROW_TOL / 2, np.median(tok)
        assert max(moved) < MOVED_MAX, moved
    got = []
    tokens = np.asarray([prompt[-1], 0], np.int32)
    for i in range(steps):
        assert slots.ensure(slot, len(prompt) + i)
        slots.flush()
        logits, slots.cache = decode(params, jnp.asarray(tokens),
                                     slots.cache)
        got.append(np.asarray(logits)[slot])
        tokens = np.asarray([teacher[i], 0], np.int32)
    got = np.stack(got)
    seq = np.zeros(4096, np.int64)
    full = prompt + teacher[:steps - 1]
    seq[:len(full)] = full
    ref = np.asarray(reference.logits_at(dims, SEED, seq, len(prompt) - 1,
                                         steps))
    spread = ref.std(axis=1, keepdims=True)
    err = (np.abs(got - ref) / spread).max(axis=1)
    print(f"4000 tokens in chunks of {chunk}: program worst logit off by "
          f"median {np.median(err):.4f} max {err.max():.4f} of the "
          f"spread, {int((err > LOGIT_TOL).sum())} of {steps} past "
          f"{LOGIT_TOL}")
    assert np.median(err) < LOGIT_TOL, err
    assert (err > LOGIT_TOL).sum() <= FLIPS, err


def test_mla_decode_kernel_at_published_sizes():
    """20 heads over rows of 512 + 64 (+ 64 pad), bf16 pages of 16,
    lengths from one token to the cell's longest (several 512-row
    blocks), physical pages shuffled, against plain `jax.numpy`."""
    b, h, lat, r, ps, t = 8, 20, 512, 640, 16, 320
    key = jax.random.key(0)
    q = jax.random.normal(key, (b, h, r)).astype(jnp.bfloat16)
    q = q.at[..., 576:].set(0)
    pool = jax.random.normal(jax.random.fold_in(key, 1),
                             (1 + b * t, 1, ps, r)).astype(jnp.bfloat16)
    pool = pool.at[..., 576:].set(0)
    table = np.random.default_rng(0).permutation(
        np.arange(1, 1 + b * t)).reshape(b, t).astype(np.int32)
    kv_len = jnp.asarray([1, 16, 17, 512, 513, 2047, 4001, 5024],
                         jnp.int32)
    out = jax.jit(lambda *a: mla_decode_paged(
        *a, lat=lat, scale=256 ** -0.5))(q, pool, jnp.asarray(table),
                                         kv_len)
    ref = mla_decode_reference(q, pool, jnp.asarray(table), kv_len,
                               lat=lat, scale=256 ** -0.5)
    err = np.abs(np.asarray(out, np.float32) - np.asarray(ref)).max(
        axis=(1, 2))
    print("mla_decode_paged worst element by row:", err,
          "of values up to", float(np.abs(np.asarray(ref)).max()))
    # bf16 probabilities and a bf16 result: 2^-8 of values of order 1
    assert err.max() < 0.03, err
