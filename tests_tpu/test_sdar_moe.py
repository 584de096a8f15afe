"""SDAR-30B-A3B at its PUBLISHED widths on the chip: the tier-1
comparison (`tests/test_sdar_moe.py`) repeated where the Mosaic kernels
are real — a ~1700-token prompt through the padded 2048 bucket under
the block-causal mask, then 8 blocks through the pages (the two
denoise states of each, the finished block riding in front of the next
block's first pass — its commit — and a dead front half in the second:
`flash_decode_paged` at 64 query rows a KV head with the block in
flight hidden from the first half of them, the grouped GEMMs at the
pass's rows), the program's logits for the block in flight at every
state against the float32 reference's full forward over the sequence
as it stands, with the float8 control beside it
(the short row at every state of all 8 blocks; the long row, whose
reference costs eight times as much a state, at every state of its
first block — the one that holds the prompt's tail — and of its last,
under the sequential pattern).

The configuration's own cut (all 7 layers, 128 experts, the whole
vocabulary), two slots, both reveal patterns: the sequential schedule's
prefix and one only a confidence-ordered schedule leaves.  Readings are
printed (`-s`).

Tolerance, in units of a position's logit spread.  The program's
error is BIMODAL: a routing near-tie that bfloat16 flips — the 8th and
9th of 128 softmax scores, about 2% of tokens a layer, seven layers
deep — moves a token's logits by 0.3-0.8 of their spread, and inside a
block every position attends every other, so one flip reaches four
positions.  Measured (my chip run, PR 36, three states a block: two
denoise states and the commit's clean block): the program's worst logit
a MEDIAN 0.046-0.094 away with 33-46% of a row's positions past 0.25;
the float8 control a median 0.43-0.58 and never under 0.29.  Measured
again with the folded pass (my chip run, PR 46: two states a block, both
with masked positions in it — the clean block is no state of its own any
more, it rides in front of the next block and has no logits): the
program's median 0.041 (long row, 16 positions) / 0.088 (short row, 64)
under the sequential pattern and **0.139** under the by-confidence one
(64 positions, half of them the very logits of the sequential run: the
all-masked state is the same in both), 31-34% of the positions past
0.25; the control's median 0.41-0.57, never under 0.29, every position
past 0.25.  The median of a bimodal sample with a third of it in the
upper mode moves with a few positions, so it gets room: the program's
median within `MEDIAN_TOL` and at most `PAST` of its positions past
`LOGIT_TOL`; the control's median past `LOGIT_TOL` and nine tenths of
its positions.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench.adapters import sdar_moe as adapter
from cellbench.references import sdar_moe as reference
from triton_distributed_tpu.serving.engine_batched import (
    pad_prompt, pick_bucket)
from triton_distributed_tpu.serving.pages import PagedKV

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOGIT_TOL, MEDIAN_TOL, PAST = 0.25, 0.2, 0.6
SEED = 2790000133            # past 2**31, as the driver's are
N, BLOCKS = 4, 8
#: Blocks whose states are compared, by pattern and row (long, short):
#: the long row's reference costs eight times the short one's a state.
CHECKED = {"sequential": ((0, BLOCKS - 1), tuple(range(BLOCKS))),
           "by_confidence": ((), tuple(range(BLOCKS)))}
PATTERNS = {"sequential": [(), (0, 1)], "by_confidence": [(), (1, 3)]}


@pytest.fixture(scope="module")
def system():
    with open(os.path.join(ROOT, "cellbench", "configs",
                           "sdar-30b-a3b-1c.json")) as f:
        cfg = json.load(f)
    cfg = dict(cfg, serving=dict(
        cfg["serving"], num_slots=2, max_seq=2048,
        kv_budget_bytes_per_chip=2 * 2048 * 14336))
    return cfg, adapter.System(cfg, SEED, jax.devices()[:1])


@pytest.mark.parametrize("pattern", sorted(PATTERNS))
def test_prefill_then_blocks_through_the_pages(system, pattern):
    cfg, sysm = system
    model, params = sysm.model, sysm.params
    dims = reference.dims_of(cfg)
    mask = cfg["mask_token_id"]
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg["vocab_size"], n).tolist()
               for n in (1703, 152)]                  # r = 3 and r = 0
    teacher = [rng.integers(0, cfg["vocab_size"], BLOCKS * N).tolist()
               for _ in prompts]
    slots = PagedKV(model, 2, max_seq=2048, page_size=16,
                    prefix_cache=False)
    prefill = jax.jit(model.make_prefill_fn())
    decode = jax.jit(model.make_paged_decode_fn(page_size=16))
    for p in prompts:
        bucket = pick_bucket(len(p), sysm.buckets)
        ids, s = pad_prompt(p, bucket)
        _, row = prefill(params, ids, model.create_cache(1, bucket))
        slots.insert_prefill(row, p, s, jnp.zeros((2,), jnp.uint32), [],
                             offset=s // N * N)
    active = jnp.ones((2,), bool)
    done = [list(p[:len(p) // N * N]) for p in prompts]
    tails = [p[len(p) // N * N:] for p in prompts]
    err, ctl = [[], []], [[], []]
    before = None
    for blk in range(BLOCKS):
        full = []
        for b in range(2):
            fill = teacher[b][blk * N:(blk + 1) * N]
            tail = tails[b] if blk == 0 else []
            full.append(list(tail) + fill[len(tail):])
        for step, shown in enumerate(PATTERNS[pattern]):
            folded = step == 0 and blk > 0
            fed = [[t if (j in shown or (blk == 0 and j < len(tails[b])))
                    else mask for j, t in enumerate(full[b])]
                   for b in range(2)]
            for b in range(2):
                assert slots.ensure(b, len(done[b]) + N)
            slots.flush()
            logits, slots.cache = decode(
                params, jnp.asarray(
                    [(before[b] if folded else [mask] * N) + fed[b]
                     for b in range(2)], jnp.int32), slots.cache, active,
                jnp.full((2,), folded))
            if folded:
                # the pass has written the finished block
                slots.cache = dataclasses.replace(
                    slots.cache, offset=slots.cache.offset + N)
            logits = np.asarray(logits)
            for b in range(2):
                if blk not in CHECKED[pattern][b]:
                    continue
                state = np.asarray(done[b] + fed[b], np.int64)
                ref = np.asarray(reference.forward(
                    dims, SEED, _padded(state), len(done[b]), N))
                low = np.asarray(reference.forward(
                    dims, SEED, _padded(state), len(done[b]), N,
                    precision="fp8"))
                spread = ref.std(axis=1, keepdims=True)
                err[b].extend((np.abs(logits[b] - ref) / spread
                               ).max(axis=1))
                ctl[b].extend((np.abs(low - ref) / spread).max(axis=1))
        for b in range(2):
            done[b] += full[b]
        before = full
    print("counters of the last pass", model.STATS,
          np.asarray(slots.cache.stats))
    bad = []
    for b, p in enumerate(prompts):
        if not err[b]:
            continue
        e, c = np.asarray(err[b]), np.asarray(ctl[b])
        print(f"{pattern} row {b} (prompt {len(p)}): program worst "
              f"logit off by median {np.median(e):.4f}, quartiles "
              f"{np.percentile(e, [25, 75, 90]).round(4)}, max "
              f"{e.max():.4f} of the spread, {int((e > LOGIT_TOL).sum())}"
              f" of {len(e)} past {LOGIT_TOL}; float8 control median "
              f"{np.median(c):.4f} min {c.min():.4f}, "
              f"{int((c > LOGIT_TOL).sum())} past", flush=True)
        bad.append((b, np.median(e) < MEDIAN_TOL,
                    (e > LOGIT_TOL).mean() <= PAST,
                    np.median(c) > LOGIT_TOL,
                    (c > LOGIT_TOL).mean() > 0.9))
    assert all(all(x[1:]) for x in bad), bad


def _padded(state, to=256):
    """One compiled length a prompt: whole 256s of positions (what lies
    past the block read is masked or in later blocks)."""
    n = -(-len(state) // to) * to
    out = np.zeros(n, np.int64)
    out[:len(state)] = state
    return out
