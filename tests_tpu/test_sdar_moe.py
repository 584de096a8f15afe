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


def test_a_long_prompt_in_chunks_against_the_same_prompt_whole(system):
    """The 1703-token prompt (a tail of 3) prefilled whole through the
    2048 bucket into one slot and by the model's own chunks
    (`SdarMoe.prefill_chunk`; each over the pages its predecessors
    filled, under the block-causal mask at a traced offset — the
    rectangular grid where the whole prefill runs the packed schedule)
    into the other: the pool rows of every prompt position, the tail's
    at and past the cursor among them, are the whole prefill's to
    bfloat16 rounding and a flipped routing near-tie's reach; the first
    block pass behind either — a dead front half, the tail revealed —
    is held to the float32 reference by the file's tolerance, the
    float8 control fails it, and chunked against whole lies inside
    either's distance from the reference.  Prints what a chunk and a
    whole bucket take (`-s`)."""
    import time
    cfg, sysm = system
    model, params = sysm.model, sysm.params
    dims = reference.dims_of(cfg)
    mask = cfg["mask_token_id"]
    rng = np.random.default_rng(49)
    p = rng.integers(0, cfg["vocab_size"], 1703).tolist()
    chunk = model.prefill_chunk
    assert chunk and len(p) > chunk and chunk % 16 == 0 and chunk % N == 0
    slots = PagedKV(model, 2, max_seq=2048, page_size=16,
                    prefix_cache=False)
    key = jnp.zeros((2,), jnp.uint32)
    cursor = len(p) // N * N
    prefill = jax.jit(model.make_prefill_fn())
    suffix = jax.jit(model.make_prefill_suffix_fn())
    bucket = pick_bucket(len(p), sysm.buckets)
    ids, s = pad_prompt(p, bucket)
    _, whole = prefill(params, ids, model.create_cache(1, bucket))
    assert slots.insert_prefill(whole, p, s, key, [], offset=cursor) == 0
    slot = slots.begin_prefill(s, [])
    row = model.create_cache(1, chunk)
    for at in range(0, s, chunk):
        ids, _ = pad_prompt(p[at:at + chunk], chunk)
        out = suffix(params, ids, jnp.int32(at), row,
                     (slots.cache.ks, slots.cache.vs),
                     slots.prefill_pages(slot))
        if at + chunk >= s:
            slots.insert_rows(slot, out, at, key, cursor)
        else:
            slots.insert_rows(slot, out, at)
    slots.finish_prefill(slot, p, cursor)
    assert [int(x) for x in slots.cache.offset] == [cursor, cursor]
    n_pages = -(-s // 16)
    worst = []
    for pool in (slots.cache.ks, slots.cache.vs):
        for li in range(len(pool)):
            heads, _, width = pool[li].shape[1:]
            a, b = (np.asarray(
                pool[li][np.asarray(slots._table[r][:n_pages])],
                np.float32).transpose(1, 0, 2, 3).reshape(
                    heads, -1, width)[:, :s] for r in (0, 1))
            worst.append((np.abs(a - b).max() / np.abs(a).max(),
                          np.median(np.abs(a - b)) / np.abs(a).max()))
    print("rows, chunked against whole, of each pool's largest: max "
          f"{max(w[0] for w in worst):.4f}, median "
          f"{max(w[1] for w in worst):.2e}")
    assert max(w[1] for w in worst) < 1e-2, worst
    decode = jax.jit(model.make_paged_decode_fn(page_size=16))
    tail = p[cursor:]
    fed = tail + [mask] * (N - len(tail))
    for b in range(2):
        assert slots.ensure(b, cursor + N)
    slots.flush()
    logits, _ = decode(params, jnp.asarray([[mask] * N + fed] * 2,
                                           jnp.int32),
                       slots.cache, jnp.ones((2,), bool),
                       jnp.zeros((2,), bool))
    logits = np.asarray(logits)
    state = np.asarray(p[:cursor] + fed, np.int64)
    ref = np.asarray(reference.forward(dims, SEED, _padded(state),
                                       cursor, N))
    low = np.asarray(reference.forward(dims, SEED, _padded(state),
                                       cursor, N, precision="fp8"))
    spread = ref.std(axis=1, keepdims=True)
    err = [(np.abs(logits[b] - ref) / spread).max(axis=1)
           for b in range(2)]
    ctl = (np.abs(low - ref) / spread).max(axis=1)
    apart = (np.abs(logits[1] - logits[0]) / spread).max(axis=1)
    print(f"first block pass, worst logit of each position in units of "
          f"its spread: whole {err[0].round(4)}, chunked "
          f"{err[1].round(4)}, chunked against whole {apart.round(4)}, "
          f"float8 control {ctl.round(4)}")
    for e in err:
        assert np.median(e) < MEDIAN_TOL and (e > LOGIT_TOL).mean() \
            <= PAST, err
    assert np.median(ctl) > LOGIT_TOL, ctl
    assert np.median(apart) <= max(np.median(e) for e in err) * 1.5 \
        + 0.02, (apart, err)
    # what the pieces take, warm: a chunk where it stands, a bucket whole
    took = {}
    for at in range(0, s, chunk):
        ids, _ = pad_prompt(p[at:at + chunk], chunk)
        t0 = time.perf_counter()
        for _ in range(5):
            out = suffix(params, ids, jnp.int32(at), row,
                         (slots.cache.ks, slots.cache.vs),
                         np.asarray(slots._table[1]))
        jax.block_until_ready(out)
        took[f"chunk@{at}"] = (time.perf_counter() - t0) / 5 * 1e3
    for bucket in (b for b in sysm.buckets if b >= 512):
        ids, _ = pad_prompt(p[:bucket], bucket)
        rowb = model.create_cache(1, bucket)
        jax.block_until_ready(prefill(params, ids, rowb))
        t0 = time.perf_counter()
        for _ in range(5):
            out = prefill(params, ids, rowb)
        jax.block_until_ready(out)
        took[f"bucket{bucket}"] = (time.perf_counter() - t0) / 5 * 1e3
    print(f"ms a program (5 back to back, chunk {chunk}): "
          + json.dumps({k: round(v, 2) for k, v in took.items()}),
          flush=True)


def test_the_chunk_program_compiles_for_a_described_v5e():
    """The chunk program at the published widths, the cell's own cut
    (7 layers, 128 experts) and the model's own chunk length under the
    real Mosaic / XLA:TPU compiler for a DESCRIBED v5e — nothing
    executes, so it also runs without the chip (`--noconftest`):
    Mosaic takes `flash_attention` at a traced offset under the
    block-causal mask, and the program is a prefill by its name.
    (`test_topology_pool_copies.py` reads the same program, two layers
    deep, for copies of the pool.)"""
    import functools
    import time

    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tests_tpu.test_topology_pool_copies import _sdar, _shaped
    from triton_distributed_tpu.models.kv_cache import (
        KVCache, PagedKVCache)

    with open(os.path.join(ROOT, "cellbench", "configs",
                           "sdar-30b-a3b-1c.json")) as f:
        cfg = json.load(f)
    serving, layers = cfg["serving"], cfg["num_hidden_layers"]
    devices = topologies.get_topology_desc("v5e:2x2", "tpu").devices
    model, _ = _sdar(devices[:1], layers=layers)
    chunk = model.prefill_chunk
    table = serving["max_seq"] // 16
    pages = serving["num_slots"] * table + 1
    rep = NamedSharding(model.mesh, P())
    arg = lambda shape, dt: jax.ShapeDtypeStruct(     # noqa: E731
        shape, dt, sharding=rep)
    pool = _shaped(model, functools.partial(
        PagedKVCache.create, layers, pages, serving["num_slots"], 4, 16,
        128, table, model.dtype, num_stats=len(model.STATS)),
        model._cache_specs(16))
    row = _shaped(model, functools.partial(
        KVCache.create, layers, 1, 4, chunk, 128, model.dtype),
        model._cache_specs())
    params = _shaped(
        model, lambda: model.init_params(jax.random.key(0)),
        model.param_specs())
    t0 = time.perf_counter()
    compiled = jax.jit(model.make_prefill_suffix_fn()).lower(
        params, arg((1, chunk), jnp.int32), arg((), jnp.int32), row,
        (pool.ks, pool.vs), arg((table,), jnp.int32)).compile()
    text = compiled.as_text()
    print(f"chunk of {chunk} at {layers} layers: compiled in "
          f"{time.perf_counter() - t0:.1f} s, temporaries "
          f"{compiled.memory_analysis().temp_size_in_bytes >> 20} MB")
    assert text.startswith("HloModule jit_prefill_shard_suffix"), text[:80]
    for kernel in ("flash_attention_fwd", "moe_prefill_gate_up",
                   "moe_prefill_down"):
        assert kernel in text, kernel
    assert "moe_decode" not in text and "flash_decode" not in text


def _padded(state, to=256):
    """One compiled length a prompt: whole 256s of positions (what lies
    past the block read is masked or in later blocks)."""
    n = -(-len(state) // to) * to
    out = np.zeros(n, np.int64)
    out[:len(state)] = state
    return out
