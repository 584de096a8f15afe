"""Drift-robust device benchmarking (shared by bench.py and the
benchmark/ sweep suite; reference analogue: `perf_func` +
CUDA-event timing, `python/triton_dist/utils.py:277-291`).

Each sample dispatches N dependence-chained calls with ONE trailing
fetch, and the per-call latency is the slope between adjacent (n1, n2)
samples — every fixed per-sample cost cancels — as the median of
per-repeat slopes, with competing ops interleaved in time so slow drift
hits them equally.  (ROADMAP D11: once kernel times come from the
profiler trace, plain `block_until_ready` windows replace this.)
"""

from __future__ import annotations

import statistics
import time
from typing import Callable, Sequence

import numpy as np


def measure_ops(fs: Sequence[Callable], args: tuple,
                chain: Callable, *, n1: int = 20, n2: int = None,
                repeats: int = 6, min_window_s: float = 0.5,
                return_slopes: bool = False):
    """Per-call latency (seconds) of each `f(*args) -> out` in `fs`.

    ``chain(args, out) -> new_args`` must make call i+1 data-dependent
    on call i's output (so the device queue cannot collapse the chain)
    while keeping shapes fixed.

    ``n2`` auto-calibrates from a pilot so the slope window holds at
    least ``min_window_s`` of device work — a fast op measured with a
    small fixed window drowns in the fetch jitter and reads as ~0.

    With ``return_slopes`` also returns the per-repeat slope lists —
    A/B callers should pair slopes within a repeat (adjacent in time)
    rather than ratio two medians, which lets minutes-scale drift land
    in one op's median.
    """

    def total(f, n_calls):
        t0 = time.perf_counter()
        a = args
        for _ in range(n_calls):
            out = f(*a)
            a = chain(a, out)
        leaf = out[0] if isinstance(out, (tuple, list)) else out
        # Fence: one-element fetch forces full queue drain (device-side
        # slice first — fetching the whole array costs seconds at the
        # big sweep shapes).
        np.asarray(leaf.reshape(-1)[:1])
        return time.perf_counter() - t0

    uniq = {id(f): f for f in fs}
    for f in uniq.values():
        total(f, 2)  # warm every distinct jit once
    if n2 is None:
        # Grow each op's window until its measured (t2 - t1) dominates
        # the fetch jitter — a pilot estimate would itself be
        # jitter-dominated for fast ops.  Per-op windows: sizing by
        # the fastest op would charge its large call count to a slow
        # competitor (minutes per sample).  Calibrate each DISTINCT op
        # once (repeated entries, e.g. an ABBA schedule, share it).
        cal = {}
        for fid, f in uniq.items():
            n = max(3 * n1, n1 + 40)
            while n < 8000:
                if total(f, n) - total(f, n1) >= min_window_s:
                    break
                n = min(8000, n * 4)
            cal[fid] = n
        n2s = [cal[id(f)] for f in fs]
    else:
        n2s = [n2] * len(fs)
    slopes = [[] for _ in fs]
    for _ in range(repeats):
        for sl, f, n in zip(slopes, fs, n2s):
            t1 = total(f, n1)
            t2 = total(f, n)
            sl.append(max((t2 - t1) / (n - n1), 1e-9))
    medians = [statistics.median(sl) for sl in slopes]
    return (medians, slopes) if return_slopes else medians


def measure_ops_scanned(fs: Sequence[Callable], args: tuple,
                        mix: Callable, *, n_inner: int = 16,
                        n1: int = 4, repeats: int = 6,
                        min_window_s: float = 0.5,
                        carry_args: int = 1,
                        return_slopes: bool = False):
    """Per-call latency for SUB-MILLISECOND ops.

    One-dispatch-per-call measurement (``measure_ops``) bottoms out at
    the host's dispatch-rate floor, so ops faster than that read as
    the floor.
    Here each dispatch runs ``n_inner`` data-chained iterations of the
    op inside ONE jitted `lax.scan`, so per-dispatch device work is
    n_inner× the op and the floor amortizes away.

    ``mix(args, out) -> new_args`` chains iteration i+1 on iteration
    i's output *inside* the scan (shapes must be preserved; it is
    traced, so no jit wrapper is needed).

    Only the first ``carry_args`` arguments travel through the scan
    CARRY; the rest enter the body as loop-invariant jit arguments.
    Carrying invariants is not free: XLA shuffles the full carry every
    iteration, and measured overhead was ~20% when a decode op's KV
    cache plus baseline buffers (~0.8 GB) rode the carry.  (They must
    still be jit ARGUMENTS, not Python closures — closure-captured
    arrays embed as compile-time constants in the executable.)
    """
    import jax

    def scanned(f):
        def g(*a):
            invariant = a[carry_args:]

            def body(c, _):
                full = c + invariant
                return mix(full, f(*full))[:carry_args], None

            final, _ = jax.lax.scan(body, a[:carry_args], None,
                                    length=n_inner)
            return final

        return jax.jit(g)

    # Dedupe by identity: repeated entries (ABBA schedules) share one
    # jitted scan — one compile, one window calibration.
    wrapped = {}
    gs = [wrapped.setdefault(id(f), scanned(f)) for f in fs]
    res = measure_ops(gs, args,
                      # g returns only the carry: reattach the
                      # invariant args for the next chained dispatch.
                      lambda a, out: tuple(out) + tuple(a[len(out):]),
                      n1=n1, repeats=repeats,
                      min_window_s=min_window_s,
                      return_slopes=return_slopes)
    if return_slopes:
        medians, slopes = res
        return ([t / n_inner for t in medians],
                [[s / n_inner for s in sl] for sl in slopes])
    return [t / n_inner for t in res]


def feedback_mix(x, out):
    """Shape-safe dependence edge: mix `out` (cropped/padded to x's
    shape) into the next call's input.  Keeps magnitudes bounded so a
    thousand-call chain cannot overflow."""
    import jax.numpy as jnp

    crop = out[tuple(slice(0, min(a, b))
                     for a, b in zip(x.shape, out.shape))]
    pad = [(0, xs - cs) for xs, cs in zip(x.shape, crop.shape)]
    crop = jnp.pad(crop, pad)
    return (x * 0.5 + crop.astype(jnp.float32).astype(x.dtype) * 1e-3
            ).astype(x.dtype)
