"""Profiling context manager.

Reference analogue: `group_profile` (`python/triton_dist/utils.py:508-593`)
which wraps torch.profiler and merges per-rank chrome traces.  On TPU the
native tool is `jax.profiler`: each process writes a trace directory and
XProf/TensorBoard merges them; timestamps are already host-synchronised by
the profiler, so no manual shifting (reference `utils.py:373-506`) is
needed.

Multi-process discipline: each process writes into its own
``rank-<N>`` subdirectory — N processes tracing into ONE directory on a
shared (or same-host) filesystem collide on the profiler's session
files.  A profiler that was asked for and cannot start raises: a
traced run that silently went on untraced yields per-layer numbers
from nothing.
"""

from __future__ import annotations

import contextlib
import os
from typing import Optional

import jax

from triton_distributed_tpu.utils.debug import logger


def _rank_subdir(path: str) -> str:
    """Per-process subdirectory under the trace path for multi-process
    runs (single-process keeps the flat layout unchanged)."""
    from triton_distributed_tpu.observability.metrics import (
        _process_count, _process_index)
    if _process_count() > 1:
        return os.path.join(path, f"rank-{_process_index()}")
    return path


@contextlib.contextmanager
def group_profile(
    name: Optional[str] = None,
    do_prof: bool = True,
    trace_dir: str = "prof",
):
    """Capture a jax.profiler trace for the enclosed region.

    Usage mirrors the reference:

        with group_profile("ag_gemm", do_prof=args.profile):
            run_benchmark()

    Every process writes into `{trace_dir}/{name}` (multi-process:
    `{trace_dir}/{name}/rank-{i}`, so concurrent processes never
    collide on one session directory); open with TensorBoard (XProf)
    to see the merged multi-host timeline.
    """
    if not do_prof:
        yield
        return
    path = _rank_subdir(os.path.join(trace_dir, name or "trace"))
    os.makedirs(path, exist_ok=True)
    jax.profiler.start_trace(path)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
        logger.info("profile trace written to %s", path)


@contextlib.contextmanager
def annotate(name: str):
    """Named region that shows up on the profiler timeline
    (reference: kernel `launch_metadata` hooks, `allgather_gemm.py:132-144`)."""
    with jax.profiler.TraceAnnotation(name):
        yield
