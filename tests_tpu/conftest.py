"""Real-TPU kernel sweep harness.

Unlike `tests/` (which forces an 8-virtual-device CPU mesh + interpret
mode), this directory runs against the real chip(s) and compiles every
kernel family with Mosaic — the breakage class interpret mode cannot
catch.  Run via `scripts/run_tpu.sh` (through the chip tool).  Without
a TPU backend the sweep FAILS: a run that compiled nothing with Mosaic
must not read as green.  (`pytest` at the repo root never collects this
directory — tier-1 names `tests/` explicitly.)
"""

import jax
import pytest


@pytest.fixture(scope="session", autouse=True)
def require_tpu():
    backend = jax.default_backend()
    if backend != "tpu":
        pytest.exit(f"real-TPU sweep needs the accelerator: "
                    f"jax.default_backend() is {backend!r}",
                    returncode=4)
