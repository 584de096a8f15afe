#!/usr/bin/env bash
# Real-TPU kernel sweep: compiles + checks every Pallas kernel family
# with Mosaic on the attached chip(s).  The CPU harness (tests/) cannot
# catch Mosaic-acceptance breakage; this can.  No TPU is a failure
# (exit 4), never a skip.  One process holds the chip at a time, so the
# native-AOT test — whose children take the chip in turn — runs in a
# second pytest whose own process stays on the CPU.
# Usage: bash scripts/run_tpu.sh [extra pytest args]
set -uo pipefail
cd "$(dirname "$0")/.."
rc=0
python -m pytest tests_tpu -q --ignore=tests_tpu/test_aot_native.py "$@" || rc=$?
JAX_PLATFORMS=cpu python -m pytest tests_tpu/test_aot_native.py -q "$@" || rc=$?
exit $rc
