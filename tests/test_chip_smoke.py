"""`chip_smoke.py` off the chip: the rehearsal completes and says what
it is, the real command refuses the CPU, and the compile cache is
placed from outside or at the one fixed path."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # one CPU device: the one-chip path (the harness' 8 virtual
    # devices would select the four-chip one)
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(extra)
    return env


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    cache = tmp_path_factory.mktemp("jax_cache")
    res = subprocess.run(
        [sys.executable, SMOKE, "--rehearse"], cwd=str(cache),
        env=_env(JAX_COMPILATION_CACHE_DIR=str(cache)),
        capture_output=True, text=True, timeout=600)
    res.cache_dir = str(cache)
    return res


def test_rehearsal_completes_and_is_marked(rehearsal):
    assert rehearsal.returncode == 0, (rehearsal.stdout[-3000:],
                                       rehearsal.stderr[-3000:])
    result = json.loads(rehearsal.stdout.strip().splitlines()[-1])
    assert result["rehearsal"] is True
    assert result["ok"] is False            # a rehearsal is never a pass
    assert result["device"]["platform"] == "cpu"
    assert result["claim"] is None and list(result)[-1] == "claim"
    phases = result["phases"]
    assert {"block_until_ready", "load", "paged", "slots", "engine",
            "logits", "profile", "methods"} <= set(phases)
    assert all(p["ok"] for p in phases.values()), phases
    # the second fresh process found cached programs
    assert sum(p["warm_cache_hits"] for p in phases.values()) > 0


def test_result_line_has_exactly_the_contract_keys():
    """What a real run prints last: `ok` and `device`, and in the
    device `platform`, `kind` (text) and `count` (a whole number) —
    nothing else, whatever the child's record carried."""
    import importlib.util
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    line = mod.result_line(True, {"platform": "tpu", "kind": "TPU v5 lite",
                                  "count": 1, "extra": "dropped"})
    assert "\n" not in line
    assert json.loads(line) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


def test_rehearsal_prints_no_result_line(rehearsal):
    last = json.loads(rehearsal.stdout.strip().splitlines()[-1])
    assert set(last) != {"ok", "device"}


def test_rehearsal_honours_cache_dir_from_outside(rehearsal):
    header = json.loads(next(
        ln for ln in rehearsal.stdout.splitlines()
        if ln.startswith('{"pass": "cold"')))
    assert header["compile_cache_dir_from_env"] is True
    # given from outside: the program set no other directory
    assert header["compile_cache_dir"] == rehearsal.cache_dir
    assert os.listdir(rehearsal.cache_dir)      # entries landed there


def test_real_run_refuses_the_cpu_and_places_the_fixed_cache():
    res = subprocess.run([sys.executable, SMOKE], env=_env(),
                         capture_output=True, text=True, timeout=300)
    assert res.returncode not in (0, 2, 3), res.stdout[-2000:]
    assert "no TPU" in res.stdout
    # no result line: the last line is not the {"ok": ...} object
    assert not res.stdout.strip().splitlines()[-1].startswith('{"ok"')
    # nothing given from outside: the one fixed, checkout-relative place
    header = json.loads(next(
        ln for ln in res.stdout.splitlines()
        if ln.startswith('{"pass": "cold"')))
    assert header["compile_cache_dir_from_env"] is False
    assert header["compile_cache_dir"] == os.path.join(REPO,
                                                       ".jax_cache")
