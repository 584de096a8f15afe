"""The plain reference against the program's own golden mode
(`Qwen3(mode="xla")`, no Pallas kernel) at `ModelConfig.tiny` sizes on
the CPU, through the adapter's weight layout at tp=1 and tp=4; and the
float8 control against the limits the rehearsal cell states."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench import correctness
from cellbench.adapters import qwen3 as adapter
from cellbench.references import qwen3 as reference

HERE = os.path.dirname(os.path.abspath(__file__))


def tiny_config():
    with open(os.path.join(HERE, "..", "configs",
                           "qwen3-8b-1c.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(HERE, "..", "rehearsal", "config.json")) as f:
        cfg.update(json.load(f))
    return cfg


@pytest.mark.parametrize("world", [1, 4])
def test_reference_agrees_with_the_programs_xla_mode(world):
    if len(jax.devices()) < world:
        pytest.skip(f"needs {world} devices")
    from triton_distributed_tpu.models.qwen import Qwen3
    cfg = tiny_config()
    system = adapter.System(cfg, 7, jax.devices()[:world])
    xla = Qwen3(system.model_cfg, system.mesh, mode="xla")
    ids = np.random.default_rng(0).integers(0, cfg["vocab_size"], (1, 32))
    got, _ = jax.jit(xla.make_prefill_fn())(
        system.params, jnp.asarray(ids, jnp.int32),
        xla.create_cache(1, max_seq=32))
    want = reference.logits_at(reference.dims_of(cfg), 7, ids[0], 31, 1)
    got = np.asarray(got, np.float32)[0]
    want = np.asarray(want)[0]
    rel = np.sqrt(np.mean((got - want) ** 2)) / np.std(want)
    # bf16 against float32 over two layers: measured 0.6-0.7%
    assert rel < 0.02, rel
    assert got.argmax() == want.argmax()


def test_padding_on_the_right_changes_no_position_read():
    cfg = tiny_config()
    dims = reference.dims_of(cfg)
    ids = np.random.default_rng(1).integers(0, cfg["vocab_size"], 24)
    a = reference.logits_at(dims, 3, ids, 10, 8)
    padded = np.concatenate([ids, np.zeros(40, np.int64)])
    b = reference.logits_at(dims, 3, padded, 10, 8)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-5)


def test_float8_control_comes_out_as_not_correct():
    """Greedy tokens of the float32 reference itself pass with gap 0;
    the same positions decided in float8 fail the rehearsal limits."""
    cfg = tiny_config()
    dims = reference.dims_of(cfg)
    with open(os.path.join(HERE, "..", "rehearsal",
                           "open_loop.json")) as f:
        limits = json.load(f)["correct"]
    rng = np.random.default_rng(5)
    sample = []
    for i in range(4):
        prompt = rng.integers(0, cfg["vocab_size"], 40).tolist()
        # teacher-force the reference's own greedy continuation
        seq = list(prompt)
        for _ in range(24):
            lg = reference.logits_at(dims, 9, np.asarray(seq + [0] * (
                64 - len(seq))), len(seq) - 1, 1)
            seq.append(int(np.asarray(lg)[0].argmax()))
        sample.append({"index": i, "prompt": prompt, "prompt_len": 40,
                       "tokens": seq[40:], "ok": True})
    res = correctness.score(reference, dims, 9, sample, 64, 24,
                            control=True)
    ok, _ = correctness.judge(res["program"], limits)
    assert ok and res["program"]["served_gap_max"] == 0.0
    bad, lines = correctness.judge(res["control"], limits)
    assert not bad, lines


def test_float8_rounding_changes_the_weights():
    """The control is not the program: `fp8_rounded` moves a matmul
    weight by about the float8 step, and leaves norm weights alone."""
    dims = reference.dims_of(tiny_config())
    assert 0.01 < reference.fp8_change(dims, 3) < 0.05
    w = reference.layer_weights(
        reference.layer_key(reference.base_key(3), 0), dims)
    r = reference.fp8_rounded(w)
    assert np.array_equal(np.asarray(r["ln1"], np.float32),
                          np.asarray(w["ln1"], np.float32))
    assert r["q"].dtype == w["q"].dtype
