"""Native AOT runtime end-to-end: a pure-C process loads a bundle,
creates a PJRT client from libtpu, compiles the bundled StableHLO and
executes it on the chip (reference: `tools/runtime/triton_aot_runtime.cc`,
which loads and launches cubins via the CUDA driver).

One process per chip.  Three processes take part, and only one at a time
may hold the accelerator:

1. this pytest process — stays on the CPU (`scripts/run_tpu.sh` starts
   it with ``JAX_PLATFORMS=cpu``; on a TPU backend the module stops
   with a message instead of letting the C child fail or hang);
2. a BUILDER child (this file run as a script, on the chip): exports
   the bundle, runs the golden on the chip, writes both, exits;
3. the C child (`csrc/build/aot_test`, on the chip): compiles and runs
   the bundle and compares against the golden.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
AOT_TEST = os.path.join(REPO, "csrc", "build", "aot_test")


@pytest.fixture(scope="module", autouse=True)
def require_tpu():
    """Overrides the sweep's fixture: THIS process must not hold the
    chip its children need."""
    import jax
    if jax.default_backend() == "tpu":
        pytest.exit(
            "tests_tpu/test_aot_native.py: the pytest process holds the "
            "TPU, so its C child cannot open it (one process per chip). "
            "Run it as scripts/run_tpu.sh does: JAX_PLATFORMS=cpu "
            "python -m pytest tests_tpu/test_aot_native.py",
            returncode=4)


def _chip_env():
    """Environment of a child that owns the chip: ours minus the CPU
    pin."""
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


def _build_on_chip(builder: str, out_dir: str) -> None:
    res = subprocess.run([sys.executable, os.path.abspath(__file__),
                          builder, out_dir], env=_chip_env(),
                         capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, (res.stdout[-4000:], res.stderr[-4000:])


def _run_c_child(out_dir: str, variant: str, timeout: int = 600):
    import libtpu
    plugin = os.path.join(os.path.dirname(libtpu.__file__), "libtpu.so")
    subprocess.run(["make", "-C", os.path.join(REPO, "csrc")],
                   check=True, capture_output=True, timeout=300)
    res = subprocess.run([AOT_TEST, out_dir, variant, plugin],
                         env=_chip_env(), capture_output=True,
                         text=True, timeout=timeout)
    assert res.returncode == 0, (res.stdout, res.stderr)
    assert "AOT_NATIVE_OK" in res.stdout, (res.stdout, res.stderr)
    return res


# ---------------------------------------------------------------------------
# Builders — run in the builder child, on the chip
# ---------------------------------------------------------------------------

def build_matmul(out_dir):
    import jax.numpy as jnp

    from triton_distributed_tpu.tools.compile_aot import (
        AotVariant, compile_aot)

    def matmul_fn(a, b):
        return jnp.dot(a, b, preferred_element_type=jnp.float32
                       ).astype(a.dtype)

    m = k = n = 256
    compile_aot(matmul_fn, "matmul",
                [AotVariant("m256", [(m, k), (k, n)],
                            ["float32", "float32"])],
                out_dir)

    rng = np.random.RandomState(0)
    a = (rng.randn(m, k) / 8).astype(np.float32)
    b = (rng.randn(k, n) / 8).astype(np.float32)
    a.tofile(os.path.join(out_dir, "test_arg0.bin"))
    b.tofile(os.path.join(out_dir, "test_arg1.bin"))
    (a @ b).astype(np.float32).tofile(
        os.path.join(out_dir, "test_out0.bin"))


def build_decode_family(out_dir):
    import jax
    import jax.numpy as jnp

    from triton_distributed_tpu.kernels.flash_decode import flash_decode
    from triton_distributed_tpu.tools.aot_kernels import (
        build_flash_decode_bundle, write_call_site_sigs)

    b, h, hkv, d = 2, 8, 2, 128
    seqs = (512, 1024)
    build_flash_decode_bundle(out_dir, batch=b, heads=h, kv_heads=hkv,
                              head_dim=d, seqs=seqs, dtype="bfloat16")

    # Call site: the LONGER variant's shapes — selection must pick
    # "s1024", not the first variant in the bundle.
    s = 1024
    q = (jax.random.normal(jax.random.key(0), (b, h, d)) / 4
         ).astype(jnp.bfloat16)
    kc = (jax.random.normal(jax.random.key(1), (b, hkv, s, d)) / 4
          ).astype(jnp.bfloat16)
    vc = (jax.random.normal(jax.random.key(2), (b, hkv, s, d)) / 4
          ).astype(jnp.bfloat16)
    kv_len = jnp.full((b,), s, jnp.int32)

    args = [q, kc, vc, kv_len]
    write_call_site_sigs(os.path.join(out_dir, "test_sigs.txt"), args)
    for i, a in enumerate(args):
        np.asarray(a).tofile(os.path.join(out_dir, f"test_arg{i}.bin"))
    ref = flash_decode(q, kc, vc, kv_len)[0]
    np.asarray(ref).tofile(os.path.join(out_dir, "test_out0.bin"))


N_LOOP = 3


def build_decode_step(out_dir):
    import jax
    import jax.numpy as jnp

    from triton_distributed_tpu.tools.aot_kernels import (
        build_decode_step_bundle, write_call_site_sigs, write_loop_spec)

    bundle, params, step = build_decode_step_bundle(
        out_dir, batches=(1, 4), kv_cap=64)
    assert set(bundle.variants()) == {"b1", "b4"}

    # Call site: batch 4 — selection must pick "b4".
    man = bundle.manifest["variants"]["b4"]
    p_leaves = jax.tree.leaves(params)
    args = [jnp.array([3, 7, 11, 42], jnp.int32)] + list(p_leaves)
    for shp, dt in zip(man["arg_shapes"][len(args):],
                       man["arg_dtypes"][len(args):]):
        args.append(jnp.zeros(tuple(shp), dt))
    n_cache = len(args) - 1 - len(p_leaves)

    write_call_site_sigs(os.path.join(out_dir, "test_sigs.txt"), args)
    for i, a in enumerate(args):
        np.asarray(a).tofile(os.path.join(out_dir, f"test_arg{i}.bin"))

    # Golden: first step (compared after execute) + N_LOOP more steps
    # with the same feedback wiring (compared after the C loop).
    # Generated from the BUNDLE's own exported program, not the python
    # step: greedy argmax on a random tiny model is chaotic — a 1-ulp
    # logit difference between two compilations flips tokens — and the
    # C side must be compared against the exact computation it runs.
    run = lambda *a: bundle.call("b4", *a)
    outs = run(*args)
    for i, o in enumerate(outs):
        np.asarray(o).tofile(os.path.join(out_dir, f"test_out{i}.bin"))
    write_loop_spec(os.path.join(out_dir, "test_loop.txt"), N_LOOP,
                    len(p_leaves), n_cache)
    cur = outs
    for _ in range(N_LOOP):
        # outs = (next_tokens, logits, *new_cache): logits are
        # verification-only, not fed back.
        cur = run(cur[0], *p_leaves, *cur[2:])
    for i, o in enumerate(cur):
        np.asarray(o).tofile(
            os.path.join(out_dir, f"test_loop_out{i}.bin"))
    # Sanity: the python step agrees with the exported program on the
    # first step (tokens exact, logits/cache within bf16 tolerance).
    ref = step(*args)
    assert bool((outs[0] == ref[0]).all())
    assert all(
        float(jnp.abs(a.astype(jnp.float32) - b2.astype(jnp.float32)
                      ).max()) < 5e-2
        for a, b2 in zip(outs[1:], ref[1:]))


# ---------------------------------------------------------------------------
# Tests — the CPU-only parent
# ---------------------------------------------------------------------------

def test_native_aot_execute(tmp_path):
    out_dir = str(tmp_path / "bundle")
    _build_on_chip("build_matmul", out_dir)
    _run_c_child(out_dir, "m256", timeout=300)


def test_native_aot_decode_family_shape_select(tmp_path):
    """Deployment dispatch for the decode family: one bundle, TWO
    flash_decode variants (different KV lengths); the C executor
    selects the variant FROM THE CALL-SITE SHAPES
    (tdt_bundle_select_variant), compiles its Pallas StableHLO and
    executes it on the chip.  Reference:
    `tools/compile_aot.py:61-183` + `scripts/aot_kernels.txt`."""
    out_dir = str(tmp_path / "decode_bundle")
    _build_on_chip("build_decode_family", out_dir)
    res = _run_c_child(out_dir, "auto", timeout=300)
    assert "SELECTED s1024" in res.stdout, (res.stdout, res.stderr)


def test_native_aot_decode_step_serving_loop(tmp_path):
    """ONE bundled jitted FULL decode step (attn + mlp + lm head +
    greedy sample) selected by the (batch, kv) call-site signature IN
    C, executed on the chip, then re-executed in a C-only SERVING LOOP
    (next tokens + new KV cache fed back positionally) and compared
    against the golden after every step (the reference's AOT
    deployment path, `csrc/op_pybind.cc:25`)."""
    out_dir = str(tmp_path / "decode_step_bundle")
    _build_on_chip("build_decode_step", out_dir)
    res = _run_c_child(out_dir, "auto")
    assert "SELECTED b4" in res.stdout, (res.stdout, res.stderr)
    assert f"LOOP_OK steps={N_LOOP}" in res.stdout, (res.stdout,
                                                     res.stderr)


if __name__ == "__main__":
    globals()[sys.argv[1]](sys.argv[2])
