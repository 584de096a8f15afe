"""Hand-worked Qwen3-8B numbers (hidden 4096, 32 q / 8 kv heads x 128,
ffn 12288, vocab 151936)."""

import json
import os

import pytest

from cellbench import model_math as mm

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "..", "configs", "qwen3-8b-1c.json")) as f:
    DIMS = json.load(f)


def test_parameters_of_a_layer():
    # q 4096x4096, k and v 4096x1024 each, o 4096x4096, three 4096x12288
    assert mm.layer_matmul_params(DIMS) == (
        16777216 + 2 * 4194304 + 16777216 + 3 * 50331648)
    assert mm.layer_params(DIMS) == 192946432          # 192.9 M
    assert mm.head_params(DIMS) == 4096 * 151936


def test_kv_bytes_a_token():
    # 2 (K, V) x 8 heads x 128 x 2 bytes = 4 KiB a layer; 12 layers
    assert mm.kv_bytes_per_token(DIMS) == 48 * 1024
    assert mm.kv_bytes_per_token(dict(DIMS, num_hidden_layers=36)) == (
        144 * 1024)


def test_decode_step_bytes():
    weights = (12 * 192946432 + 4096 * 151936) * 2 + 4096 * 2
    assert mm.decode_step_bytes(DIMS, 0, 1) == weights   # 5.875 GB
    assert weights == pytest.approx(5.875e9, rel=1e-3)
    # 8 rows of 1000 live positions add 8000 x 48 KiB
    assert mm.decode_step_bytes(DIMS, 8000, 1) - weights == (
        8000 * 48 * 1024)
    # four chips: each its quarter of the shards, ln_f replicated
    full = dict(DIMS, num_hidden_layers=36)
    per_chip = mm.decode_step_bytes(full, 0, 4)
    assert per_chip == pytest.approx(
        (36 * 192946432 + 4096 * 151936) * 2 / 4 + 8192)


def test_prefill_flops():
    s = 2048
    matmul = 2 * s * 192937984                # projections, a layer
    attn = 4 * 32 * 128 * (s * (s + 1) // 2)  # causal QK^T and PV
    want = 12 * (matmul + attn) + 2 * 4096 * 151936
    assert mm.prefill_flops(DIMS, s, 1) == want
    assert want == pytest.approx(9.9e12, rel=0.02)   # ~10 TFLOP
    assert mm.prefill_flops(DIMS, s, 4) == want / 4


def test_unknown_device_kind_is_an_error():
    assert mm.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        mm.load_peaks("cpu")
