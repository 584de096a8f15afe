"""Hand-worked GLM-4.7-Flash numbers (hidden 2048, 20 heads, q rank
768, latent 512 + 64 rotated, nope 192, v 256, dense ffn 10240, 64
experts of 1536 top-4 + 1 shared, vocab 154880; 7 of 47 layers)."""

import json
import os

import pytest

from cellbench import model_math_glm4_moe_lite as mm

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "..", "configs",
                       "glm-4.7-flash-1c.json")) as f:
    CFG = json.load(f)


def test_parameters_of_the_attention_and_an_expert():
    # 2048x768 + 768x(20x256) + 2048x576 + 512x(20x448) + 5120x2048,
    # and the two inner norms (768, 512)
    assert mm.attention_params(CFG) == (
        1572864 + 3932160 + 1179648 + 4587520 + 10485760 + 768 + 512)
    assert mm.expert_params(CFG) == 3 * 2048 * 1536        # 9.44 M
    assert mm.sparse_layers(CFG) == 6


def test_the_cut_is_4_53_billion_parameters():
    assert mm.total_params(CFG) == pytest.approx(4.531e9, rel=1e-3)
    assert mm.total_params(CFG) * 2 == pytest.approx(9.06e9, rel=1e-3)
    # the whole model: 47 layers, 29.9 B
    assert mm.total_params(dict(CFG, num_hidden_layers=47)) == (
        pytest.approx(29.9e9, rel=5e-3))


def test_a_cached_token_is_1152_bytes_a_layer():
    assert mm.latent_bytes_per_token(CFG) == 7 * 1152 == 8064
    assert mm.latent_bytes(CFG, 80000) == 80000 * 8064


def test_decode_step_bytes_follow_the_experts_hit():
    fixed = mm.fixed_weight_bytes(CFG)
    # attention + norms of 7 layers, one dense ffn, six shared experts,
    # final norm, head — in bf16 — and six float32 routers with bias
    params = (7 * (21759232 + 4096) + 3 * 2048 * 10240
              + 6 * 9437184 + 2048 + 2048 * 154880)
    assert fixed == params * 2 + 6 * (2048 + 1) * 64 * 4   # 1.18 GB
    assert mm.decode_step_bytes(CFG, 0, 0) == fixed
    one = 9437184 * 2
    assert mm.expert_bytes(CFG, 1) == one                  # 18.9 MB
    # 56 of 64 experts in each of 6 layers, 80 k live tokens
    step = mm.decode_step_bytes(CFG, 80000, 56 * 6)
    assert step == fixed + 336 * one + 80000 * 8064
    assert step == pytest.approx(8.17e9, rel=1e-3)
    # every expert hit is the ceiling: nothing can count more
    assert mm.expert_bytes(CFG, 64 * 6) + fixed == pytest.approx(
        mm.total_params(CFG) * 2 - 2048 * 154880 * 2, rel=1e-3)


def test_kernel_operations():
    # 32 rows x 4 experts x 6 layers = 768 pairs
    assert mm.expert_flops(CFG, 768) == 2 * 768 * 9437184
    # a head's scores over 576 and its sum over 512, per live token
    assert mm.mla_decode_flops(CFG, 1000) == (
        2 * 20 * 1000 * (512 + 64 + 512) * 7)
