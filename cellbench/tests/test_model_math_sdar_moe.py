"""Hand-worked SDAR-30B-A3B numbers (hidden 2048, 32 query / 4 KV heads
of 128, 128 experts of 768 top-8, no shared expert, vocab 151936; 7 of
48 layers; blocks of 4 positions)."""

import json
import os

import pytest

from cellbench import model_math_sdar_moe as mm

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "..", "configs",
                       "sdar-30b-a3b-1c.json")) as f:
    CFG = json.load(f)


def test_parameters_of_a_layer():
    # W_q 2048x4096, W_k and W_v 2048x512, W_o 4096x2048, two head norms
    assert mm.attention_params(CFG) == (
        8388608 + 2 * 1048576 + 8388608 + 256)
    assert mm.router_params(CFG) == 262144
    assert mm.expert_params(CFG) == 3 * 2048 * 768 == 4718592
    # the issue's layer: 19 140 864 outside the experts, 603 979 776 in
    assert (mm.attention_params(CFG) + 2 * 2048
            + mm.router_params(CFG)) == 19140864
    assert 128 * mm.expert_params(CFG) == 603979776


def test_the_cut_is_4_98_billion_parameters():
    assert mm.total_params(CFG) == 7 * 623120640 + 622331904
    assert mm.total_params(CFG) == 4984176384              # 9.968 GB
    assert mm.total_params(dict(CFG, num_hidden_layers=48)) == (
        pytest.approx(30.53e9, rel=1e-3))


def test_a_cached_token_is_14336_bytes():
    assert mm.kv_bytes_per_token(CFG) == 2 * 4 * 128 * 2 * 7 == 14336
    # a slot at max_total 3584: 51.4 MB; 64 of them the configured pool
    assert 3584 * 14336 * 64 == CFG["serving"][
        "kv_budget_bytes_per_chip"] == 3288334336


def test_pass_bytes_follow_the_experts_hit():
    fixed = mm.fixed_weight_bytes(CFG)
    # attention + norms of 7 layers, final norm, head — in bf16 — and
    # seven float32 routers
    params = 7 * (18874624 + 4096) + 2048 + 2048 * 151936
    assert fixed == params * 2 + 7 * 262144 * 4            # 0.894 GB
    assert mm.pass_bytes(CFG, 0, 0, 0) == fixed
    assert mm.expert_bytes(CFG, 1) == 9437184
    # 64 rows of 4 positions, ~115 of 128 experts a layer hit, 65 k
    # live positions
    b = mm.pass_bytes(CFG, 65000, 256, 115 * 7)
    assert b == fixed + 805 * 9437184 + (65000 + 256) * 14336
    assert b == pytest.approx(9.43e9, rel=2e-3)
    # every expert hit is the ceiling: nothing can count more
    assert mm.expert_bytes(CFG, 128 * 7) + fixed == pytest.approx(
        mm.total_params(CFG) * 2 - 2048 * 151936 * 2, rel=1e-3)


def test_pass_operations():
    # 256 positions x 8 experts x 7 layers = 14336 pairs
    flops = mm.pass_flops(CFG, 65000, 256, 14336)
    dense = 2 * 256 * (7 * (18874624 + 262144) + 2048 * 151936)
    experts = 2 * 14336 * 4718592
    attn = 4 * 32 * 128 * 4 * (65000 + 256) * 7
    assert flops == dense + experts + attn
    assert flops == pytest.approx(0.393e12, rel=5e-3)
    # bandwidth is the bound: 11.5 ms of bytes against 2 ms of FLOPs
    assert (mm.pass_bytes(CFG, 65000, 256, 805) / 819e9
            > 5 * flops / 197e12)
