"""The SmallThinker family's arithmetic, worked by hand from the
published sizes, and the alias keys the accepted readers ask the
configuration for, each held equal to the published key it stands
for."""

import json
import os

import pytest

from cellbench import model_math_cohere2_moe as window_math
from cellbench import model_math_glm4_moe_lite as sparse_math
from cellbench import model_math_smallthinker as mm

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "..", "configs",
                       "smallthinker-21b-1c.json")) as f:
    CFG = json.load(f)


def test_the_cut_by_hand():
    assert mm.attention_params(CFG) == 2 * 2560 * 128 * 32 == 20_971_520
    assert mm.expert_params(CFG) == 3 * 2560 * 768 == 5_898_240
    assert mm.router_params(CFG) == 163_840
    assert mm.layer_params_outside_experts(CFG) == 21_140_480
    assert mm.total_params(CFG) == 3_966_937_600
    assert (mm.window_layers(CFG), mm.full_layers(CFG)) == (6, 2)


def test_a_decode_steps_bytes_by_hand():
    # attention 41 943 040 B, two norms 10 240 B, the router 655 360 B
    # (float32) a layer; the final norm and the head
    fixed = 8 * (41_943_040 + 10_240 + 655_360) + 5_120 + 777_912_320
    assert mm.fixed_weight_bytes(CFG) == fixed == 1_118_786_560
    assert mm.expert_bytes(CFG, 1) == 11_796_480
    assert mm.kv_bytes_per_token(CFG) == 2048
    # 35 rows of 2500 tokens, 2300 of them inside the window, 500
    # experts hit
    assert mm.decode_step_bytes(CFG, 35 * 2300, 35 * 2500, 500) == (
        fixed + 500 * 11_796_480 + 80_500 * 6 * 2048
        + 87_500 * 2 * 2048)


def test_a_prefill_programs_experts_by_hand():
    # 1024 tokens reach every expert: 8 x 64 experts streamed once ...
    assert mm.expected_experts_hit(CFG, 1024) == pytest.approx(64.0)
    assert mm.prefill_ffn_bytes(CFG, 1024) == pytest.approx(
        512 * 11_796_480)
    # ... under 6 pairs a token a layer, 2 operations a product
    assert mm.prefill_ffn_flops(CFG, 1024) == (
        2 * 1024 * 6 * 5_898_240 * 8)
    # 4 tokens reach at most 24 of a layer's 64
    assert 20 < mm.expected_experts_hit(CFG, 4) < 24


def test_the_aliases_are_the_published_keys():
    """What `moe_ffn_roofline`, `moe_experts_hit` and the `swa_*` /
    `window_*` readers ask the configuration for."""
    assert CFG["moe_intermediate_size"] == CFG["moe_ffn_hidden_size"]
    assert CFG["n_routed_experts"] == CFG["moe_num_primary_experts"]
    assert CFG["first_k_dense_replace"] == 0
    assert CFG["sliding_window"] == CFG["sliding_window_size"]
    assert CFG["layer_types"] == [
        "sliding_attention" if x else "full_attention"
        for x in CFG["sliding_window_layout"]]
    assert CFG["rope_layout"] == CFG["sliding_window_layout"]
    assert len(CFG["layer_types"]) == CFG["num_hidden_layers"]
    # and the accepted functions the cell's readers call price this
    # model as its own arithmetic does
    assert sparse_math.expert_bytes(CFG, 7) == mm.expert_bytes(CFG, 7)
    assert sparse_math.sparse_layers(CFG) == CFG["num_hidden_layers"]
    assert window_math.window_kv_bytes(CFG, 1000) == 1000 * 6 * 2048
    assert window_math.kv_saved_share(CFG, 2300, 2500) == pytest.approx(
        1 - (2300 * 6 + 2500 * 2) / (2500 * 8))
    assert window_math.window_prefill_flops(CFG, [(4096, 1024)]) == (
        4.0 * 1024 * 4096 * 128 * 28 * 6)


def test_the_serving_arithmetic():
    s = CFG["serving"]
    slot = 257 * 196_608 + (s["max_seq"] // 16) * 65_536
    assert slot == 109_248_512
    assert s["kv_budget_bytes_per_chip"] == s["num_slots"] * slot
