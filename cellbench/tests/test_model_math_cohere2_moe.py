"""`model_math_cohere2_moe` worked by hand at the configuration's own
sizes, and the accepted expert-layer readers' arithmetic
(`model_math_glm4_moe_lite`) read from THIS configuration's keys."""

import json
import os

import pytest

from cellbench import model_math_cohere2_moe as mm
from cellbench import model_math_glm4_moe_lite as glm

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "..", "configs",
                       "command-a-plus-218b-1c.json")) as f:
    CFG = json.load(f)


def test_the_cut_by_hand():
    # q and o 4096 x 16384 each, k and v 4096 x 1024 each
    assert mm.attention_params(CFG) == 2 * 4096 * 16384 + 2 * 4096 * 1024
    assert mm.attention_params(CFG) == 142606336
    assert mm.expert_params(CFG) == 3 * 4096 * 4096 == 50331648
    # attention + four shared experts + the router's 128 columns + norm
    assert mm.layer_params_outside_experts(CFG) == (
        142606336 + 4 * 50331648 + 4096 * 128 + 4096) == 344461312
    # four layers with 16 experts each, an eighth of the tied
    # vocabulary, the final norm
    assert mm.total_params(CFG) == (
        4 * (344461312 + 16 * 50331648) + 32768 * 4096 + 4096)
    assert mm.total_params(CFG) == 4733292544
    assert mm.total_params(CFG) * 2 / 1e9 == pytest.approx(9.47, abs=5e-3)


def test_the_published_model_by_the_same_arithmetic():
    """32 layers of 128 experts over the whole vocabulary: 218.3 B, and
    25.0 B with 8 of them active."""
    whole = dict(CFG, num_hidden_layers=32, num_experts=128,
                 vocab_size=262144, layer_types=CFG["layer_types"] * 8)
    assert mm.total_params(whole) / 1e9 == pytest.approx(218.3, abs=0.05)
    active = mm.total_params(dict(whole, num_experts=8))
    assert active / 1e9 == pytest.approx(25.0, abs=0.05)


def test_a_decode_steps_bytes_by_kind():
    # bfloat16 but the float32 routers; the head is the embedding
    fixed = (4 * ((142606336 + 4 * 50331648 + 4096) * 2 + 4096 * 128 * 4)
             + (4096 + 32768 * 4096) * 2)
    assert mm.fixed_weight_bytes(CFG) == fixed == 3028328448
    assert mm.expert_bytes(CFG, 1) == 100663296
    # K and V of 8 heads x 128, 2 bytes: 4096 B a token a layer
    assert mm.kv_bytes_per_token(CFG) == 4096
    assert mm.window_kv_bytes(CFG, 1000) == 3 * 4096 * 1000
    assert mm.full_kv_bytes(CFG, 1000) == 4096 * 1000
    assert mm.decode_step_bytes(CFG, 24 * 4096, 24 * 10000, 50) == (
        fixed + 50 * 100663296 + 24 * 4096 * 3 * 4096 + 24 * 10000 * 4096)
    # a row of 25600 tokens: 105 + 3 x 16.8 MB against 419 MB
    assert mm.kv_saved_share(CFG, 4096, 25600) == pytest.approx(
        1 - (3 * 4096 + 25600) / (4 * 25600))
    assert mm.kv_saved_share(CFG, 1000, 1000) == 0.0


def test_a_chunks_window_attention():
    # below the window a query sees its own position's count of keys
    assert mm.window_pairs(CFG, 0, 4) == 1 + 2 + 3 + 4
    assert mm.window_pairs(CFG, 0, 1024) == 1024 * 1025 // 2
    # past it, 4096 each
    assert mm.window_pairs(CFG, 8192, 1024) == 1024 * 4096
    # across the edge
    assert mm.window_pairs(CFG, 4094, 4) == 4095 + 4096 + 4096 + 4096
    piece = [(8192, 1024)]
    assert mm.window_prefill_flops(CFG, piece) == (
        4 * 1024 * 4096 * 128 * 128 * 3)
    # q and out of 128 heads, K and V of the 1024 + 4095 keys seen
    assert mm.window_prefill_bytes(CFG, piece) == (
        (2 * 1024 * 128 * 128 + 2 * (1024 + 4095) * 8 * 128) * 2 * 3)


def test_the_accepted_expert_readers_price_this_configuration():
    """`moe_ffn_roofline` and `moe_experts_hit` read
    `model_math_glm4_moe_lite`: from this file's keys it sees one
    expert's bytes, four sparse layers and sixteen held experts."""
    assert glm.expert_params(CFG) == mm.expert_params(CFG) == 50331648
    assert glm.expert_bytes(CFG, 3) == mm.expert_bytes(CFG, 3) == 301989888
    assert glm.sparse_layers(CFG) == 4 == CFG["num_hidden_layers"]
    assert CFG["n_routed_experts"] == CFG["num_experts"] == 16
    assert CFG["n_shared_experts"] == CFG["num_shared_experts"] == 4
    assert CFG["moe_intermediate_size"] == CFG["intermediate_size"]
