"""Hand-worked Solar-Open2-250B numbers (hidden 4096; softmax layers of
64 query / 8 key heads x 128 with an output gate; delta-rule layers of
64 heads x 128, 4 taps, rank-128 decay and gate maps; 320 experts of
1280 top-8 + 1 shared; vocab 196608) at the cut: layers 0-3 of 48, 40
of 320 experts held, 24576 rows of the vocabulary."""

import json
import os

import pytest

from cellbench import model_math_solar_open2 as mm

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "..", "configs",
                       "solar-open2-250b-1c.json")) as f:
    CFG = json.load(f)

STATE = 64 * 128 * 128 * 4          # a layer's state of one sequence
CONV_ROW = 3 * 8192 * 2             # one kept input of q | k | v


def test_parameters_of_the_mixers_and_an_expert():
    assert mm.gqa_layers(CFG) == 1 and mm.kda_layers(CFG) == 3
    # q, gate and o 4096 x 8192 each; k and v 4096 x 1024
    assert mm.gqa_mixer_params(CFG) == 3 * 33554432 + 2 * 4194304
    # q, k, v, o; 4 taps x 24576; two (4096x128 + 128x8192) pairs;
    # 4096 x 64; A (64), b_dt (8192), the head norm (128)
    assert mm.kda_mixer_params(CFG) == (
        4 * 33554432 + 98304 + 2 * (524288 + 1048576) + 262144
        + 64 + 8192 + 128)                                  # 137.7 M
    assert mm.expert_params(CFG) == 3 * 4096 * 1280         # 15.73 M
    assert mm.router_params(CFG) == 4097 * 320


def test_the_cut_is_3_31_billion_parameters():
    by_hand = (109051904 + 3 * 137732288 + 4 * 2 * 4096
               + 4 * (41 * 15728640 + 1311040)
               + 4096 + 2 * 4096 * 24576)
    assert mm.total_params(CFG) == by_hand
    assert by_hand == pytest.approx(3.308e9, rel=1e-3)
    assert by_hand * 2 == pytest.approx(6.62e9, rel=1e-3)


def test_a_slot_is_13_megabytes_of_state_and_4096_bytes_a_token():
    assert mm.state_bytes_per_slot(CFG) == 3 * (STATE + 3 * CONV_ROW)
    assert mm.state_bytes_per_slot(CFG) == 13025280
    assert mm.kv_bytes_per_token(CFG) == 2 * 8 * 128 * 2 == 4096
    serving = CFG["serving"]
    assert serving["kv_budget_bytes_per_chip"] == serving["num_slots"] * (
        13025280 + serving["max_seq"] * 4096)


def test_decode_step_bytes_follow_what_was_live_and_hit():
    fixed = mm.fixed_weight_bytes(CFG)
    params = (109051904 + 3 * 137732288 + 4 * 2 * 4096
              + 4 * 15728640 + 4096 + 4096 * 24576)
    assert fixed == params * 2 + 4 * 1311040 * 4            # 1.39 GB
    assert mm.decode_step_bytes(CFG, 0, 0, 0) == fixed
    # a live row: each layer's state read and written, three kept
    # inputs read and one written
    assert mm.kda_decode_bytes(CFG, 1) == 3 * (2 * STATE + 4 * CONV_ROW)
    assert mm.kda_decode_bytes(CFG, 128) == pytest.approx(3.30e9,
                                                          rel=1e-2)
    one = 15728640 * 2
    assert mm.expert_bytes(CFG, 1) == one
    # 128 live rows of ~1500 tokens, 96% of the 160 held experts hit
    step = mm.decode_step_bytes(CFG, 128, 192000, 154)
    assert step == (fixed + 154 * one + mm.kda_decode_bytes(CFG, 128)
                    + 192000 * 4096)
    assert step == pytest.approx(10.3e9, rel=1e-2)
    # every held expert hit is the ceiling of the weights' part: all
    # that is held but the embedding table (the routers' other 2 bytes)
    assert mm.expert_bytes(CFG, 160) + fixed == (
        mm.total_params(CFG) * 2 - 4096 * 24576 * 2 + 4 * 1311040 * 2)


def test_the_chunked_delta_rule_operations_and_bytes():
    # a chunk of 64 a head: 64^2 x 128 + 64^3/3 + 64^2 x 128
    # + 3 x 64 x 128^2 + 64^2 x 64 multiply-adds
    macs = 524288 + 262144 / 3 + 524288 + 3145728 + 262144
    assert mm.kda_prefill_flops(CFG, 64) == pytest.approx(
        2 * macs * 64 * 3)
    # 2048 tokens: 56 GFLOP, 0.28 ms of the MXU's peak
    assert mm.kda_prefill_flops(CFG, 2048) == pytest.approx(55.8e9,
                                                            rel=1e-2)
    # a token a head: q, k, v, o at 2 bytes, the decay at 4, beta
    assert mm.kda_prefill_bytes(CFG, 1, 0) == 3 * 64 * (1024 + 512 + 4)
    assert mm.kda_prefill_bytes(CFG, 0, 1) == 3 * STATE
