"""Hand-worked NVIDIA-Nemotron-3-Super-120B-A12B numbers (hidden 4096;
Mamba-2 layers of 128 heads x 64 over a state of 128, 8 groups, 4 taps;
attention of 32 query / 2 key heads x 128; 512 experts of 2 x 1024 x
2688 top-22 in a 1024-wide latent + a shared expert of 5376; vocab
131072) at the cut: layers 0-10 of 88 (`MEMEMEM*EME`), 128 of 512
experts held, 32768 rows of the vocabulary."""

import json
import os

import pytest

from cellbench import model_math_nemotron_h as mm

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "..", "configs",
                       "nemotron-3-super-120b-1c.json")) as f:
    CFG = json.load(f)

STATE = 128 * 64 * 128 * 4          # a layer's state of one sequence
CONV_ROW = 10240 * 2                # one kept input of x | B | C
W_IN = 4096 * (8192 + 10240 + 128)
W_OUT = 8192 * 4096


def test_the_cut_keeps_the_published_ratio_of_layers():
    assert [mm.layers(CFG, k) for k in "M*E"] == [5, 1, 5]
    whole = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
             "EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME")
    assert len(whole) == 88 == CFG["reduced_from_source"][
        "num_hidden_layers"][0]
    assert CFG["hybrid_override_pattern"] == whole[:11]
    assert [whole.count(k) for k in "M*E"] == [40, 8, 40]


def test_parameters_of_the_mixers_and_an_expert():
    assert mm.ssm_inner(CFG) == 8192 and mm.conv_width(CFG) == 10240
    # W_in, 4 taps + the bias over 10240 channels, A_log, dt_bias and D
    # (128 each), the gated norm (8192), W_out; and the layer's norm
    assert mm.ssm_mixer_params(CFG) == (
        W_IN + 5 * 10240 + 3 * 128 + 8192 + W_OUT)
    assert mm.ssm_mixer_params(CFG) + 4096 == 109640064
    # q and o 4096 x 4096, k and v 4096 x 256
    assert mm.attention_params(CFG) + 4096 == 35655680
    assert mm.expert_params(CFG) == 2 * 1024 * 2688 == 5505024
    # outside the experts: the router over 512 and its bias, the
    # latent's two projections, the shared expert, the norm
    assert (mm.router_params(CFG) + mm.moe_shared_params(CFG) + 4096
            == 4097 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376 + 4096
            == 54530560)


def test_the_cut_holds_4_648_163_712_parameters():
    assert mm.total_params(CFG) == (
        5 * 109640064 + 35655680 + 5 * (54530560 + 128 * 5505024)
        + 4096 + 2 * 32768 * 4096)
    assert mm.total_params(CFG) == 4648163712
    # the whole model, at the published counts
    whole = dict(CFG, hybrid_override_pattern="M" * 40 + "*" * 8
                 + "E" * 40, n_routed_experts=512, vocab_size=131072)
    assert mm.total_params(whole) == pytest.approx(120.67e9, rel=1e-3)


def test_a_slot_is_21_megabytes_of_state_and_1024_bytes_a_token():
    assert mm.state_bytes_per_slot(CFG) == 5 * (STATE + 3 * CONV_ROW)
    assert mm.state_bytes_per_slot(CFG) == 21278720
    assert mm.kv_bytes_per_token(CFG) == 2 * 2 * 128 * 2 == 1024
    s = CFG["serving"]
    assert s["kv_budget_bytes_per_chip"] == s["num_slots"] * (
        21278720 + 1024 * s["max_seq"])


def test_a_decode_step_at_128_slots_moves_14_gigabytes():
    fixed = mm.fixed_weight_bytes(CFG)
    # everything but the experts and the embedding at 2 bytes, the
    # routers' 5 x 4097 x 512 at 4
    assert fixed == 2 * (4648163712 - 5 * 128 * 5505024 - 32768 * 4096
                         ) + 2 * 5 * 4097 * 512
    assert fixed == pytest.approx(2.00e9, rel=2e-3)
    assert mm.expert_bytes(CFG, 640) == 640 * 5505024 * 2
    assert mm.expert_bytes(CFG, 640) == pytest.approx(7.05e9, rel=1e-3)
    # a live row: each layer's state read and written, three kept
    # convolution inputs read and one written
    assert mm.ssm_decode_bytes(CFG, 1) == 5 * (2 * STATE + 4 * CONV_ROW)
    assert mm.ssm_decode_bytes(CFG, 128) == pytest.approx(5.42e9,
                                                          rel=1e-3)
    step = mm.decode_step_bytes(CFG, 128, 128 * 600, 640)
    assert step == (fixed + mm.expert_bytes(CFG, 640)
                    + mm.ssm_decode_bytes(CFG, 128) + 128 * 600 * 1024)
    assert step / 819e9 == pytest.approx(17.75e-3, rel=1e-2)


def test_the_chunked_recurrence_over_a_prefill():
    # a head and chunk of 128: scores x dt x (128^2/2 x 64), C S_0 and
    # the update (128 x 128 x 64 each), a sixteenth of the group's
    # C B^T (128^2/2 x 128)
    a_chunk = 524288 + 2 * 1048576 + 1048576 / 16
    assert mm.ssm_prefill_flops(CFG, 128) == 2 * a_chunk * 128 * 5
    assert mm.ssm_prefill_flops(CFG, 512) == pytest.approx(13.76e9,
                                                           rel=1e-3)
    # a token: x and y (8192 x 2 bytes each), B and C (1024 x 2 each),
    # a step size a head (128 x 4)
    assert mm.ssm_prefill_bytes(CFG, 1, 0) == 5 * (32768 + 4096 + 512)
    assert mm.ssm_prefill_bytes(CFG, 0, 1) == 5 * STATE
