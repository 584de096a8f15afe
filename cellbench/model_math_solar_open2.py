"""Operations and bytes the ALGORITHM needs for the Solar-Open2 family
(gated softmax attention without positions in the layers `gqa_layers`
names, Kimi Delta Attention in the rest, a SHARE of a sparse expert
layer in every block, a slice of the vocabulary) — the numerators of
this family's roofline shares, from the published sizes, the share the
configuration states and what the program COUNTED (held experts that
got a row, live rows of the state pool, live context positions).

Only what has to be moved is counted: an expert no row was sent to and
an expert held elsewhere are not read; a row of the state pool that
holds no sequence is neither read nor written; the convolution's three
kept inputs are read and ONE is written (the shift the program makes is
its own); the embedding table is looked up, not streamed; the delta
rule's inputs count at the model's 2 bytes (4 for the log-decay).  So a
share cannot pass 100% unless the time leaves work out.

``d`` is the configuration's object (published `config.json` keys,
`n_routed_experts` the experts HELD here, `share.experts_of_layer` the
router's width); weights and K/V are 2-byte (bfloat16), the router and
the recurrent state 4-byte (float32).  One chip.
"""

from __future__ import annotations

BYTES = 2      # bfloat16
ROUTER_BYTES = 4
STATE_BYTES = 4


def gqa_layers(d: dict) -> int:
    return sum(1 for i in d["gqa_layers"] if i < d["num_hidden_layers"])


def kda_layers(d: dict) -> int:
    return d["num_hidden_layers"] - gqa_layers(d)


def gqa_mixer_params(d: dict) -> int:
    """q, k, v, the output gate, o."""
    h, hd = d["hidden_size"], d["head_dim"]
    nq, nkv = d["num_attention_heads"], d["num_key_value_heads"]
    gate = h * nq * hd if d["use_gqa_gate"] else 0
    return h * nq * hd + 2 * h * nkv * hd + gate + nq * hd * h


def kda_width(d: dict) -> int:
    lin = d["linear_attn_config"]
    return lin["num_heads"] * lin["head_dim"]


def kda_mixer_params(d: dict) -> int:
    """q, k, v, o; the convolution's taps; the decay's and the gate's
    rank-`head_dim` pairs; the write strength; A, b_dt, the head norm."""
    lin = d["linear_attn_config"]
    h, c, r = d["hidden_size"], kda_width(d), lin["head_dim"]
    return (4 * h * c + lin["short_conv_kernel_size"] * 3 * c
            + 2 * (h * r + r * c) + h * lin["num_heads"]
            + lin["num_heads"] + c + r)


def expert_params(d: dict) -> int:
    """One routed expert: gate, up, down."""
    return 3 * d["hidden_size"] * d["moe_intermediate_size"]


def router_params(d: dict) -> int:
    """One layer's router over ALL the layer's experts, and its bias."""
    return (d["hidden_size"] + 1) * d["share"]["experts_of_layer"]


def mixer_params(d: dict) -> int:
    return (gqa_layers(d) * gqa_mixer_params(d)
            + kda_layers(d) * kda_mixer_params(d))


def total_params(d: dict) -> int:
    """Every parameter held (embedding included): the arithmetic of the
    cut."""
    h, n = d["hidden_size"], d["num_hidden_layers"]
    return (mixer_params(d) + n * 2 * h
            + n * ((d["n_routed_experts"] + d["n_shared_experts"])
                   * expert_params(d) + router_params(d))
            + h + 2 * h * d["vocab_size"])


def fixed_weight_bytes(d: dict) -> int:
    """What every decode step reads whatever the routing: the mixers
    and norms of every layer, each layer's router (float32), selection
    bias and shared expert, the final norm and the head."""
    h, n = d["hidden_size"], d["num_hidden_layers"]
    params = (mixer_params(d) + n * 2 * h
              + n * d["n_shared_experts"] * expert_params(d)
              + h + h * d["vocab_size"])
    return params * BYTES + n * router_params(d) * ROUTER_BYTES


def expert_bytes(d: dict, experts_hit: float) -> float:
    """Routed experts a step reads: ``experts_hit`` is the count over
    all layers of HELD experts with at least one row."""
    return experts_hit * expert_params(d) * BYTES


def _layer_state_bytes(d: dict) -> int:
    """One delta-rule layer's state of one sequence: a float32 (d, d)
    matrix a head."""
    lin = d["linear_attn_config"]
    return lin["num_heads"] * lin["head_dim"] ** 2 * STATE_BYTES


def state_bytes_per_slot(d: dict) -> int:
    """What one sequence holds in the delta-rule layers, whatever its
    length: the state and the convolution's last inputs of q, k and
    v."""
    taps = d["linear_attn_config"]["short_conv_kernel_size"]
    conv = (taps - 1) * 3 * kda_width(d) * BYTES
    return kda_layers(d) * (_layer_state_bytes(d) + conv)


def kda_decode_bytes(d: dict, live_rows: float) -> float:
    """One decode step of the delta-rule layers: each live row's state
    read and written once, its kept convolution inputs read and the
    new one written."""
    taps = d["linear_attn_config"]["short_conv_kernel_size"]
    conv = taps * 3 * kda_width(d) * BYTES     # taps - 1 read, 1 written
    return live_rows * kda_layers(d) * (2 * _layer_state_bytes(d) + conv)


def kv_bytes_per_token(d: dict) -> int:
    """One cached position over the softmax layers: K and V."""
    return (2 * d["num_key_value_heads"] * d["head_dim"] * BYTES
            * gqa_layers(d))


def decode_step_bytes(d: dict, live_rows: float, live_tokens: float,
                      experts_hit: float) -> float:
    """Bytes the chip must move for one decode step."""
    return (fixed_weight_bytes(d) + expert_bytes(d, experts_hit)
            + kda_decode_bytes(d, live_rows)
            + live_tokens * kv_bytes_per_token(d))


#: Tokens a chunk of the chunked delta rule (`kernels/kda.py`): the
#: algorithm's own parameter, not a tuning of the program's.
CHUNK = 64


def kda_prefill_flops(d: dict, tokens: float) -> float:
    """The chunked delta rule over ``tokens`` positions, all layers and
    heads: within a chunk of C the two triangular score matrices
    (C^2/2 x dk each), the triangular solve (C^3/3), T times (b K+, b
    V) (C^2/2 x (dk + dv)), the three products with the carried state
    (C x dk x dv each) and the scores times U (C^2/2 x dv)."""
    lin = d["linear_attn_config"]
    dk = dv = lin["head_dim"]
    c = CHUNK
    macs_a_chunk = (c * c * dk + c ** 3 / 3 + c * c * (dk + dv) / 2
                    + 3 * c * dk * dv + c * c * dv / 2)
    return (2 * macs_a_chunk / c * tokens * lin["num_heads"]
            * kda_layers(d))


def kda_prefill_bytes(d: dict, tokens: float, prefills: float) -> float:
    """What the kernel must move: q, k, v in and o out at 2 bytes, the
    log-decay at 4, a write strength a head, and each prefill's final
    state written once."""
    lin = d["linear_attn_config"]
    dk = lin["head_dim"]
    a_token = 4 * dk * BYTES + dk * 4 + 4
    return kda_layers(d) * (tokens * lin["num_heads"] * a_token
                            + prefills * _layer_state_bytes(d))
