"""Operations and bytes the ALGORITHM needs for the Nemotron-H family
(one mixer a layer, named by `hybrid_override_pattern`: `M` Mamba-2
over a recurrent state a sequence, `*` softmax attention without
positions, `E` a SHARE of a latent expert layer of two-matrix
squared-ReLU experts; a slice of the vocabulary) — the numerators of
this family's roofline shares, from the published sizes, the share the
configuration states and what the program COUNTED (held experts that
got a row, live rows of the state pool, live context positions).

Only what has to be moved is counted: an expert no row was sent to and
an expert held elsewhere are not read; a row of the state pool that
holds no sequence is neither read nor written; the convolution's kept
inputs are read and ONE is written (the shift the program makes is its
own); the embedding table is looked up, not streamed; the state-space
kernels' inputs and outputs count at the model's 2 bytes (4 for the
step size), whatever the program hands its kernels.  So a share cannot
pass 100% unless the time leaves work out.

``d`` is the configuration's object (published `config.json` keys,
`n_routed_experts` the experts HELD here, `share.experts_of_layer` the
router's width); weights and K/V are 2-byte (bfloat16), the router, the
state-space layers' A, dt_bias and D and the recurrent state 4-byte
(float32; the parameters COUNT as one each).  One chip.
"""

from __future__ import annotations

BYTES = 2      # bfloat16
ROUTER_BYTES = 4
STATE_BYTES = 4


def layers(d: dict, kind: str) -> int:
    """Layers of ``kind`` (`M`, `*` or `E`) held."""
    return d["hybrid_override_pattern"].count(kind)


def ssm_inner(d: dict) -> int:
    """Channels of a state-space layer's x, z and y."""
    return d["mamba_num_heads"] * d["mamba_head_dim"]


def conv_width(d: dict) -> int:
    """Channels the convolution runs over: x, B and C."""
    return ssm_inner(d) + 2 * d["n_groups"] * d["ssm_state_size"]


def ssm_mixer_params(d: dict) -> int:
    """W_in (z | xBC | dt), the convolution's taps and bias, A_log,
    dt_bias and D a head, the gated norm, W_out."""
    h, c, n = d["hidden_size"], ssm_inner(d), d["mamba_num_heads"]
    return (h * (c + conv_width(d) + n) + (d["conv_kernel"] + 1)
            * conv_width(d) + 3 * n + c + c * h)


def attention_params(d: dict) -> int:
    """q, k, v, o."""
    h, hd = d["hidden_size"], d["head_dim"]
    nq, nkv = d["num_attention_heads"], d["num_key_value_heads"]
    return 2 * h * nq * hd + 2 * h * nkv * hd


def expert_params(d: dict) -> int:
    """One routed expert: two matrices in the latent."""
    return 2 * d["moe_latent_size"] * d["moe_intermediate_size"]


def router_params(d: dict) -> int:
    """One layer's router over ALL the layer's experts, and its bias."""
    return (d["hidden_size"] + 1) * d["share"]["experts_of_layer"]


def moe_shared_params(d: dict) -> int:
    """What every token of an expert layer reads beside the router:
    the latent's two projections and the shared expert."""
    h = d["hidden_size"]
    return (2 * h * d["moe_latent_size"]
            + 2 * h * d["moe_shared_expert_intermediate_size"])


def total_params(d: dict) -> int:
    """Every parameter held (embedding included): the arithmetic of the
    cut."""
    h = d["hidden_size"]
    return (layers(d, "M") * (ssm_mixer_params(d) + h)
            + layers(d, "*") * (attention_params(d) + h)
            + layers(d, "E") * (router_params(d) + moe_shared_params(d)
                                + h + d["n_routed_experts"]
                                * expert_params(d))
            + h + 2 * h * d["vocab_size"])


def fixed_weight_bytes(d: dict) -> int:
    """What every decode step reads whatever the routing: the mixers
    and norms of the `M` and `*` layers, each `E` layer's norm, router
    (float32), selection bias, latent projections and shared expert,
    the final norm and the head."""
    h = d["hidden_size"]
    params = (layers(d, "M") * (ssm_mixer_params(d) + h)
              + layers(d, "*") * (attention_params(d) + h)
              + layers(d, "E") * (moe_shared_params(d) + h)
              + h + h * d["vocab_size"])
    return (params * BYTES
            + layers(d, "E") * router_params(d) * ROUTER_BYTES)


def expert_bytes(d: dict, experts_hit: float) -> float:
    """Routed experts a step reads: ``experts_hit`` is the count over
    all `E` layers of HELD experts with at least one row."""
    return experts_hit * expert_params(d) * BYTES


def _layer_state_bytes(d: dict) -> int:
    """One state-space layer's state of one sequence: a float32
    (head_dim, state) matrix a head."""
    return ssm_inner(d) * d["ssm_state_size"] * STATE_BYTES


def state_bytes_per_slot(d: dict) -> int:
    """What one sequence holds in the state-space layers, whatever its
    length: the state and the convolution's last inputs."""
    conv = (d["conv_kernel"] - 1) * conv_width(d) * BYTES
    return layers(d, "M") * (_layer_state_bytes(d) + conv)


def ssm_decode_bytes(d: dict, live_rows: float) -> float:
    """One decode step of the state-space layers: each live row's
    state read and written once, its kept convolution inputs read and
    the new one written."""
    conv = d["conv_kernel"] * conv_width(d) * BYTES
    return live_rows * layers(d, "M") * (2 * _layer_state_bytes(d) + conv)


def kv_bytes_per_token(d: dict) -> int:
    """One cached position over the attention layers: K and V."""
    return (2 * d["num_key_value_heads"] * d["head_dim"] * BYTES
            * layers(d, "*"))


def decode_step_bytes(d: dict, live_rows: float, live_tokens: float,
                      experts_hit: float) -> float:
    """Bytes the chip must move for one decode step."""
    return (fixed_weight_bytes(d) + expert_bytes(d, experts_hit)
            + ssm_decode_bytes(d, live_rows)
            + live_tokens * kv_bytes_per_token(d))


def ssm_prefill_flops(d: dict, tokens: float) -> float:
    """The chunked recurrence over ``tokens`` positions, all `M` layers
    and heads: within a chunk of C = `chunk_size` a head's triangular
    scores times dt x (C^2/2 x P), C S_0 and the state's update (C x N
    x P each), and its share of the group's triangular C B^T (C^2/2 x
    N over the heads of a group)."""
    c, p, n = d["chunk_size"], d["mamba_head_dim"], d["ssm_state_size"]
    heads = d["mamba_num_heads"]
    macs_a_chunk = (c * c * p / 2 + 2 * c * n * p
                    + c * c * n / 2 * d["n_groups"] / heads)
    return 2 * macs_a_chunk / c * tokens * heads * layers(d, "M")


def ssm_prefill_bytes(d: dict, tokens: float, prefills: float) -> float:
    """What the kernel must move: x in and y out and a group's B and C
    at 2 bytes, a step size a head at 4, and each prefill's final state
    written once."""
    a_token = (2 * ssm_inner(d) * BYTES
               + 2 * d["n_groups"] * d["ssm_state_size"] * BYTES
               + d["mamba_num_heads"] * 4)
    return layers(d, "M") * (tokens * a_token
                             + prefills * _layer_state_bytes(d))
