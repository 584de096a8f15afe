"""Operations and bytes the ALGORITHM needs for the GLM-4-MoE-Lite
family (latent attention, a dense feed-forward in the leading layers,
routed experts beside a shared expert in the rest) — the numerators of
this family's roofline shares, from the published sizes and from what
the program COUNTED (experts that got a row, live context positions).

Only what has to be read is counted: an expert no row was sent to is
not read; a cached token is the 576 numbers that carry information
(`kv_lora_rank` + `qk_rope_head_dim`), not the lane padding the
program's pool adds; the embedding table is looked up, not streamed.
So a share cannot pass 100% unless the time leaves work out.

``d`` is the configuration's object (published `config.json` keys);
weights and cache are 2-byte (bfloat16), the router 4-byte (float32).
One chip: the family is not sharded.
"""

from __future__ import annotations

BYTES = 2      # bfloat16
ROUTER_BYTES = 4


def sparse_layers(d: dict) -> int:
    return d["num_hidden_layers"] - d["first_k_dense_replace"]


def attention_params(d: dict) -> int:
    """One layer's latent attention: the five projections and the two
    inner norms."""
    h, hd = d["hidden_size"], d["num_attention_heads"]
    qr, lat = d["q_lora_rank"], d["kv_lora_rank"]
    nope, rope, vd = (d["qk_nope_head_dim"], d["qk_rope_head_dim"],
                      d["v_head_dim"])
    return (h * qr + qr + qr * hd * (nope + rope) + h * (lat + rope)
            + lat + lat * hd * (nope + vd) + hd * vd * h)


def expert_params(d: dict) -> int:
    """One routed expert: gate, up, down."""
    return 3 * d["hidden_size"] * d["moe_intermediate_size"]


def fixed_weight_bytes(d: dict) -> int:
    """What every decode step reads whatever the routing: attention and
    norms of every layer, the dense feed-forwards, each sparse layer's
    router (float32), selection bias and shared expert, the final norm
    and the head."""
    h = d["hidden_size"]
    dense, sparse = d["first_k_dense_replace"], sparse_layers(d)
    params = (d["num_hidden_layers"] * (attention_params(d) + 2 * h)
              + dense * 3 * h * d["intermediate_size"]
              + sparse * d["n_shared_experts"] * expert_params(d)
              + h + h * d["vocab_size"])
    router = sparse * (h + 1) * d["n_routed_experts"] * ROUTER_BYTES
    return params * BYTES + router


def expert_bytes(d: dict, experts_hit: float) -> float:
    """Routed experts a step reads: ``experts_hit`` is the count over
    all sparse layers of experts with at least one row."""
    return experts_hit * expert_params(d) * BYTES


def latent_bytes_per_token(d: dict) -> int:
    """One cached position over all layers: the normalised latent and
    the rotated key, no padding."""
    return ((d["kv_lora_rank"] + d["qk_rope_head_dim"]) * BYTES
            * d["num_hidden_layers"])


def latent_bytes(d: dict, live_tokens: float) -> float:
    return live_tokens * latent_bytes_per_token(d)


def decode_step_bytes(d: dict, live_tokens: float,
                      experts_hit: float) -> float:
    """Bytes the chip must read from HBM for one decode step."""
    return (fixed_weight_bytes(d) + expert_bytes(d, experts_hit)
            + latent_bytes(d, live_tokens))


def mla_decode_flops(d: dict, live_tokens: float) -> float:
    """Absorbed decode attention over the live rows: every head's
    scores over latent + rotated key, and its weighted sum of the
    latent."""
    lat, rope = d["kv_lora_rank"], d["qk_rope_head_dim"]
    return (2 * d["num_attention_heads"] * live_tokens
            * (2 * lat + rope) * d["num_hidden_layers"])


def expert_flops(d: dict, pairs: float) -> float:
    """The routed experts' three products for ``pairs`` (token, expert)
    pairs (summed over the sparse layers)."""
    return 2 * pairs * expert_params(d)


def total_params(d: dict) -> int:
    """Every parameter held (embedding included): the arithmetic of the
    cut."""
    h = d["hidden_size"]
    return (d["num_hidden_layers"] * (attention_params(d) + 2 * h)
            + d["first_k_dense_replace"] * 3 * h * d["intermediate_size"]
            + sparse_layers(d) * (
                (d["n_routed_experts"] + d["n_shared_experts"])
                * expert_params(d) + (h + 1) * d["n_routed_experts"])
            + h + 2 * h * d["vocab_size"])
