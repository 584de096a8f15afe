"""Operations and bytes the ALGORITHM needs for the SDAR-MoE family
(grouped-query attention, a dropless expert layer in every block,
generation by diffusion over blocks) — the numerators of this family's
roofline shares, from the published sizes and from what the program
COUNTED (experts that got a row, positions a pass fed, live context
positions).

Only what has to be read is counted: an expert no row was sent to is
not read; the embedding table is looked up, not streamed; a pass reads
the K/V of the positions its rows attend and writes its own block's.
So a share cannot pass 100% unless the time leaves work out.

``d`` is the configuration's object (published `config.json` keys);
weights and cache are 2-byte (bfloat16), the router 4-byte (float32).
One chip: the family is not sharded.
"""

from __future__ import annotations

BYTES = 2      # bfloat16
ROUTER_BYTES = 4


def attention_params(d: dict) -> int:
    """One layer's attention: the four projections and the two head
    norms."""
    h, hd = d["hidden_size"], d["head_dim"]
    nq, nkv = d["num_attention_heads"], d["num_key_value_heads"]
    return h * nq * hd + 2 * h * nkv * hd + nq * hd * h + 2 * hd


def expert_params(d: dict) -> int:
    """One expert: gate, up, down."""
    return 3 * d["hidden_size"] * d["moe_intermediate_size"]


def router_params(d: dict) -> int:
    return d["hidden_size"] * d["num_experts"]


def fixed_weight_bytes(d: dict) -> int:
    """What every pass reads whatever the routing: attention and norms
    of every layer, each layer's router (float32), the final norm and
    the head."""
    h, n = d["hidden_size"], d["num_hidden_layers"]
    params = n * (attention_params(d) + 2 * h) + h + h * d["vocab_size"]
    return params * BYTES + n * router_params(d) * ROUTER_BYTES


def expert_bytes(d: dict, experts_hit: float) -> float:
    """Experts a pass reads: ``experts_hit`` is the count over all
    layers of experts with at least one row."""
    return experts_hit * expert_params(d) * BYTES


def kv_bytes_per_token(d: dict) -> int:
    """K and V of one position over all layers."""
    return (2 * d["num_key_value_heads"] * d["head_dim"] * BYTES
            * d["num_hidden_layers"])


def attention_bytes(d: dict, live_tokens: float) -> float:
    """K/V the paged attention kernel must read in a pass whose rows'
    delivered context is ``live_tokens`` positions in all (each row
    reads its committed prefix and its own block: at least that)."""
    return live_tokens * kv_bytes_per_token(d)


def pass_bytes(d: dict, live_tokens: float, positions_fed: float,
               experts_hit: float) -> float:
    """Bytes the chip must move for one block pass: the fixed weights,
    the experts hit, the K/V its rows attend, and the K/V of the
    ``positions_fed`` block rows it writes."""
    return (fixed_weight_bytes(d) + expert_bytes(d, experts_hit)
            + attention_bytes(d, live_tokens)
            + positions_fed * kv_bytes_per_token(d))


def pass_flops(d: dict, live_tokens: float, positions_fed: float,
               pairs: float) -> float:
    """Floating-point operations of one block pass: the projections,
    router and head of every position fed, the experts' three products
    for ``pairs`` (token, expert) pairs (summed over the layers), and
    attention of each row's block over its context
    (``block_length`` queries a row, each over the row's keys)."""
    n = d["num_hidden_layers"]
    block = d["generation"]["block_length"]
    dense = 2 * positions_fed * (
        n * (attention_params(d) + router_params(d))
        + d["hidden_size"] * d["vocab_size"])
    attn = (4 * d["num_attention_heads"] * d["head_dim"] * block
            * (live_tokens + positions_fed) * n)
    return dense + 2 * pairs * expert_params(d) + attn


def total_params(d: dict) -> int:
    """Every parameter held (embedding included): the arithmetic of the
    cut."""
    h = d["hidden_size"]
    return (d["num_hidden_layers"] * (
        attention_params(d) + 2 * h + router_params(d)
        + d["num_experts"] * expert_params(d))
        + h + 2 * h * d["vocab_size"])
