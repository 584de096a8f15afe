"""Operations and bytes the ALGORITHM needs for the SmallThinker family
(full and window attention mixed 1 : 3, 7 query heads a KV head, every
feed-forward a dropless layer of gated-ReLU experts with no shared
expert, an untied head) — the numerators of this family's roofline
shares, from the published sizes and from what the program COUNTED
(experts that got a row, the tokens each kind of layer's kernel reads,
the tokens of each prefill program).

Only what has to be read is counted: an expert no row was sent to is
not read; a WINDOW layer reads a row's last `sliding_window_size`
tokens, not the pages they lie in; the embedding table is looked up,
not streamed; the head is streamed once.  So a share cannot pass 100%
unless the time leaves work out.

``d`` is the configuration's object (published `config.json` keys);
weights and cache are 2-byte (bfloat16), the router 4-byte (float32).
One chip: the family is not sharded.
"""

from __future__ import annotations

BYTES = 2      # bfloat16
ROUTER_BYTES = 4


def window_layers(d: dict) -> int:
    return sum(int(x) for x in
               d["sliding_window_layout"][:d["num_hidden_layers"]])


def full_layers(d: dict) -> int:
    return d["num_hidden_layers"] - window_layers(d)


def attention_params(d: dict) -> int:
    """One layer's attention: q and o over the query heads, k and v
    over the key heads; no bias, no q/k norm."""
    h, hd = d["hidden_size"], d["head_dim"]
    return 2 * h * hd * (d["num_attention_heads"]
                         + d["num_key_value_heads"])


def expert_params(d: dict) -> int:
    """One expert: gate, up, down."""
    return 3 * d["hidden_size"] * d["moe_ffn_hidden_size"]


def router_params(d: dict) -> int:
    return d["hidden_size"] * d["moe_num_primary_experts"]


def layer_params_outside_experts(d: dict) -> int:
    """A layer less its experts: attention, the router, two norms."""
    return attention_params(d) + router_params(d) + 2 * d["hidden_size"]


def total_params(d: dict) -> int:
    """Every parameter held: the arithmetic of the cut (embedding and
    head are two matrices: untied)."""
    h = d["hidden_size"]
    return (d["num_hidden_layers"] * (
        layer_params_outside_experts(d)
        + d["moe_num_primary_experts"] * expert_params(d))
        + 2 * h * d["vocab_size"] + h)


def fixed_weight_bytes(d: dict) -> int:
    """What every decode step reads whatever the routing: attention
    and the norms of every layer, each router (float32), the final norm
    and the head."""
    h = d["hidden_size"]
    layer = ((attention_params(d) + 2 * h) * BYTES
             + router_params(d) * ROUTER_BYTES)
    return (d["num_hidden_layers"] * layer
            + (h + h * d["vocab_size"]) * BYTES)


def expert_bytes(d: dict, experts_hit: float) -> float:
    """Experts a program reads: ``experts_hit`` is the count over all
    layers of experts with at least one row."""
    return experts_hit * expert_params(d) * BYTES


def kv_bytes_per_token(d: dict) -> int:
    """One cached position of ONE layer: K and V over the key heads."""
    return 2 * d["num_key_value_heads"] * d["head_dim"] * BYTES


def decode_step_bytes(d: dict, window_tokens: float, full_tokens: float,
                      experts_hit: float) -> float:
    """Bytes the chip must read from HBM for one decode step:
    ``window_tokens`` the sum over live rows of min(length, window),
    ``full_tokens`` the sum of their lengths."""
    return (fixed_weight_bytes(d) + expert_bytes(d, experts_hit)
            + window_tokens * window_layers(d) * kv_bytes_per_token(d)
            + full_tokens * full_layers(d) * kv_bytes_per_token(d))


def expected_experts_hit(d: dict, tokens: float) -> float:
    """Experts of ONE layer that ``tokens`` tokens reach when each
    draws its top-k uniformly: E (1 - (1 - k / E) ** tokens) — what a
    prefill program streams, whose own count the program does not
    leave."""
    e = d["moe_num_primary_experts"]
    k = d["moe_num_active_primary_experts"]
    return e * (1.0 - (1.0 - k / e) ** tokens)


def prefill_ffn_flops(d: dict, tokens: float) -> float:
    """The experts' three products for ``tokens`` tokens of ONE prefill
    program, every layer: top-k pairs a token a layer."""
    return (2.0 * tokens * d["moe_num_active_primary_experts"]
            * expert_params(d) * d["num_hidden_layers"])


def prefill_ffn_bytes(d: dict, tokens: float) -> float:
    """The experts ONE prefill program of ``tokens`` tokens streams,
    every layer (`expected_experts_hit`)."""
    return expert_bytes(d, expected_experts_hit(d, tokens)
                        * d["num_hidden_layers"])
