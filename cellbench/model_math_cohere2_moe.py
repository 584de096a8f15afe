"""Operations and bytes the ALGORITHM needs for the Cohere2-MoE family
(window and full attention mixed, a parallel block, a share of an
expert layer beside four averaged shared experts, a tied head) — the
numerators of this family's roofline shares, from the published sizes
and from what the program COUNTED (experts that got a row, the tokens
each kind of layer's kernel reads).

Only what has to be read is counted: an expert no row was sent to is
not read; a WINDOW layer reads a row's last `sliding_window` tokens,
not the pages they lie in; the embedding is looked up at the bottom
and streamed once, as the head, at the top.  So a share cannot pass
100% unless the time leaves work out.

``d`` is the configuration's object (published `config.json` keys;
`num_experts` the experts HELD here, `share.experts_of_layer` the
router's width); weights and cache are 2-byte (bfloat16), the router
4-byte (float32).  One chip: the family is not sharded.
"""

from __future__ import annotations

BYTES = 2      # bfloat16
ROUTER_BYTES = 4
SLIDING, FULL = "sliding_attention", "full_attention"


def layers_of(d: dict, kind: str) -> int:
    return list(d["layer_types"]).count(kind)


def attention_params(d: dict) -> int:
    """One layer's attention: q and o over the query heads, k and v
    over the key heads; no bias, no q/k norm."""
    h, hd = d["hidden_size"], d["head_dim"]
    return 2 * h * hd * (d["num_attention_heads"]
                         + d["num_key_value_heads"])


def expert_params(d: dict) -> int:
    """One expert, routed or shared: gate, up, down."""
    return 3 * d["hidden_size"] * d["intermediate_size"]


def router_params(d: dict) -> int:
    return d["hidden_size"] * d["share"]["experts_of_layer"]


def layer_params_outside_experts(d: dict) -> int:
    """A layer less its routed experts: attention, the shared experts,
    the router and the ONE norm."""
    return (attention_params(d)
            + d["num_shared_experts"] * expert_params(d)
            + router_params(d) + d["hidden_size"])


def total_params(d: dict) -> int:
    """Every parameter held: the arithmetic of the cut (the embedding
    is the head: tied, counted once)."""
    h = d["hidden_size"]
    return (d["num_hidden_layers"] * (layer_params_outside_experts(d)
                                      + d["num_experts"] * expert_params(d))
            + h * d["vocab_size"] + h)


def fixed_weight_bytes(d: dict) -> int:
    """What every decode step reads whatever the routing: attention,
    the shared experts and the norm of every layer, each router
    (float32), the final norm and the head."""
    h = d["hidden_size"]
    layer = ((layer_params_outside_experts(d) - router_params(d)) * BYTES
             + router_params(d) * ROUTER_BYTES)
    return (d["num_hidden_layers"] * layer
            + (h + h * d["vocab_size"]) * BYTES)


def expert_bytes(d: dict, experts_hit: float) -> float:
    """Routed experts a step reads: ``experts_hit`` is the count over
    all layers of held experts with at least one row."""
    return experts_hit * expert_params(d) * BYTES


def kv_bytes_per_token(d: dict) -> int:
    """One cached position of ONE layer: K and V over the key heads."""
    return 2 * d["num_key_value_heads"] * d["head_dim"] * BYTES


def window_kv_bytes(d: dict, window_tokens: float) -> float:
    """What the window layers' decode kernels read: ``window_tokens``
    is the sum over live rows of min(length, window)."""
    return window_tokens * layers_of(d, SLIDING) * kv_bytes_per_token(d)


def full_kv_bytes(d: dict, full_tokens: float) -> float:
    return full_tokens * layers_of(d, FULL) * kv_bytes_per_token(d)


def one_table_kv_bytes(d: dict, full_tokens: float) -> float:
    """What ONE page table for all layers would hold for the same
    rows: every layer keeps every token."""
    return full_tokens * d["num_hidden_layers"] * kv_bytes_per_token(d)


def kv_saved_share(d: dict, window_tokens: float,
                   full_tokens: float) -> float:
    """1 - live KV bytes by layer kind / what one table would hold."""
    return 1.0 - ((window_kv_bytes(d, window_tokens)
                   + full_kv_bytes(d, full_tokens))
                  / one_table_kv_bytes(d, full_tokens))


def decode_step_bytes(d: dict, window_tokens: float, full_tokens: float,
                      experts_hit: float) -> float:
    """Bytes the chip must read from HBM for one decode step."""
    return (fixed_weight_bytes(d) + expert_bytes(d, experts_hit)
            + window_kv_bytes(d, window_tokens)
            + full_kv_bytes(d, full_tokens))


def window_pairs(d: dict, start: int, tokens: int) -> int:
    """Visible (query, key) pairs of ONE window layer for the queries
    at positions ``start .. start + tokens - 1``: query i sees min(i +
    1, window) keys."""
    w = d["sliding_window"]
    ramp = range(start, min(start + tokens, w))
    return sum(i + 1 for i in ramp) + (tokens - len(ramp)) * w


def window_prefill_flops(d: dict, pieces) -> float:
    """The window layers' attention over ``pieces`` — (start, tokens)
    of each prefill program: q k^T and p v, 2 operations a product,
    every query head."""
    pairs = sum(window_pairs(d, s, n) for s, n in pieces)
    return (4.0 * pairs * d["head_dim"] * d["num_attention_heads"]
            * layers_of(d, SLIDING))


def window_prefill_bytes(d: dict, pieces) -> float:
    """Least bytes for the same: q read and the output written over
    the query heads, K and V of the keys the piece's queries see read
    once."""
    hd, w = d["head_dim"], d["sliding_window"]
    total = 0
    for start, n in pieces:
        keys = n + min(start, w - 1)
        total += (2 * n * d["num_attention_heads"] * hd
                  + 2 * keys * d["num_key_value_heads"] * hd)
    return float(total * BYTES * layers_of(d, SLIDING))
