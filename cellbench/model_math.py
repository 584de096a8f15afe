"""Operations and bytes the ALGORITHM needs, from shapes alone — the
numerators of the roofline shares.  Only what has to happen is
counted (the embedding table is not streamed, padding does no useful
work, each chip of a tensor-parallel mesh is charged its own shard),
so a share cannot pass 100% unless the time leaves work out.

``dims`` are a configuration's published sizes (`hidden_size`,
`intermediate_size`, `num_hidden_layers`, `num_attention_heads`,
`num_key_value_heads`, `head_dim`, `vocab_size`); ``chips`` the
tensor-parallel width; weights and KV are 2-byte (bfloat16)."""

from __future__ import annotations

import json
import os

BYTES = 2      # bfloat16


def load_peaks(device_kind: str) -> dict:
    """Peaks of the device JAX names; an unknown kind is an error."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "peaks.json")
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path}: add a row with its source")
    return table[device_kind]


def layer_matmul_params(d: dict) -> int:
    """Weights of one layer's seven projections."""
    h, f, hd = d["hidden_size"], d["intermediate_size"], d["head_dim"]
    nq, nkv = d["num_attention_heads"], d["num_key_value_heads"]
    return (h * nq * hd + 2 * h * nkv * hd + nq * hd * h + 3 * h * f)


def layer_params(d: dict) -> int:
    """All weights of one layer: projections and the four norms."""
    return (layer_matmul_params(d) + 2 * d["hidden_size"]
            + 2 * d["head_dim"])


def head_params(d: dict) -> int:
    return d["hidden_size"] * d["vocab_size"]


def kv_bytes_per_token(d: dict) -> int:
    """K and V of one position over all layers, all chips together."""
    return (2 * d["num_key_value_heads"] * d["head_dim"] * BYTES
            * d["num_hidden_layers"])


def decode_step_bytes(d: dict, live_tokens: float, chips: int) -> float:
    """Bytes ONE chip must read from HBM for one decode step: its shard
    of every layer and of the output head once, the final norm, and its
    KV heads of every live context position."""
    weights = (d["num_hidden_layers"] * layer_params(d)
               + head_params(d)) * BYTES / chips
    weights += d["hidden_size"] * BYTES             # ln_f, replicated
    return weights + live_tokens * kv_bytes_per_token(d) / chips


def prefill_flops(d: dict, prompt_len: int, chips: int) -> float:
    """Floating-point operations ONE chip must do to prefill a prompt
    of ``prompt_len`` true tokens: the projections of every position,
    causal attention (half the square), and the head at the last
    position only."""
    s = prompt_len
    matmul = 2 * s * layer_matmul_params(d)
    attn = 2 * 2 * d["num_attention_heads"] * d["head_dim"] * (
        s * (s + 1) // 2)
    return (d["num_hidden_layers"] * (matmul + attn)
            + 2 * head_params(d)) / chips
