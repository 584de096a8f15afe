"""Plain reference for the Qwen3 dense family: weights from a seed and
the forward pass, written from the published description.

Nothing of the program is imported here, and nothing the program made
is taken: the benchmark makes the weights (this file), hands them to
the program in the published (HuggingFace) layout through its adapter,
and this file computes the same model from the same seed in float32
with `jax.default_matmul_precision("highest")` — no kernels, no cache,
no batching.

Model (Qwen3 technical report, `Qwen3ForCausalLM`): token embedding;
per layer RMSNorm -> attention (grouped-query, per-head RMSNorm on q
and k before RoPE, rotate-half RoPE with theta from the config,
causal softmax in float32) -> residual -> RMSNorm -> SwiGLU MLP ->
residual; final RMSNorm; untied output head.

Weights are bfloat16 values, the type they are served in; the
reference upcasts them one layer at a time.  Projections are normal
with standard deviation fan_in ** -0.5, the embedding normal(0, 1),
norm weights 1 + 0.1 * normal (so a dropped norm weight shows).

`precision="fp8"` is the CONTROL, never the reference: every matmul's
weights and input activations are rounded to float8_e4m3 (per-tensor
scale, float32 accumulation), the step below the bfloat16 the
configurations state.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

#: Published layout names (HF `config.json` keys) this family reads.
DIM_KEYS = ("hidden_size", "intermediate_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "vocab_size", "rms_norm_eps", "rope_theta")


def dims_of(config: dict) -> dict:
    """The sizes the mathematics needs, by their published names."""
    return {k: config[k] for k in DIM_KEYS}


def _hashable(dims: dict):
    return tuple(sorted(dims.items()))


# ---------------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------------

def base_key(seed: int):
    """A PRNG key from any whole number (the driver's seeds pass
    2**31, more than an int32 holds)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def layer_key(key, i: int):
    return jax.random.fold_in(key, i)


def _normal(key, shape, scale):
    return (jax.random.normal(key, shape, jnp.float32)
            * scale).astype(jnp.bfloat16)


def _norm_weight(key, n):
    return (1.0 + 0.1 * jax.random.normal(key, (n,), jnp.float32)
            ).astype(jnp.bfloat16)


def layer_weights(key, dims: dict) -> dict:
    """One layer in the published layout, `(in, out)` oriented:
    q/k/v/o projections, gate/up/down, the four norm weights."""
    h, f = dims["hidden_size"], dims["intermediate_size"]
    d = dims["head_dim"]
    nq, nkv = dims["num_attention_heads"], dims["num_key_value_heads"]
    ks = jax.random.split(key, 11)
    return {
        "q": _normal(ks[0], (h, nq * d), h ** -0.5),
        "k": _normal(ks[1], (h, nkv * d), h ** -0.5),
        "v": _normal(ks[2], (h, nkv * d), h ** -0.5),
        "o": _normal(ks[3], (nq * d, h), (nq * d) ** -0.5),
        "gate": _normal(ks[4], (h, f), h ** -0.5),
        "up": _normal(ks[5], (h, f), h ** -0.5),
        "down": _normal(ks[6], (f, h), f ** -0.5),
        "ln1": _norm_weight(ks[7], h),
        "ln2": _norm_weight(ks[8], h),
        "q_norm": _norm_weight(ks[9], d),
        "k_norm": _norm_weight(ks[10], d),
    }


def end_weights(key, dims: dict) -> dict:
    """Embedding `(vocab, hidden)`, final norm, head `(hidden, vocab)`."""
    h, v = dims["hidden_size"], dims["vocab_size"]
    k = jax.random.split(jax.random.fold_in(key, 1 << 20), 3)
    return {"embed": _normal(k[0], (v, h), 1.0),
            "ln_f": _norm_weight(k[1], h),
            "lm_head": _normal(k[2], (h, v), h ** -0.5)}


# ---------------------------------------------------------------------------
# the forward pass
# ---------------------------------------------------------------------------

def _fp8(x):
    """Round to float8 precision (e4m3: 4 exponent bits, 3 mantissa
    bits) with one scale for the whole tensor.  `reduce_precision` is
    the rounding the compiler may not take out: a convert to float8
    and back inside one fusion it may skip, and the TPU's did (a
    control that read exactly what the program reads, PERF.md)."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 240.0
    return jax.lax.reduce_precision(x / s, exponent_bits=4,
                                    mantissa_bits=3) * s


#: The weights a matmul reads (the rest are norm weights and the
#: embedding table, which is looked up, not multiplied).
MATMUL_WEIGHTS = ("q", "k", "v", "o", "gate", "up", "down", "lm_head")


def fp8_rounded(weights: dict) -> dict:
    """CONTROL only: ``weights`` (of a layer or of the ends) with every
    matmul weight rounded to float8_e4m3 and handed back in its own
    type — what a program serving float8 weights would hold, given to
    the program in the reference's place to see `correct` fail."""
    return {k: (_fp8(w.astype(jnp.float32)).astype(w.dtype)
                if k in MATMUL_WEIGHTS else w)
            for k, w in weights.items()}


@functools.partial(jax.jit, static_argnames=("dims",))
def _fp8_change(key, *, dims):
    w = layer_weights(key, dict(dims))["o"]
    a = w.astype(jnp.float32)
    b = fp8_rounded({"o": w})["o"].astype(jnp.float32)
    return jnp.mean(jnp.abs(b - a)) / jnp.mean(jnp.abs(a))


def fp8_change(dims: dict, seed: int) -> float:
    """CONTROL only: the mean change `fp8_rounded` makes to one
    projection of the first layer, as a share of its mean magnitude
    (about 0.02 where the rounding takes effect; 0 would mean the
    control is the program)."""
    return float(_fp8_change(layer_key(base_key(seed), 0),
                             dims=_hashable(dims)))


def _mm(x, w, fp8: bool):
    w = w.astype(jnp.float32)
    if fp8:
        x, w = _fp8(x), _fp8(w)
    return jnp.dot(x, w, precision="highest")


def _rms(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def _rope(x, pos, theta):
    """x (S, n, d); rotate-half convention of the published model."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]     # (S, d/2)
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


@functools.partial(jax.jit, static_argnames=("dims", "fp8"))
def _layer(x, key, *, dims, fp8):
    dm = dict(dims)
    w = layer_weights(key, dm)
    s = x.shape[0]
    d = dm["head_dim"]
    nq, nkv = dm["num_attention_heads"], dm["num_key_value_heads"]
    eps, theta = dm["rms_norm_eps"], dm["rope_theta"]
    pos = jnp.arange(s)

    hdn = _rms(x, w["ln1"], eps)
    q = _mm(hdn, w["q"], fp8).reshape(s, nq, d)
    k = _mm(hdn, w["k"], fp8).reshape(s, nkv, d)
    v = _mm(hdn, w["v"], fp8).reshape(s, nkv, d)
    q = _rope(_rms(q, w["q_norm"], eps), pos, theta)
    k = _rope(_rms(k, w["k_norm"], eps), pos, theta)
    g = nq // nkv
    qg = q.reshape(s, nkv, g, d).transpose(1, 2, 0, 3)        # (kv,g,S,d)
    kg = k.transpose(1, 0, 2)                                  # (kv,S,d)
    vg = v.transpose(1, 0, 2)
    causal = pos[:, None] >= pos[None, :]

    def one_group(args):                # one KV head and its g q heads
        qh, kh, vh = args
        sc = jnp.einsum("gsd,td->gst", qh, kh,
                        precision="highest") * d ** -0.5
        sc = jnp.where(causal[None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("gst,td->gsd", p, vh, precision="highest")

    att = jax.lax.map(one_group, (qg, kg, vg))                # (kv,g,S,d)
    att = att.transpose(2, 0, 1, 3).reshape(s, nq * d)
    x = x + _mm(att, w["o"], fp8)

    hdn = _rms(x, w["ln2"], eps)
    act = jax.nn.silu(_mm(hdn, w["gate"], fp8)) * _mm(hdn, w["up"], fp8)
    return x + _mm(act, w["down"], fp8)


@functools.partial(jax.jit, static_argnames=("dims",))
def _embed(tokens, key, *, dims):
    return end_weights(key, dict(dims))["embed"][tokens].astype(
        jnp.float32)


@functools.partial(jax.jit, static_argnames=("dims", "fp8", "n_out"))
def _head(x, first, key, *, dims, fp8, n_out):
    dm = dict(dims)
    w = end_weights(key, dm)
    rows = jax.lax.dynamic_slice_in_dim(x, first, n_out, 0)
    rows = _rms(rows, w["ln_f"], dm["rms_norm_eps"])
    return _mm(rows, w["lm_head"], fp8)


def logits_at(dims: dict, seed: int, tokens, first: int, n_out: int,
              precision: str = "f32"):
    """Logits `(n_out, vocab)` float32 at positions
    ``first .. first + n_out - 1`` of ONE sequence ``tokens`` (1-D,
    already padded by the caller to the length it wants compiled; the
    causal mask keeps padding on the right out of every position read,
    so ``first + n_out`` must not pass the true length).

    ``precision``: "f32" is the reference; "fp8" the control."""
    if precision not in ("f32", "fp8"):
        raise ValueError(f"unknown precision {precision!r}")
    fp8 = precision == "fp8"
    hd = _hashable(dims)
    key = base_key(seed)
    tokens = jnp.asarray(np.asarray(tokens), jnp.int32)
    x = _embed(tokens, key, dims=hd)
    for i in range(dims["num_hidden_layers"]):
        x = _layer(x, layer_key(key, i), dims=hd, fp8=fp8)
    return _head(x, jnp.int32(first), key, dims=hd, fp8=fp8,
                 n_out=int(n_out))
