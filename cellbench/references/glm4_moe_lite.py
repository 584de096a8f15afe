"""Plain reference for the GLM-4-MoE-Lite family (`model_type`
`glm4_moe_lite`): weights from a seed and the forward pass, written
from the published `config.json` keys.

Nothing of the program is imported here, and nothing the program made
is taken: the benchmark makes the weights (this file), hands them to
the program in the published layout through its adapter, and this file
computes the same model from the same seed in float32 with
`precision="highest"` — no kernels, no cache, no batching, the
NON-absorbed attention over the whole sequence, every expert over
every token (a block of `EXPERT_BLOCK` experts at a time, so that it
fits beside the program on the chip) and the router's weights applied
as a dense (tokens, experts) matrix that is zero off the chosen four.

The equations (every size from `config.json`; no biases, untied head,
`rope_scaling` null):

- block l: x <- x + MLA(RMSNorm(x)); x <- x + FFN_l(RMSNorm(x)).
  FFN_l is SwiGLU of width `intermediate_size` for
  l < `first_k_dense_replace`, the expert layer after.  Final RMSNorm,
  head.
- MLA, H heads: c_q = RMSNorm(x W_qa) (`q_lora_rank`); q = c_q W_qb ->
  per head [q_nope (`qk_nope_head_dim`) | q_rope (`qk_rope_head_dim`)];
  [c_kv (`kv_lora_rank`) | k_r] = x W_kva; c_kv <- RMSNorm(c_kv);
  k_rope = RoPE(k_r), one for all heads; q_rope <- RoPE(q_rope);
  [k_nope | v (`v_head_dim`)] = c_kv W_kvb per head; scores
  (q_nope.k_nope + q_rope.k_rope) / sqrt(nope + rope), causal softmax,
  o = sum p v, output W_o.
- expert layer (`topk_method` noaux_tc, `n_group` = `topk_group` = 1:
  no group limit): s = sigmoid(x W_r); chosen = top-k of s + b (b the
  selection bias, used for the choice only); w = s[chosen] /
  (sum s[chosen] + 1e-20) * `routed_scaling_factor`
  (`norm_topk_prob`); y = sum_i w_i SwiGLU_i(x) + SwiGLU_shared(x),
  the shared expert of width `moe_intermediate_size` *
  `n_shared_experts`.  No token is dropped.

Departures (also in the configuration file's `assumed`): the
multi-token-prediction block (`num_nextn_predict_layers`) is not part
of the served forward pass and is left out; RoPE pairs dimension i with
i + d/2 (rotate-half), the program's convention — with random weights
the other pairing is a permutation of columns.

Weights are bfloat16 values, the type they are served in (the router's
and the bias are float32 values of bfloat16 draws); projections normal
with standard deviation fan_in ** -0.5, the embedding normal(0, 1),
norm weights 1 + 0.1 * normal, the selection bias 0.05 * normal (small
and non-zero, so its choice-only use is exercised).

`precision="fp8"` is the CONTROL, never the reference: every matmul's
weights and input activations rounded to float8_e4m3 (float32
accumulation), the step below the configuration's bfloat16.  The
router stays float32 there, as a float8 deployment would keep it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

#: Published layout names (HF `config.json` keys) this family reads.
DIM_KEYS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
            "num_hidden_layers", "num_attention_heads", "q_lora_rank",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "n_routed_experts", "n_shared_experts",
            "num_experts_per_tok", "first_k_dense_replace",
            "routed_scaling_factor", "norm_topk_prob", "vocab_size",
            "rms_norm_eps", "rope_theta")

#: Experts made and computed at a time.
EXPERT_BLOCK = 8


def dims_of(config: dict) -> dict:
    """The sizes the mathematics needs, by their published names."""
    d = {k: config[k] for k in DIM_KEYS}
    assert d["n_routed_experts"] % EXPERT_BLOCK == 0, d
    assert config.get("n_group", 1) == 1 == config.get("topk_group", 1), (
        "group-limited routing is not written here")
    return d


def _hashable(dims: dict):
    return tuple(sorted(dims.items()))


def is_sparse(dims: dict, i: int) -> bool:
    return i >= dims["first_k_dense_replace"]


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


def layer_weights(key, dims: dict, sparse: bool) -> dict:
    """One layer in the published layout, `(in, out)` oriented, less
    its routed experts (`expert_weights`): the attention's five
    projections and two inner norms, the two layer norms, and either
    the dense gate/up/down or the router, its selection bias and the
    shared expert."""
    h, hd = dims["hidden_size"], dims["num_attention_heads"]
    qr, lat = dims["q_lora_rank"], dims["kv_lora_rank"]
    nope, rope = dims["qk_nope_head_dim"], dims["qk_rope_head_dim"]
    vd = dims["v_head_dim"]
    ks = jax.random.split(key, 16)
    w = {
        "q_a": _normal(ks[0], (h, qr), h ** -0.5),
        "q_a_norm": _norm_weight(ks[1], qr),
        "q_b": _normal(ks[2], (qr, hd * (nope + rope)), qr ** -0.5),
        "kv_a": _normal(ks[3], (h, lat + rope), h ** -0.5),
        "kv_a_norm": _norm_weight(ks[4], lat),
        "kv_b": _normal(ks[5], (lat, hd * (nope + vd)), lat ** -0.5),
        "o": _normal(ks[6], (hd * vd, h), (hd * vd) ** -0.5),
        "ln1": _norm_weight(ks[7], h),
        "ln2": _norm_weight(ks[8], h),
    }
    if sparse:
        e = dims["n_routed_experts"]
        fs = dims["moe_intermediate_size"] * dims["n_shared_experts"]
        w.update(
            router=_normal(ks[9], (h, e), h ** -0.5).astype(jnp.float32),
            e_bias=_normal(ks[10], (e,), 0.05).astype(jnp.float32),
            shared_gate=_normal(ks[11], (h, fs), h ** -0.5),
            shared_up=_normal(ks[12], (h, fs), h ** -0.5),
            shared_down=_normal(ks[13], (fs, h), fs ** -0.5))
    else:
        f = dims["intermediate_size"]
        w.update(gate=_normal(ks[9], (h, f), h ** -0.5),
                 up=_normal(ks[10], (h, f), h ** -0.5),
                 down=_normal(ks[11], (f, h), f ** -0.5))
    return w


def expert_weights(key, dims: dict, block: int) -> dict:
    """Routed experts ``block * EXPERT_BLOCK ..`` of the layer whose
    key is ``key``: gate and up `(EXPERT_BLOCK, h, f)`, down
    `(EXPERT_BLOCK, f, h)`."""
    h, f = dims["hidden_size"], dims["moe_intermediate_size"]
    ks = jax.random.split(jax.random.fold_in(key, 1000 + block), 3)
    n = EXPERT_BLOCK
    return {"gate": _normal(ks[0], (n, h, f), h ** -0.5),
            "up": _normal(ks[1], (n, h, f), h ** -0.5),
            "down": _normal(ks[2], (n, f, h), f ** -0.5)}


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

def _fp8(x, batched: bool = False):
    """Round to float8 precision (e4m3) with one scale for the whole
    tensor — one for each leading index with ``batched`` (a stack of
    experts).  `reduce_precision` is the rounding the compiler may not
    take out (PERF.md)."""
    axes = tuple(range(1, x.ndim)) if batched else None
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axes, keepdims=True),
                    1e-30) / 240.0
    return jax.lax.reduce_precision(x / s, exponent_bits=4,
                                    mantissa_bits=3) * s


#: The weights a matmul reads in bfloat16 (the rest: norm weights, the
#: embedding table, which is looked up, and the float32 router).
MATMUL_WEIGHTS = ("q_a", "q_b", "kv_a", "kv_b", "o", "gate", "up",
                  "down", "shared_gate", "shared_up", "shared_down",
                  "lm_head")


def fp8_rounded(weights: dict) -> dict:
    """CONTROL only: ``weights`` (of a layer, of a block of experts —
    rounded expert by expert — or of the ends) with every matmul weight
    rounded to float8_e4m3 and handed back in its own type: what a
    program serving float8 weights would hold."""
    return {k: (_fp8(w.astype(jnp.float32), batched=w.ndim == 3
                     ).astype(w.dtype)
                if k in MATMUL_WEIGHTS else w)
            for k, w in weights.items()}


@functools.partial(jax.jit, static_argnames=("dims",))
def _fp8_change(key, *, dims):
    w = layer_weights(key, dict(dims), False)["o"]
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
    """x (S, n, d); dimension i rotates with i + d/2."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]     # (S, d/2)
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _swiglu(x, gate, up, down, fp8):
    return _mm(jax.nn.silu(_mm(x, gate, fp8)) * _mm(x, up, fp8), down,
               fp8)


def router_weights(hdn, w, dm: dict):
    """Dense (tokens, experts) float32 combine weights: zero off each
    token's chosen experts."""
    s = jax.nn.sigmoid(jnp.dot(hdn, w["router"], precision="highest"))
    _, chosen = jax.lax.top_k(s + w["e_bias"], dm["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, chosen, axis=1)
    if dm["norm_topk_prob"]:
        picked = picked / (picked.sum(axis=-1, keepdims=True) + 1e-20)
    picked = picked * dm["routed_scaling_factor"]
    rows = jnp.arange(hdn.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, chosen].set(picked)


@functools.partial(jax.jit, static_argnames=("dims", "fp8", "sparse"))
def _attention_and_dense(x, key, *, dims, fp8, sparse):
    """x after the attention block; then for a dense layer x after the
    feed-forward too, for a sparse one (x, normed input of the experts,
    dense combine weights, the shared expert's output)."""
    dm = dict(dims)
    w = layer_weights(key, dm, sparse)
    s = x.shape[0]
    hd = dm["num_attention_heads"]
    lat, vd = dm["kv_lora_rank"], dm["v_head_dim"]
    nope, rope = dm["qk_nope_head_dim"], dm["qk_rope_head_dim"]
    eps, theta = dm["rms_norm_eps"], dm["rope_theta"]
    pos = jnp.arange(s)

    hdn = _rms(x, w["ln1"], eps)
    cq = _rms(_mm(hdn, w["q_a"], fp8), w["q_a_norm"], eps)
    q = _mm(cq, w["q_b"], fp8).reshape(s, hd, nope + rope)
    kv = _mm(hdn, w["kv_a"], fp8)
    c = _rms(kv[:, :lat], w["kv_a_norm"], eps)
    k_rope = _rope(kv[:, None, lat:], pos, theta)[:, 0]      # (S, rope)
    q_rope = _rope(q[..., nope:], pos, theta)
    kvb = _mm(c, w["kv_b"], fp8).reshape(s, hd, nope + vd)
    causal = pos[:, None] >= pos[None, :]

    def one_head(args):
        qn, qr, kn, v = args                 # (S, nope) (S, rope) ..
        sc = (jnp.dot(qn, kn.T, precision="highest")
              + jnp.dot(qr, k_rope.T, precision="highest")
              ) * (nope + rope) ** -0.5
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        return jnp.dot(p, v, precision="highest")

    heads = lambda t: t.transpose(1, 0, 2)   # noqa: E731
    att = jax.lax.map(one_head, (
        heads(q[..., :nope]), heads(q_rope), heads(kvb[..., :nope]),
        heads(kvb[..., nope:])))                              # (H,S,v)
    x = x + _mm(att.transpose(1, 0, 2).reshape(s, hd * vd), w["o"], fp8)

    hdn = _rms(x, w["ln2"], eps)
    if not sparse:
        return x + _swiglu(hdn, w["gate"], w["up"], w["down"], fp8)
    shared = _swiglu(hdn, w["shared_gate"], w["shared_up"],
                     w["shared_down"], fp8)
    return x, hdn, router_weights(hdn, w, dm), shared


@functools.partial(jax.jit, static_argnames=("dims", "fp8", "block"))
def _expert_block(hdn, combine, key, *, dims, fp8, block):
    """sum over this block's experts of combine[:, e] * SwiGLU_e(hdn):
    every expert over every token."""
    w = expert_weights(key, dict(dims), block)
    if fp8:
        hdn = _fp8(hdn)
        w = {k: _fp8(v.astype(jnp.float32), batched=True)
             for k, v in w.items()}
    f32 = lambda t: t.astype(jnp.float32)    # noqa: E731
    g = jnp.einsum("sh,ehf->esf", hdn, f32(w["gate"]),
                   precision="highest")
    u = jnp.einsum("sh,ehf->esf", hdn, f32(w["up"]), precision="highest")
    act = jax.nn.silu(g) * u
    if fp8:
        act = _fp8(act, batched=True)
    y = jnp.einsum("esf,efh->esh", act, f32(w["down"]),
                   precision="highest")
    lo = block * EXPERT_BLOCK
    return jnp.einsum("esh,se->sh", y,
                      combine[:, lo:lo + EXPERT_BLOCK],
                      precision="highest")


def layer_forward(x, key, i: int, dims: dict, fp8: bool = False):
    """x (S, hidden) float32 through layer ``i``."""
    hd = _hashable(dims)
    if not is_sparse(dims, i):
        return _attention_and_dense(x, key, dims=hd, fp8=fp8,
                                    sparse=False)
    x, hdn, combine, y = _attention_and_dense(x, key, dims=hd, fp8=fp8,
                                              sparse=True)
    for b in range(dims["n_routed_experts"] // EXPERT_BLOCK):
        y = y + _expert_block(hdn, combine, key, dims=hd, fp8=fp8,
                              block=b)
    return x + y


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
        x = layer_forward(x, layer_key(key, i), i, dims, fp8)
    return _head(x, jnp.int32(first), key, dims=hd, fp8=fp8,
                 n_out=int(n_out))
