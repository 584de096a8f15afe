"""Plain reference for the Cohere2-MoE family (`model_type`
`cohere2_moe`): weights from a seed and the forward pass, written from
the published `config.json` keys.

Nothing of the program is imported here, and nothing the program made
is taken: the benchmark makes the weights (this file), hands them to
the program in the published layout through its adapter, and this file
computes the same model from the same seed in float32 with
`precision="highest"` — no kernels, no cache, no batching.  So that a
25 k-token request fits beside the program on the chip, the work is cut
into BLOCKS that change no number's meaning: attention a key head's
group of query heads and `QUERY_BLOCK` query rows at a time (a window
layer over the keys its rows can see, a full layer over all keys), the
expert layer `TOKEN_BLOCK` rows at a time, `EXPERT_BLOCK` experts' weights
made at a time and one expert computed at a time.

The equations (`u = LN(h)`: `(h - mean) / sqrt(var + layer_norm_eps) *
g`, no bias; a row a token):

- every layer l: `h <- h + Attn_l(u) + FFN_l(u)` — ONE norm, two
  readers, one residual add (`use_parallel_block`);
- `Attn_l`: q = u W_q (`num_attention_heads` x `head_dim`), k, v = u
  W_k, u W_v (`num_key_value_heads`), no bias, no q/k norm;
  `layer_types[l]`: `sliding_attention` — rotary embedding over
  ADJACENT pairs `(x[2i], x[2i+1])` (`rope_gptj`), `rope_theta`, all
  `head_dim` dimensions (`rotary_pct` 1), and key j visible to query i
  iff `i - sliding_window < j <= i`; `full_attention` — NO positions,
  causal; softmax of q k^T head_dim^-0.5, a key head serving heads /
  kv_heads query heads; out = concat(heads) W_o;
- `FFN_l` (every layer: `first_k_dense_replace` 0): s = sigmoid(u W_r)
  over ALL the layer's experts, float32 (`expert_selection_fn`
  sigmoid: no selection bias, no scaling); chosen = top-k of s; w =
  s[chosen] / sum(s[chosen]) (`norm_topk_prob`); expert e: (silu(u
  Wg_e) * (u Wu_e)) Wd_e, `intermediate_size` wide; routed = sum over
  the chosen experts HELD HERE of w_e expert_e(u); shared = the MEAN of
  the `num_shared_experts` shared experts' outputs
  (`shared_expert_combination_strategy` average; each as wide as a
  routed expert); out = routed + shared;
- logits = `logit_scale` * LN_f(h) E^T over the held rows of the tied
  embedding.

DEPARTURES FROM THE PUBLISHED MODEL, here as in the program:

- the vision tower is no part of the language model's `config` and is
  left out;
- THE SHARE (guide section 4; the configuration's `share` group): the
  router is `share.experts_of_layer` wide, this chip holds the experts
  `share.experts_held` = [lo, hi) (`num_experts` = hi - lo) and the
  `vocab_size` rows of the vocabulary it was given.  What the experts
  elsewhere would have added is left out and that partial result goes on
  to the next layer.  `held` can be given to `dims_of` to compute another
  chip's share, or the whole layer, of the same weights (expert e's
  weights depend on e alone).

Weights are bfloat16 values, the type they are served in (the router's
float32): projections normal with standard deviation fan_in ** -0.5 —
the tied embedding among them, as the head it is (hidden ** -0.5: with
normal(0, 1) rows the head's logit of the INPUT token stands sqrt(hidden)
spreads over every other, the model repeats its last prompt token
whatever its layers compute, and no comparison of served tokens can see
a fault; my chip run, PR 44) — norm weights 1 + 0.1 * normal.

`precision="fp8"` is the CONTROL, never the reference: every matmul's
weights and input activations rounded to float8_e4m3 (float32
accumulation).  The router stays float32 there, as a float8 deployment
would keep it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

#: Published layout names (HF `config.json` keys) this family reads.
DIM_KEYS = ("hidden_size", "num_hidden_layers", "layer_types",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "intermediate_size", "num_experts", "num_experts_per_tok",
            "num_shared_experts", "norm_topk_prob", "sliding_window",
            "rope_theta", "layer_norm_eps", "logit_scale", "vocab_size")

#: Experts made at a time; query rows and token rows computed at a time.
EXPERT_BLOCK = 4
QUERY_BLOCK = 1024
TOKEN_BLOCK = 3200
SLIDING, FULL = "sliding_attention", "full_attention"


def dims_of(config: dict, held=None) -> dict:
    """The sizes the mathematics needs, by their published names, and
    the share: `experts_of_layer` (the router's width) and `held` (lo,
    hi).  ``held``: another share of the same layer (tests)."""
    d = {k: config[k] for k in DIM_KEYS}
    # (a rehearsal's config cuts the depth under the pattern: the LAST
    # layers then, so that a full layer is among them)
    d["layer_types"] = tuple(d["layer_types"])[-d["num_hidden_layers"]:]
    assert len(d["layer_types"]) == d["num_hidden_layers"]
    assert set(d["layer_types"]) <= {SLIDING, FULL}
    assert config["model_type"] == "cohere2_moe"
    assert config["use_parallel_block"] and config["use_gated_activation"]
    assert config["hidden_act"] == "silu" and not config["use_qk_norm"]
    assert config["expert_selection_fn"] == "sigmoid"
    assert config["shared_expert_combination_strategy"] == "average"
    assert config["position_embedding_type"] == "rope_gptj"
    assert config["rotary_pct"] == 1 and config["tie_word_embeddings"]
    assert config["first_k_dense_replace"] == 0
    assert not config["attention_bias"]
    d["experts_of_layer"] = config["share"]["experts_of_layer"]
    lo, hi = held or config["share"]["experts_held"]
    assert hi - lo == d["num_experts"] or held is not None, (lo, hi)
    assert lo % EXPERT_BLOCK == 0 == hi % EXPERT_BLOCK, (lo, hi)
    d["held"] = (int(lo), int(hi))
    return d


def _hashable(dims: dict):
    return tuple(sorted(dims.items()))


def kind_of(dims: dict, i: int) -> str:
    return dims["layer_types"][i]


def _block(n: int, cap: int) -> int:
    """The largest divisor of ``n`` that is at most ``cap``."""
    return next(b for b in range(min(n, cap), 0, -1) if n % b == 0)


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
    """One layer in the published layout, `(in, out)` oriented: its ONE
    norm, its attention, its router and its shared experts (stacked) —
    less its routed experts (`expert_weights`)."""
    h, f = dims["hidden_size"], dims["intermediate_size"]
    d, ns = dims["head_dim"], dims["num_shared_experts"]
    nq, nkv = dims["num_attention_heads"], dims["num_key_value_heads"]
    ks = jax.random.split(key, 9)
    return {"ln": _norm_weight(ks[0], h),
            "q": _normal(ks[1], (h, nq * d), h ** -0.5),
            "k": _normal(ks[2], (h, nkv * d), h ** -0.5),
            "v": _normal(ks[3], (h, nkv * d), h ** -0.5),
            "o": _normal(ks[4], (nq * d, h), (nq * d) ** -0.5),
            "router": _normal(ks[5], (h, dims["experts_of_layer"]),
                              h ** -0.5).astype(jnp.float32),
            "shared_gate": _normal(ks[6], (ns, h, f), h ** -0.5),
            "shared_up": _normal(ks[7], (ns, h, f), h ** -0.5),
            "shared_down": _normal(ks[8], (ns, f, h), f ** -0.5)}


def expert_weights(key, dims: dict, block: int) -> dict:
    """Routed experts ``block * EXPERT_BLOCK ..`` OF THE LAYER (their
    published numbers, whichever chip holds them): gate and up
    `(EXPERT_BLOCK, hidden, f)`, down `(EXPERT_BLOCK, f, hidden)`."""
    h, f = dims["hidden_size"], dims["intermediate_size"]
    ks = jax.random.split(jax.random.fold_in(key, 1000 + block), 3)
    n = EXPERT_BLOCK
    return {"gate": _normal(ks[0], (n, h, f), h ** -0.5),
            "up": _normal(ks[1], (n, h, f), h ** -0.5),
            "down": _normal(ks[2], (n, f, h), f ** -0.5)}


def held_blocks(dims: dict):
    """The blocks an adapter stacks into the program's held experts."""
    lo, hi = dims["held"]
    return range(lo // EXPERT_BLOCK, hi // EXPERT_BLOCK)


def end_weights(key, dims: dict) -> dict:
    """The tied embedding `(vocab, hidden)` — the head is its transpose
    — over the rows of the vocabulary held here, and the final norm."""
    h, v = dims["hidden_size"], dims["vocab_size"]
    k = jax.random.split(jax.random.fold_in(key, 1 << 20), 2)
    return {"embed": _normal(k[0], (v, h), h ** -0.5),
            "ln_f": _norm_weight(k[1], h)}


# ---------------------------------------------------------------------------
# the forward pass
# ---------------------------------------------------------------------------

def _fp8(x, batched: bool = False):
    """Round to float8 precision (e4m3) with one scale for the whole
    tensor — one for each leading index with ``batched`` (a stack of
    experts)."""
    axes = tuple(range(1, x.ndim)) if batched else None
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axes, keepdims=True),
                    1e-30) / 240.0
    return jax.lax.reduce_precision(x / s, exponent_bits=4,
                                    mantissa_bits=3) * s


#: The weights a matmul reads in bfloat16 (the rest: norm weights and
#: the float32 router).  The tied embedding is looked up AND multiplied:
#: the control rounds it, so its lookup reads rounded rows too, as a
#: float8 deployment's one table would.
MATMUL_WEIGHTS = ("q", "k", "v", "o", "gate", "up", "down",
                  "shared_gate", "shared_up", "shared_down", "embed")


def fp8_rounded(weights: dict) -> dict:
    """CONTROL only: ``weights`` (of a layer, of a block of experts —
    rounded expert by expert — or of the ends) with every matmul weight
    rounded to float8_e4m3 and handed back in its own type."""
    return {k: (_fp8(w.astype(jnp.float32), batched=w.ndim == 3
                     ).astype(w.dtype)
                if k in MATMUL_WEIGHTS else w)
            for k, w in weights.items()}


@functools.partial(jax.jit, static_argnames=("dims",))
def _fp8_change(key, *, dims):
    w = layer_weights(key, dict(dims))["k"]
    a = w.astype(jnp.float32)
    b = fp8_rounded({"k": w})["k"].astype(jnp.float32)
    return jnp.mean(jnp.abs(b - a)) / jnp.mean(jnp.abs(a))


def fp8_change(dims: dict, seed: int) -> float:
    """CONTROL only: the mean change `fp8_rounded` makes to one
    projection, as a share of its mean magnitude."""
    return float(_fp8_change(layer_key(base_key(seed), 0),
                             dims=_hashable(dims)))


def _mm(x, w, fp8: bool):
    w = w.astype(jnp.float32)
    if fp8:
        x, w = _fp8(x), _fp8(w)
    return jnp.dot(x, w, precision="highest")


def layer_norm(x, w, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def rope_pairs(x, pos, theta):
    """x (S, n, d): dimension 2i rotates with 2i + 1 (`rope_gptj`)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]     # (S, d/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def _swiglu(x, gate, up, down, fp8):
    return _mm(jax.nn.silu(_mm(x, gate, fp8)) * _mm(x, up, fp8), down,
               fp8)


def attention(u, w, dm: dict, kind: str, fp8: bool = False):
    """u (S, hidden) -> (S, hidden): the layer's attention, a key head
    and `QUERY_BLOCK` query rows at a time."""
    s = u.shape[0]
    d, nq = dm["head_dim"], dm["num_attention_heads"]
    nkv = dm["num_key_value_heads"]
    rep = nq // nkv
    sliding = kind == SLIDING
    win = dm["sliding_window"]
    qb = _block(s, QUERY_BLOCK)
    pos = jnp.arange(s)
    k = _mm(u, w["k"], fp8).reshape(s, nkv, d)
    v = _mm(u, w["v"], fp8).reshape(s, nkv, d)
    if sliding:
        k = rope_pairs(k, pos, dm["rope_theta"])
        # keys a block of query rows can see: those of the block and
        # the ``win`` before it, at positions ``start - win + column``
        pad = ((win, 0), (0, 0), (0, 0))
        k, v = jnp.pad(k, pad), jnp.pad(v, pad)
    span = win + qb if sliding else s

    def one_group(g, out):
        cols = jax.lax.dynamic_slice_in_dim(w["q"], g * rep * d, rep * d, 1)
        q = _mm(u, cols, fp8).reshape(s, rep, d)
        if sliding:
            q = rope_pairs(q, pos, dm["rope_theta"])
        kg, vg = k[:, g], v[:, g]

        def one_block(start):
            qrows = jax.lax.dynamic_slice_in_dim(q, start, qb, 0)
            qpos = start + jnp.arange(qb)
            if sliding:
                kk = jax.lax.dynamic_slice_in_dim(kg, start, span, 0)
                vv = jax.lax.dynamic_slice_in_dim(vg, start, span, 0)
                kpos = start - win + jnp.arange(span)
                seen = ((kpos[None, :] >= 0)
                        & (kpos[None, :] <= qpos[:, None])
                        & (kpos[None, :] > qpos[:, None] - win))
            else:
                kk, vv = kg, vg
                seen = pos[None, :] <= qpos[:, None]

            def one_head(qh):                              # (qb, d)
                sc = jnp.dot(qh, kk.T, precision="highest") * d ** -0.5
                p = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
                return jnp.dot(p, vv, precision="highest")

            att = jax.lax.map(one_head, qrows.transpose(1, 0, 2))
            return att.transpose(1, 0, 2).reshape(qb, rep * d)

        att = jax.lax.map(one_block, jnp.arange(0, s, qb)).reshape(
            s, rep * d)
        rows = jax.lax.dynamic_slice_in_dim(w["o"], g * rep * d, rep * d, 0)
        return out + _mm(att, rows, fp8)

    return jax.lax.fori_loop(0, nkv, one_group,
                             jnp.zeros((s, dm["hidden_size"]),
                                       jnp.float32))


def router_weights(u, w, dm: dict):
    """Dense (tokens, experts of the layer) float32 combine weights:
    zero off each token's chosen experts."""
    s = jax.nn.sigmoid(jnp.dot(u, w["router"], precision="highest"))
    picked, chosen = jax.lax.top_k(s, dm["num_experts_per_tok"])
    if dm["norm_topk_prob"]:
        picked = picked / picked.sum(axis=-1, keepdims=True)
    rows = jnp.arange(u.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, chosen].set(picked)


def shared_part(u, w, dm: dict, fp8: bool = False):
    """The MEAN of the shared experts' outputs for rows u."""
    f32 = lambda t: t.astype(jnp.float32)    # noqa: E731

    def one(total, e):
        return total + _swiglu(u, f32(w["shared_gate"][e]),
                               f32(w["shared_up"][e]),
                               f32(w["shared_down"][e]), fp8), None

    total, _ = jax.lax.scan(one, jnp.zeros_like(u),
                            jnp.arange(dm["num_shared_experts"]))
    return total / dm["num_shared_experts"]


@functools.partial(jax.jit, static_argnames=("dims",))
def _norm(x, key, *, dims):
    dm = dict(dims)
    return layer_norm(x, layer_weights(key, dm)["ln"], dm["layer_norm_eps"])


@functools.partial(jax.jit, static_argnames=("dims", "fp8", "kind"),
                   donate_argnums=(0,))
def _add_attention(x, u, key, *, dims, fp8, kind):
    dm = dict(dims)
    return x + attention(u, layer_weights(key, dm), dm, kind, fp8)


@functools.partial(jax.jit, static_argnames=("dims", "fp8"))
def _ffn_ends(u, key, *, dims, fp8):
    """u -> (the dense combine weights, the shared experts' mean),
    `TOKEN_BLOCK` rows at a time."""
    dm = dict(dims)
    w = layer_weights(key, dm)
    tb = _block(u.shape[0], TOKEN_BLOCK)
    combine, shared = jax.lax.map(
        lambda rows: (router_weights(rows, w, dm),
                      shared_part(rows, w, dm, fp8)),
        u.reshape(-1, tb, u.shape[1]))
    return (combine.reshape(u.shape[0], -1),
            shared.reshape(u.shape))


@functools.partial(jax.jit, static_argnames=("dims", "fp8"))
def _held_experts(u, combine, key, *, dims, fp8):
    """sum over the experts held of combine[:, e] * expert_e(u): every
    held expert over every token, a block of weights made and one
    expert computed at a time, `TOKEN_BLOCK` rows at a time.  ONE
    program a shape: the block is a loop's index, not a static
    argument."""
    dm = dict(dims)
    lo, hi = dm["held"]
    f32 = lambda t: t.astype(jnp.float32)    # noqa: E731
    tb = _block(u.shape[0], TOKEN_BLOCK)
    blocks = u.reshape(-1, tb, u.shape[1])
    if fp8:
        blocks = jax.lax.map(_fp8, blocks)

    def block(b, total):
        w = expert_weights(key, dm, b)
        if fp8:
            w = {k: _fp8(f32(t), batched=True) for k, t in w.items()}

        def one(total, e):
            def rows(args):
                x, c = args
                act = (jax.nn.silu(jnp.dot(x, f32(w["gate"][e]),
                                           precision="highest"))
                       * jnp.dot(x, f32(w["up"][e]), precision="highest"))
                if fp8:
                    act = _fp8(act)
                return jnp.dot(act, f32(w["down"][e]),
                               precision="highest") * c[:, None]
            col = jax.lax.dynamic_index_in_dim(
                combine, b * EXPERT_BLOCK + e, 1, keepdims=False)
            y = jax.lax.map(rows, (blocks, col.reshape(-1, tb)))
            return total + y.reshape(total.shape), None

        return jax.lax.scan(one, total, jnp.arange(EXPERT_BLOCK))[0]

    return jax.lax.fori_loop(lo // EXPERT_BLOCK, hi // EXPERT_BLOCK, block,
                             jnp.zeros_like(u))


def routed_part(u, combine, key, dims: dict, fp8: bool = False):
    """What the experts `dims["held"]` add for each token."""
    return _held_experts(u, combine, key, dims=_hashable(dims), fp8=fp8)


def ffn_parts(x, key, dims: dict, fp8: bool = False):
    """(routed, shared) of a layer's expert layer for the layer's INPUT
    x (S, hidden): both read the layer's one norm."""
    hd = _hashable(dims)
    u = _norm(x, key, dims=hd)
    combine, shared = _ffn_ends(u, key, dims=hd, fp8=fp8)
    return routed_part(u, combine, key, dims, fp8), shared


_add = jax.jit(lambda x, a, b: x + a + b, donate_argnums=(0,))


def layer_forward(x, key, i: int, dims: dict, fp8: bool = False):
    """x (S, hidden) float32 through layer ``i`` (x is consumed)."""
    hd = _hashable(dims)
    routed, shared = ffn_parts(x, key, dims, fp8)
    x = _add_attention(x, _norm(x, key, dims=hd), key, dims=hd, fp8=fp8,
                       kind=kind_of(dims, i))
    return _add(x, routed, shared)


@functools.partial(jax.jit, static_argnames=("dims", "fp8"))
def _embed(tokens, key, *, dims, fp8):
    w = end_weights(key, dict(dims))
    if fp8:
        w = fp8_rounded(w)
    return w["embed"][tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("dims", "fp8", "n_out"))
def _head(x, first, key, *, dims, fp8, n_out):
    dm = dict(dims)
    w = end_weights(key, dm)
    rows = jax.lax.dynamic_slice_in_dim(x, first, n_out, 0)
    rows = layer_norm(rows, w["ln_f"], dm["layer_norm_eps"])
    return _mm(rows, w["embed"].T, fp8) * dm["logit_scale"]


def logits_at(dims: dict, seed: int, tokens, first: int, n_out: int,
              precision: str = "f32"):
    """Logits `(n_out, vocab)` float32 at positions
    ``first .. first + n_out - 1`` of ONE sequence ``tokens`` (1-D,
    already padded by the caller to the length it wants compiled; every
    layer is causal, so padding on the right reaches no position read,
    and ``first + n_out`` must not pass the true length).

    ``precision``: "f32" is the reference; "fp8" the control."""
    if precision not in ("f32", "fp8"):
        raise ValueError(f"unknown precision {precision!r}")
    fp8 = precision == "fp8"
    hd = _hashable(dims)
    key = base_key(seed)
    tokens = jnp.asarray(np.asarray(tokens), jnp.int32)
    x = _embed(tokens, key, dims=hd, fp8=fp8)
    for i in range(dims["num_hidden_layers"]):
        x = layer_forward(x, layer_key(key, i), i, dims, fp8)
    return _head(x, jnp.int32(first), key, dims=hd, fp8=fp8,
                 n_out=int(n_out))
