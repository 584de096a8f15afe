"""Plain reference for the SmallThinker family (`model_name`
`smallthinker_*`): weights from a seed and the forward pass, written
from the published `config.json` keys.

Nothing of the program is imported here, and nothing the program made
is taken: the benchmark makes the weights (this file), hands them to
the program in the published layout through its adapter, and this file
computes the same model from the same seed in float32 with
`precision="highest"` — no kernels, no cache, no batching.  So that a
14 k-token request fits beside the program on the chip, the work is cut
into BLOCKS that change no number's meaning: attention a key head's
group of query heads and `QUERY_BLOCK` query rows at a time (a window
layer over the keys its rows can see, a full layer over all keys), the
expert layer `TOKEN_BLOCK` rows at a time, `EXPERT_BLOCK` experts'
weights made at a time and one expert computed at a time, the head
`vocab / VOCAB_BLOCKS` columns at a time.

The equations (`RMS(h) = h / sqrt(mean(h^2) + rms_norm_eps) * g`; a row
a token; no bias anywhere):

- layer l, input h: `r = h W_r` in float32, `moe_num_primary_experts`
  logits — from h ITSELF, as it enters the layer, before any norm ("the
  router placed before attention"); `u = RMS_1(h)`; `h' = h +
  Attn_l(u)`; `m = RMS_2(h')`; chosen = the top
  `moe_num_active_primary_experts` of r; `p = softmax(r[chosen])`
  (`moe_primary_router_apply_softmax` with `norm_topk_prob`: the softmax
  over all experts, its top-k, renormalised — the same numbers);
  `expert_e(m) = (relu(m Wg_e) * (m Wu_e)) Wd_e`, `moe_ffn_hidden_size`
  wide; `h'' = h' + sum over the chosen e of p_e expert_e(m)`.  No
  shared expert, no scaling factor, no selection bias; every layer is
  an expert layer;
- `Attn_l`: q = u W_q (`num_attention_heads` x `head_dim`), k, v = u
  W_k, u W_v (`num_key_value_heads`), no q/k norm;
  `sliding_window_layout[l] == 1` (`rope_layout` is the same list):
  rotary embedding, `rope_theta`, all `head_dim` dimensions, dimension i
  rotating with i + head_dim / 2, and key j visible to query i iff `i -
  sliding_window_size < j <= i`; `== 0`: NO positions, causal over
  everything; softmax of q k^T head_dim^-0.5, a key head serving heads
  / kv_heads query heads (7 as published); out = concat(heads) W_o;
- logits = RMS_f(h) W_head, the head its own `(hidden, vocab)` matrix
  (`tie_word_embeddings` false).

DEPARTURES FROM THE PUBLISHED MODEL, here as in the program: none in
the mathematics; the depth is the configuration's (`num_hidden_layers`
and the two layouts cut to it).  `described_as` speaks of "secondary
experts": the config has keys for primary experts only, and none are
run.

Weights are bfloat16 values, the type they are served in (the router's
float32): projections normal with standard deviation fan_in ** -0.5,
the embedding normal(0, 1), the head hidden ** -0.5, norm weights 1 +
0.1 * normal.

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
DIM_KEYS = ("hidden_size", "num_hidden_layers", "sliding_window_layout",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "moe_ffn_hidden_size", "moe_num_primary_experts",
            "moe_num_active_primary_experts", "sliding_window_size",
            "rope_theta", "rms_norm_eps", "vocab_size")

#: Experts made at a time; query rows and token rows computed at a
#: time; the head's column blocks.
EXPERT_BLOCK = 8
QUERY_BLOCK = 1024
TOKEN_BLOCK = 3584
VOCAB_BLOCKS = 8


def dims_of(config: dict) -> dict:
    """The sizes the mathematics needs, by their published names."""
    d = {k: config[k] for k in DIM_KEYS}
    layout = tuple(int(x) for x in config["sliding_window_layout"])
    assert tuple(int(x) for x in config["rope_layout"]) == layout, (
        "a window layer rotates and a full layer does not")
    # (a rehearsal's config cuts the depth under the pattern: the FIRST
    # layers then — a full layer leads every period)
    d["sliding_window_layout"] = layout[:d["num_hidden_layers"]]
    assert len(d["sliding_window_layout"]) == d["num_hidden_layers"]
    assert set(layout) <= {0, 1}
    assert config["model_name"].startswith("smallthinker")
    assert config["moe_primary_router_apply_softmax"]
    assert config["norm_topk_prob"] and config["rope_scaling"] is None
    assert not config["tie_word_embeddings"]
    assert d["moe_num_primary_experts"] % EXPERT_BLOCK == 0, d
    assert d["vocab_size"] % VOCAB_BLOCKS == 0, d
    return d


def _hashable(dims: dict):
    return tuple(sorted(dims.items()))


def is_window(dims: dict, i: int) -> bool:
    return bool(dims["sliding_window_layout"][i])


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
    """One layer in the published layout, `(in, out)` oriented: its two
    norms, its attention and its router — less its experts
    (`expert_weights`)."""
    h, d = dims["hidden_size"], dims["head_dim"]
    nq, nkv = dims["num_attention_heads"], dims["num_key_value_heads"]
    ks = jax.random.split(key, 7)
    return {"ln1": _norm_weight(ks[0], h),
            "ln2": _norm_weight(ks[1], h),
            "q": _normal(ks[2], (h, nq * d), h ** -0.5),
            "k": _normal(ks[3], (h, nkv * d), h ** -0.5),
            "v": _normal(ks[4], (h, nkv * d), h ** -0.5),
            "o": _normal(ks[5], (nq * d, h), (nq * d) ** -0.5),
            "router": _normal(ks[6], (h, dims["moe_num_primary_experts"]),
                              h ** -0.5).astype(jnp.float32)}


def expert_weights(key, dims: dict, block) -> dict:
    """Experts ``block * EXPERT_BLOCK ..`` of the layer: gate and up
    `(EXPERT_BLOCK, hidden, f)`, down `(EXPERT_BLOCK, f, hidden)`."""
    h, f = dims["hidden_size"], dims["moe_ffn_hidden_size"]
    ks = jax.random.split(jax.random.fold_in(key, 1000 + block), 3)
    n = EXPERT_BLOCK
    return {"gate": _normal(ks[0], (n, h, f), h ** -0.5),
            "up": _normal(ks[1], (n, h, f), h ** -0.5),
            "down": _normal(ks[2], (n, f, h), f ** -0.5)}


def expert_blocks(dims: dict):
    """The blocks an adapter stacks into the program's experts."""
    return range(dims["moe_num_primary_experts"] // EXPERT_BLOCK)


def head_block(key, dims: dict, block):
    """Columns ``block * vocab / VOCAB_BLOCKS ..`` of the head
    `(hidden, vocab)`: each block from its own key, so that the
    reference never holds the whole head in float32."""
    h, v = dims["hidden_size"], dims["vocab_size"]
    k = jax.random.fold_in(jax.random.fold_in(key, (1 << 20) + 2), block)
    return _normal(k, (h, v // VOCAB_BLOCKS), h ** -0.5)


def end_weights(key, dims: dict) -> dict:
    """Embedding `(vocab, hidden)`, final norm, head `(hidden, vocab)`
    (`head_block`s side by side)."""
    h, v = dims["hidden_size"], dims["vocab_size"]
    k = jax.random.split(jax.random.fold_in(key, 1 << 20), 2)
    return {"embed": _normal(k[0], (v, h), 1.0),
            "ln_f": _norm_weight(k[1], h),
            "lm_head": jnp.concatenate(
                [head_block(key, dims, b) for b in range(VOCAB_BLOCKS)],
                axis=1)}


# ---------------------------------------------------------------------------
# the float8 control
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


#: The weights a matmul reads in bfloat16 (the rest: norm weights, the
#: embedding table, which is looked up, and the float32 router).
MATMUL_WEIGHTS = ("q", "k", "v", "o", "gate", "up", "down", "lm_head")


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


# ---------------------------------------------------------------------------
# the forward pass
# ---------------------------------------------------------------------------

def _mm(x, w, fp8: bool):
    w = w.astype(jnp.float32)
    if fp8:
        x, w = _fp8(x), _fp8(w)
    return jnp.dot(x, w, precision="highest")


def rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def rope(x, pos, theta):
    """x (S, n, d): dimension i rotates with i + d/2."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]     # (S, d/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def attention(u, w, dm: dict, sliding: bool, fp8: bool = False):
    """u (S, hidden) -> (S, hidden): the layer's attention, a key head
    and `QUERY_BLOCK` query rows at a time."""
    s = u.shape[0]
    d, nq = dm["head_dim"], dm["num_attention_heads"]
    nkv = dm["num_key_value_heads"]
    rep = nq // nkv
    win = dm["sliding_window_size"]
    qb = _block(s, QUERY_BLOCK)
    pos = jnp.arange(s)
    k = _mm(u, w["k"], fp8).reshape(s, nkv, d)
    v = _mm(u, w["v"], fp8).reshape(s, nkv, d)
    if sliding:
        k = rope(k, pos, dm["rope_theta"])
        # keys a block of query rows can see: those of the block and
        # the ``win`` before it, at positions ``start - win + column``
        pad = ((win, 0), (0, 0), (0, 0))
        k, v = jnp.pad(k, pad), jnp.pad(v, pad)
    span = win + qb if sliding else s

    def one_group(g, out):
        cols = jax.lax.dynamic_slice_in_dim(w["q"], g * rep * d, rep * d, 1)
        q = _mm(u, cols, fp8).reshape(s, rep, d)
        if sliding:
            q = rope(q, pos, dm["rope_theta"])
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


def router_weights(h, w, dm: dict):
    """Dense (tokens, experts) float32 combine weights from the
    layer's INPUT h: zero off each token's chosen experts, the softmax
    of the chosen experts' logits on them."""
    r = jnp.dot(h, w["router"], precision="highest")
    picked, chosen = jax.lax.top_k(r, dm["moe_num_active_primary_experts"])
    p = jax.nn.softmax(picked, axis=-1)
    rows = jnp.arange(h.shape[0])[:, None]
    return jnp.zeros_like(r).at[rows, chosen].set(p)


@functools.partial(jax.jit, static_argnames=("dims",))
def _route(x, key, *, dims):
    """The layer's input -> its dense combine weights, `TOKEN_BLOCK`
    rows at a time."""
    dm = dict(dims)
    w = layer_weights(key, dm)
    tb = _block(x.shape[0], TOKEN_BLOCK)
    combine = jax.lax.map(lambda rows: router_weights(rows, w, dm),
                          x.reshape(-1, tb, x.shape[1]))
    return combine.reshape(x.shape[0], -1)


@functools.partial(jax.jit, static_argnames=("dims", "fp8", "sliding"),
                   donate_argnums=(0,))
def _add_attention(x, key, *, dims, fp8, sliding):
    dm = dict(dims)
    w = layer_weights(key, dm)
    u = rms_norm(x, w["ln1"], dm["rms_norm_eps"])
    return x + attention(u, w, dm, sliding, fp8)


@functools.partial(jax.jit, static_argnames=("dims", "fp8"),
                   donate_argnums=(0,))
def _add_experts(x, combine, key, *, dims, fp8):
    """x + sum over the experts of combine[:, e] * expert_e(RMS_2(x)):
    every expert over every token, a block of weights made and one
    expert computed at a time, `TOKEN_BLOCK` rows at a time.  ONE
    program a shape: the block is a loop's index, not a static
    argument."""
    dm = dict(dims)
    f32 = lambda t: t.astype(jnp.float32)    # noqa: E731
    m = rms_norm(x, layer_weights(key, dm)["ln2"], dm["rms_norm_eps"])
    tb = _block(m.shape[0], TOKEN_BLOCK)
    blocks = m.reshape(-1, tb, m.shape[1])
    if fp8:
        blocks = jax.lax.map(_fp8, blocks)

    def block(b, total):
        w = expert_weights(key, dm, b)
        if fp8:
            w = {k: _fp8(f32(t), batched=True) for k, t in w.items()}

        def one(total, e):
            def rows(args):
                rws, c = args
                act = (jax.nn.relu(jnp.dot(rws, f32(w["gate"][e]),
                                           precision="highest"))
                       * jnp.dot(rws, f32(w["up"][e]),
                                 precision="highest"))
                if fp8:
                    act = _fp8(act)
                return jnp.dot(act, f32(w["down"][e]),
                               precision="highest") * c[:, None]
            col = jax.lax.dynamic_index_in_dim(
                combine, b * EXPERT_BLOCK + e, 1, keepdims=False)
            y = jax.lax.map(rows, (blocks, col.reshape(-1, tb)))
            return total + y.reshape(total.shape), None

        return jax.lax.scan(one, total, jnp.arange(EXPERT_BLOCK))[0]

    return jax.lax.fori_loop(
        0, dm["moe_num_primary_experts"] // EXPERT_BLOCK, block, x)


def layer_forward(x, key, i: int, dims: dict, fp8: bool = False):
    """x (S, hidden) float32 through layer ``i`` (x is consumed)."""
    hd = _hashable(dims)
    combine = _route(x, key, dims=hd)         # BEFORE the attention
    x = _add_attention(x, key, dims=hd, fp8=fp8,
                       sliding=is_window(dims, i))
    return _add_experts(x, combine, key, dims=hd, fp8=fp8)


@functools.partial(jax.jit, static_argnames=("dims",))
def _embed(tokens, key, *, dims):
    h, v = dict(dims)["hidden_size"], dict(dims)["vocab_size"]
    k = jax.random.split(jax.random.fold_in(key, 1 << 20), 2)
    return _normal(k[0], (v, h), 1.0)[tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("dims", "fp8", "n_out"))
def _head(x, first, key, *, dims, fp8, n_out):
    """Final norm and head of rows ``first ..``, a block of the
    vocabulary at a time into one `(n_out, vocab)` array."""
    dm = dict(dims)
    width = dm["vocab_size"] // VOCAB_BLOCKS
    ln_f = _norm_weight(
        jax.random.split(jax.random.fold_in(key, 1 << 20), 2)[1],
        dm["hidden_size"])
    rows = jax.lax.dynamic_slice_in_dim(x, first, n_out, 0)
    rows = rms_norm(rows, ln_f, dm["rms_norm_eps"])
    if fp8:
        rows = _fp8(rows)

    def one(b, out):
        w = head_block(key, dm, b).astype(jnp.float32)
        part = jnp.dot(rows, _fp8(w) if fp8 else w, precision="highest")
        return jax.lax.dynamic_update_slice_in_dim(out, part, b * width,
                                                   1)

    return jax.lax.fori_loop(
        0, VOCAB_BLOCKS, one,
        jnp.zeros((n_out, dm["vocab_size"]), jnp.float32))


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
    x = _embed(tokens, key, dims=hd)
    for i in range(dims["num_hidden_layers"]):
        x = layer_forward(x, layer_key(key, i), i, dims, fp8)
    return _head(x, jnp.int32(first), key, dims=hd, fp8=fp8,
                 n_out=int(n_out))
