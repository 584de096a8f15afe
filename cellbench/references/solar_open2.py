"""Plain reference for the Solar-Open2 family (`model_type`
`solar_open2`): weights from a seed and the forward pass, written from
the published `config.json` keys and, for the delta-rule layers, from
the equations of Kimi Linear (arXiv 2510.26692), whose keys they are.

Nothing of the program is imported here, and nothing the program made
is taken: the benchmark makes the weights (this file), hands them to
the program in the published layout through its adapter, and this file
computes the same model from the same seed in float32 with
`precision="highest"` — no kernels, no cache, no batching: softmax
attention over the whole sequence, the delta rule a token at a time
(`lax.scan`, `KDA_HEAD_GROUP` heads at a time), every held expert over
every token (one at a time, `EXPERT_BLOCK` of them made at a time) — so
that it fits beside the program on the chip.

The equations (`x` a layer's normed input, a row a token; no biases):

- block l: h <- h + Mixer_l(RMSNorm(h)); h <- h + MoE_l(RMSNorm(h));
  final RMSNorm, untied head.  RMSNorm eps `rms_norm_eps`.
- Mixer, l in `gqa_layers`: q = x W_q (`num_attention_heads` x
  `head_dim`), k = x W_k, v = x W_v (`num_key_value_heads`); NO rotary
  embedding (`use_rope` false), no qk-norm; causal softmax of q k^T
  head_dim^-0.5, a key head serving heads/kv_heads query heads;
  y = (sigmoid(x W_g) * concat(heads)) W_o (`use_gqa_gate`).
- Mixer, every other layer (`linear_attn_config`: H heads of d,
  `short_conv_kernel_size` taps): [q|k|v] = SiLU(conv(x W_q|k|v)), the
  convolution causal and depthwise; q <- unit(q) d^-0.5, k <- unit(k),
  unit(a) = a / sqrt(sum a^2 + 1e-6) a head;
  g = -exp(A_h) softplus((x W_a1) W_a2 + b_dt), a number a channel
  (`kda_use_full_proj` false: rank `head_dim`);
  b = 2 sigmoid(x W_b) a head (`kda_allow_neg_eigval`; else no 2);
  S' = Diag(exp g_t) S_{t-1}; u = b_t (v_t - S'^T k_t); S_t = S' + k_t
  u^T; o_t = S_t^T q_t, S_0 = 0, float32;
  y = (RMSNorm_head(o) * sigmoid((x W_g1) W_g2)) W_o.
- MoE: s = sigmoid(x W_r) over ALL the layer's experts; chosen = top-k
  of s + b (b the selection bias, for the choice only); w = s[chosen]
  / (sum s[chosen] + 1e-20) * `routed_scaling_factor`
  (`norm_topk_prob`); y = sum over the chosen experts HELD HERE of
  w_i SwiGLU_i(x) + SwiGLU_shared(x), the shared expert of width
  `moe_intermediate_size` * `n_shared_experts`.

THE SHARE (guide section 4; the configuration's `share` group): the
router is `share.experts_of_layer` wide, this chip holds the experts
`share.experts_held` = [lo, hi) (`n_routed_experts` = hi - lo) and the
`vocab_size` rows of the vocabulary it was given.  What the experts
elsewhere would have added is left out, here as in the program, and
that partial result goes on to the next layer.  `held` can be given to
`dims_of` to compute another chip's share, or the whole layer, of the
same weights (expert e's weights depend on e alone).

Weights are bfloat16 values, the type they are served in (the router's,
its bias, A and b_dt are float32): projections normal with standard
deviation fan_in ** -0.5, the convolution's taps normal 0.5, the
embedding normal(0, 1), norm weights 1 + 0.1 * normal, the selection
bias 0.05 * normal, A = log U(1, 16) a head, b_dt the inverse softplus
of U(0.001, 0.1) a channel (the published initialisation).

`precision="fp8"` is the CONTROL, never the reference: every matmul's
weights and input activations rounded to float8_e4m3 (float32
accumulation).  The router, the convolution and the state's recurrence
stay float32 there, as a float8 deployment would keep them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

#: Published layout names (HF `config.json` keys) this family reads.
DIM_KEYS = ("hidden_size", "moe_intermediate_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "n_routed_experts", "n_shared_experts",
            "num_experts_per_tok", "routed_scaling_factor",
            "norm_topk_prob", "vocab_size", "rms_norm_eps",
            "use_gqa_gate", "kda_allow_neg_eigval")

#: Experts made and computed at a time.
EXPERT_BLOCK = 8
_L2_EPS = 1e-6


def dims_of(config: dict, held=None) -> dict:
    """The sizes the mathematics needs, by their published names, and
    the share: `experts_of_layer` (the router's width) and `held` (lo,
    hi).  ``held``: another share of the same layer (tests)."""
    d = {k: config[k] for k in DIM_KEYS}
    lin = config["linear_attn_config"]
    assert lin["num_kv_heads"] in (None, lin["num_heads"]), lin
    assert not config["use_rope"] and not config["kda_use_full_proj"]
    assert config["first_k_dense_replace"] == 0
    d.update(kda_heads=lin["num_heads"], kda_head_dim=lin["head_dim"],
             conv=lin["short_conv_kernel_size"],
             gqa_layers=tuple(i for i in config["gqa_layers"]
                              if i < d["num_hidden_layers"]),
             experts_of_layer=config["share"]["experts_of_layer"])
    lo, hi = held or config["share"]["experts_held"]
    assert hi - lo == d["n_routed_experts"] or held is not None, (lo, hi)
    assert lo % EXPERT_BLOCK == 0 == hi % EXPERT_BLOCK, (lo, hi)
    d["held"] = (int(lo), int(hi))
    return d


def _hashable(dims: dict):
    return tuple(sorted(dims.items()))


def is_gqa(dims: dict, i: int) -> bool:
    return i in dims["gqa_layers"]


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


def layer_weights(key, dims: dict, gqa: bool) -> dict:
    """One layer in the published layout, `(in, out)` oriented, less
    its routed experts (`expert_weights`): the mixer, the two layer
    norms, the router (over all the layer's experts), its selection
    bias and the shared expert."""
    h = dims["hidden_size"]
    ks = jax.random.split(key, 24)
    e = dims["experts_of_layer"]
    fs = dims["moe_intermediate_size"] * dims["n_shared_experts"]
    w = {
        "ln1": _norm_weight(ks[0], h), "ln2": _norm_weight(ks[1], h),
        "router": _normal(ks[2], (h, e), h ** -0.5).astype(jnp.float32),
        "e_bias": _normal(ks[3], (e,), 0.05).astype(jnp.float32),
        "shared_gate": _normal(ks[4], (h, fs), h ** -0.5),
        "shared_up": _normal(ks[5], (h, fs), h ** -0.5),
        "shared_down": _normal(ks[6], (fs, h), fs ** -0.5),
    }
    if gqa:
        d = dims["head_dim"]
        nq, nkv = dims["num_attention_heads"], dims["num_key_value_heads"]
        w.update(q=_normal(ks[7], (h, nq * d), h ** -0.5),
                 k=_normal(ks[8], (h, nkv * d), h ** -0.5),
                 v=_normal(ks[9], (h, nkv * d), h ** -0.5),
                 o=_normal(ks[10], (nq * d, h), (nq * d) ** -0.5))
        if dims["use_gqa_gate"]:
            w["g"] = _normal(ks[11], (h, nq * d), h ** -0.5)
        return w
    d, taps = dims["kda_head_dim"], dims["conv"]
    c = dims["kda_heads"] * d
    dt = jax.random.uniform(ks[20], (c,), jnp.float32, 0.001, 0.1)
    w.update(
        q=_normal(ks[7], (h, c), h ** -0.5),
        k=_normal(ks[8], (h, c), h ** -0.5),
        v=_normal(ks[9], (h, c), h ** -0.5),
        o=_normal(ks[10], (c, h), c ** -0.5),
        conv_q=_normal(ks[11], (taps, c), 0.5),
        conv_k=_normal(ks[12], (taps, c), 0.5),
        conv_v=_normal(ks[13], (taps, c), 0.5),
        a_down=_normal(ks[14], (h, d), h ** -0.5),
        a_up=_normal(ks[15], (d, c), d ** -0.5),
        g_down=_normal(ks[16], (h, d), h ** -0.5),
        g_up=_normal(ks[17], (d, c), d ** -0.5),
        beta=_normal(ks[18], (h, dims["kda_heads"]), h ** -0.5),
        a_log=jnp.log(jax.random.uniform(
            ks[19], (dims["kda_heads"],), jnp.float32, 1.0, 16.0)),
        dt_bias=jnp.log(jnp.expm1(dt)),
        o_norm=_norm_weight(ks[21], d))
    return w


def expert_weights(key, dims: dict, block: int) -> dict:
    """Routed experts ``block * EXPERT_BLOCK ..`` OF THE LAYER (their
    published numbers, whichever chip holds them): gate and up
    `(EXPERT_BLOCK, h, f)`, down `(EXPERT_BLOCK, f, h)`."""
    h, f = dims["hidden_size"], dims["moe_intermediate_size"]
    ks = jax.random.split(jax.random.fold_in(key, 1000 + block), 3)
    n = EXPERT_BLOCK
    return {"gate": _normal(ks[0], (n, h, f), h ** -0.5),
            "up": _normal(ks[1], (n, h, f), h ** -0.5),
            "down": _normal(ks[2], (n, f, h), f ** -0.5)}


def held_blocks(dims: dict):
    lo, hi = dims["held"]
    return range(lo // EXPERT_BLOCK, hi // EXPERT_BLOCK)


def end_weights(key, dims: dict) -> dict:
    """Embedding `(vocab, hidden)`, final norm, head `(hidden, vocab)`,
    over the rows of the vocabulary held here."""
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
    experts)."""
    axes = tuple(range(1, x.ndim)) if batched else None
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axes, keepdims=True),
                    1e-30) / 240.0
    return jax.lax.reduce_precision(x / s, exponent_bits=4,
                                    mantissa_bits=3) * s


#: The weights a matmul reads in bfloat16 (the rest: norm weights, the
#: convolution's taps, A, b_dt, the embedding table, which is looked
#: up, and the float32 router).
MATMUL_WEIGHTS = ("q", "k", "v", "g", "o", "a_down", "a_up", "g_down",
                  "g_up", "beta", "gate", "up", "down", "shared_gate",
                  "shared_up", "shared_down", "lm_head")


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
    w = layer_weights(key, dict(dims), True)["o"]
    a = w.astype(jnp.float32)
    b = fp8_rounded({"o": w})["o"].astype(jnp.float32)
    return jnp.mean(jnp.abs(b - a)) / jnp.mean(jnp.abs(a))


def fp8_change(dims: dict, seed: int) -> float:
    """CONTROL only: the mean change `fp8_rounded` makes to one
    projection of the first layer, as a share of its mean magnitude."""
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


def _swiglu(x, gate, up, down, fp8):
    return _mm(jax.nn.silu(_mm(x, gate, fp8)) * _mm(x, up, fp8), down,
               fp8)


def router_weights(hdn, w, dm: dict):
    """Dense (tokens, experts of the layer) float32 combine weights:
    zero off each token's chosen experts."""
    s = jax.nn.sigmoid(jnp.dot(hdn, w["router"], precision="highest"))
    _, chosen = jax.lax.top_k(s + w["e_bias"], dm["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, chosen, axis=1)
    if dm["norm_topk_prob"]:
        picked = picked / (picked.sum(axis=-1, keepdims=True) + 1e-20)
    picked = picked * dm["routed_scaling_factor"]
    rows = jnp.arange(hdn.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, chosen].set(picked)


def gqa_mixer(hdn, w, dm: dict, fp8: bool = False):
    """hdn (S, hidden) -> (S, hidden): gated softmax attention, no
    positions."""
    s = hdn.shape[0]
    d, nq = dm["head_dim"], dm["num_attention_heads"]
    nkv = dm["num_key_value_heads"]
    q = _mm(hdn, w["q"], fp8).reshape(s, nq, d)
    k = _mm(hdn, w["k"], fp8).reshape(s, nkv, d)
    v = _mm(hdn, w["v"], fp8).reshape(s, nkv, d)
    pos = jnp.arange(s)
    causal = pos[:, None] >= pos[None, :]

    def one_head(args):
        qh, kh, vh = args
        sc = jnp.dot(qh, kh.T, precision="highest") * d ** -0.5
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        return jnp.dot(p, vh, precision="highest")

    heads = lambda t: t.transpose(1, 0, 2)      # noqa: E731
    rep = nq // nkv
    att = jax.lax.map(one_head, (
        heads(q), jnp.repeat(heads(k), rep, axis=0),
        jnp.repeat(heads(v), rep, axis=0)))                 # (H, S, d)
    att = att.transpose(1, 0, 2).reshape(s, nq * d)
    if dm["use_gqa_gate"]:
        att = att * jax.nn.sigmoid(_mm(hdn, w["g"], fp8))
    return _mm(att, w["o"], fp8)


def kda_features(hdn, w, dm: dict, fp8: bool = False, heads=None):
    """q, k, v, g (S, n, d) and beta (S, n) of the delta rule for the
    heads ``heads = (lo, hi)`` (all of them by default): a head's
    features read its own columns of the projections alone."""
    s = hdn.shape[0]
    d, taps = dm["kda_head_dim"], dm["conv"]
    lo, hi = heads or (0, dm["kda_heads"])
    n, cols = hi - lo, slice(lo * d, hi * d)

    def conved(name):
        x = jnp.pad(_mm(hdn, w[name][:, cols], fp8),
                    ((taps - 1, 0), (0, 0)))
        c = w["conv_" + name][:, cols].astype(jnp.float32)
        y = sum(x[i:i + s] * c[i] for i in range(taps))
        return jax.nn.silu(y).reshape(s, n, d)

    def unit(a):
        return a * jax.lax.rsqrt(
            jnp.sum(a * a, axis=-1, keepdims=True) + _L2_EPS)

    g = -jnp.exp(w["a_log"][lo:hi])[None, :, None] * jax.nn.softplus(
        _mm(_mm(hdn, w["a_down"], fp8), w["a_up"][:, cols], fp8)
        + w["dt_bias"][cols]).reshape(s, n, d)
    beta = jax.nn.sigmoid(_mm(hdn, w["beta"][:, lo:hi], fp8))
    if dm["kda_allow_neg_eigval"]:
        beta = 2.0 * beta
    return (unit(conved("q")) * d ** -0.5, unit(conved("k")),
            conved("v"), g, beta)


def delta_rule(q, k, v, g, beta):
    """The recurrence a token at a time from S_0 = 0: (S, n, d) each,
    beta (S, n) -> o (S, n, d)."""
    def step(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = state * jnp.exp(g_t)[..., None]
        u = b_t[:, None] * (v_t - jnp.einsum(
            "hkv,hk->hv", state, k_t, precision="highest"))
        state = state + k_t[..., None] * u[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t,
                                 precision="highest")

    nh, d = q.shape[1:]
    _, o = jax.lax.scan(step, jnp.zeros((nh, d, d), jnp.float32),
                        (q, k, v, g, beta))
    return o


def kda_mixer(hdn, w, dm: dict, fp8: bool = False, heads=None):
    """hdn (S, hidden) -> (S, hidden): Kimi Delta Attention — what the
    heads ``heads = (lo, hi)`` add through their rows of W_o (all the
    heads by default; the groups' parts sum to the layer's)."""
    d = dm["kda_head_dim"]
    lo, hi = heads or (0, dm["kda_heads"])
    cols = slice(lo * d, hi * d)
    o = delta_rule(*kda_features(hdn, w, dm, fp8, (lo, hi)))
    o = _rms(o, w["o_norm"], dm["rms_norm_eps"])
    gate = jax.nn.sigmoid(
        _mm(_mm(hdn, w["g_down"], fp8), w["g_up"][:, cols], fp8))
    return _mm(o.reshape(hdn.shape[0], -1) * gate, w["o"][cols], fp8)


#: Delta-rule heads computed at a time: a sequence of 5120 tokens then
#: holds its per-token features 8 heads wide, not 64 (it has to fit
#: beside the program on the chip).
KDA_HEAD_GROUP = 8


@functools.partial(jax.jit, static_argnames=("dims", "fp8", "heads"))
def _kda_group(x, key, *, dims, fp8, heads):
    dm = dict(dims)
    w = layer_weights(key, dm, False)
    return kda_mixer(_rms(x, w["ln1"], dm["rms_norm_eps"]), w, dm, fp8,
                     heads)


@functools.partial(jax.jit, static_argnames=("dims", "fp8"))
def _gqa_layer(x, key, *, dims, fp8):
    dm = dict(dims)
    w = layer_weights(key, dm, True)
    return x + gqa_mixer(_rms(x, w["ln1"], dm["rms_norm_eps"]), w, dm,
                         fp8)


@functools.partial(jax.jit, static_argnames=("dims", "fp8", "gqa"))
def _shared_and_router(x, key, *, dims, fp8, gqa):
    """x after the mixer -> (the experts' normed input, the dense
    combine weights, the shared expert's output)."""
    dm = dict(dims)
    w = layer_weights(key, dm, gqa)
    hdn = _rms(x, w["ln2"], dm["rms_norm_eps"])
    shared = _swiglu(hdn, w["shared_gate"], w["shared_up"],
                     w["shared_down"], fp8)
    return hdn, router_weights(hdn, w, dm), shared


@functools.partial(jax.jit, static_argnames=("dims", "fp8", "block"))
def _expert_block(hdn, combine, key, *, dims, fp8, block):
    """sum over this block's experts of combine[:, e] * SwiGLU_e(hdn):
    every expert over every token."""
    w = expert_weights(key, dict(dims), block)
    if fp8:
        hdn = _fp8(hdn)
        w = {k: _fp8(v.astype(jnp.float32), batched=True)
             for k, v in w.items()}
    lo = block * EXPERT_BLOCK

    def one(args):
        gate, up, down, weight = args
        f32 = lambda t: t.astype(jnp.float32)    # noqa: E731
        act = (jax.nn.silu(jnp.dot(hdn, f32(gate), precision="highest"))
               * jnp.dot(hdn, f32(up), precision="highest"))
        if fp8:
            act = _fp8(act)
        return jnp.dot(act, f32(down),
                       precision="highest") * weight[:, None]

    return jax.lax.map(one, (
        w["gate"], w["up"], w["down"],
        combine[:, lo:lo + EXPERT_BLOCK].T)).sum(axis=0)


def routed_part(hdn, combine, key, dims: dict, fp8: bool = False):
    """What the experts `dims["held"]` add for each token."""
    hd = _hashable(dims)
    y = jnp.zeros_like(hdn)
    for b in held_blocks(dims):
        y = y + _expert_block(hdn, combine, key, dims=hd, fp8=fp8,
                              block=b)
    return y


def layer_forward(x, key, i: int, dims: dict, fp8: bool = False):
    """x (S, hidden) float32 through layer ``i``."""
    hd, gqa = _hashable(dims), is_gqa(dims, i)
    if gqa:
        x = _gqa_layer(x, key, dims=hd, fp8=fp8)
    else:
        n, step = dims["kda_heads"], min(KDA_HEAD_GROUP, dims["kda_heads"])
        x = x + sum(_kda_group(x, key, dims=hd, fp8=fp8,
                               heads=(lo, lo + step))
                    for lo in range(0, n, step))
    hdn, combine, shared = _shared_and_router(x, key, dims=hd, fp8=fp8,
                                              gqa=gqa)
    return x + shared + routed_part(hdn, combine, key, dims, fp8)


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
    already padded by the caller to the length it wants compiled; every
    mixer is causal, so padding on the right reaches no position read,
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
