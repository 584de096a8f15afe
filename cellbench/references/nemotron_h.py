"""Plain reference for the Nemotron-H family (`model_type`
`nemotron_h`): weights from a seed and the forward pass, written from
the published `config.json` keys and, for the state-space layers, from
the equations of Mamba-2 (arXiv 2405.21060), whose keys they are.

Nothing of the program is imported here, and nothing the program made
is taken: the benchmark makes the weights (this file), hands them to
the program in the published layout through its adapter, and this file
computes the same model from the same seed in float32 with
`precision="highest"` — no kernels, no chunks, no cache, no batching:
softmax attention over the whole sequence, the state-space recurrence a
token at a time (`lax.scan`), every held expert over every token (one
at a time, `EXPERT_BLOCK` of them made at a time) — so that it fits
beside the program on the chip.

The equations (`u = RMSNorm(h; layer_norm_epsilon)` a layer's normed
input, a row a token; every layer `h <- h + Mixer_l(u)`, the mixer
named by character l of `hybrid_override_pattern`; no biases but the
convolution's; final RMSNorm, untied head):

- `M`, Mamba-2 (`mamba_num_heads` H x `mamba_head_dim` P inner
  channels, `n_groups` G, `ssm_state_size` N, `conv_kernel` taps):
  [z | xBC | dt] = u W_in; xBC_t <- silu(b_c + sum_j w_c[j] *
  xBC_{t-taps+1+j}), depthwise and causal; split x (H x P), B, C (G x
  N; head h uses group h // (H / G)); dt = softplus(dt + dt_bias) a
  head; a_t = exp(-exp(A_log) dt_t), one scalar a head; S_t = a_t
  S_{t-1} + dt_t x_t (outer) B_t, S_0 = 0, (P, N) a head, float32;
  y_t = S_t C_t + D x_t; y <- y * silu(z); RMSNorm inside each of the G
  groups of H P / G channels, times w_norm; out = y W_out.
- `*`, attention: q = u W_q (`num_attention_heads` x `head_dim`), k, v
  (`num_key_value_heads`); NO rotary embedding (the state-space layers
  carry order; the configuration's `assumed` says so), no qk-norm;
  causal softmax of q k^T head_dim^-0.5, a key head serving
  heads/kv_heads query heads; out = concat(heads) W_o.
- `E`, latent experts: s = sigmoid(u W_r) over ALL the layer's experts,
  float32; chosen = top-k of s + b (b the selection bias, for the
  choice only; `n_group` 1: no groups); w = s[chosen] / (sum + 1e-20) *
  `routed_scaling_factor` (`norm_topk_prob`); v = u W_dn (hidden x
  `moe_latent_size`); expert e: relu(v W1_e)**2 W2_e, W1 latent x
  `moe_intermediate_size`; routed = (sum over the chosen experts HELD
  HERE of w_e expert_e(v)) W_up; shared = relu(u Ws1)**2 Ws2, from the
  HIDDEN stream, `moe_shared_expert_intermediate_size` wide; out =
  routed + shared.  The router reads u, not v.

DEPARTURES FROM THE PUBLISHED MODEL, here as in the program:

- the multi-token-prediction block (`num_nextn_predict_layers` 1,
  `mtp_hybrid_override_pattern` `*E`) is left out: one token a step;
- THE SHARE (guide section 4; the configuration's `share` group): the
  router is `share.experts_of_layer` wide, this chip holds the experts
  `share.experts_held` = [lo, hi) (`n_routed_experts` = hi - lo) and
  the `vocab_size` rows of the vocabulary it was given.  What the
  experts elsewhere would have added is left out and that partial
  result goes on to the next layer.  `held` can be given to `dims_of`
  to compute another chip's share, or the whole layer, of the same
  weights (expert e's weights depend on e alone).

Weights are bfloat16 values, the type they are served in (the
router's, its bias, A_log, dt_bias and D are float32): projections
normal with standard deviation fan_in ** -0.5, the convolution's taps
normal 0.5 and its bias normal 0.1, the embedding normal(0, 1), norm
weights 1 + 0.1 * normal, the selection bias 0.05 * normal, A = log
U(1, 16) and dt_bias the inverse softplus of U(0.001, 0.1) a head (the
published initialisation), D = 1 (published) + 0.1 * normal, so that a
head's skip is told from its neighbour's.

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
DIM_KEYS = ("hidden_size", "num_hidden_layers", "hybrid_override_pattern",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "mamba_num_heads", "mamba_head_dim", "n_groups",
            "ssm_state_size", "conv_kernel", "moe_intermediate_size",
            "moe_latent_size", "moe_shared_expert_intermediate_size",
            "n_routed_experts", "num_experts_per_tok",
            "routed_scaling_factor", "norm_topk_prob", "vocab_size",
            "layer_norm_epsilon")

#: Experts made and computed at a time.
EXPERT_BLOCK = 8
SSM, ATTN, MOE = "M", "*", "E"


def dims_of(config: dict, held=None) -> dict:
    """The sizes the mathematics needs, by their published names, and
    the share: `experts_of_layer` (the router's width) and `held` (lo,
    hi).  ``held``: another share of the same layer (tests)."""
    d = {k: config[k] for k in DIM_KEYS}
    assert len(d["hybrid_override_pattern"]) == d["num_hidden_layers"]
    assert set(d["hybrid_override_pattern"]) <= {SSM, ATTN, MOE}
    assert config["mlp_hidden_act"] == "relu2", config["mlp_hidden_act"]
    assert config["n_group"] == 1 == config["topk_group"]
    assert config["n_shared_experts"] == 1 and config["use_conv_bias"]
    assert not any(config[k] for k in ("use_bias", "mamba_proj_bias",
                                       "attention_bias", "mlp_bias"))
    assert d["mamba_num_heads"] % d["n_groups"] == 0
    d["experts_of_layer"] = config["share"]["experts_of_layer"]
    lo, hi = held or config["share"]["experts_held"]
    assert hi - lo == d["n_routed_experts"] or held is not None, (lo, hi)
    assert lo % EXPERT_BLOCK == 0 == hi % EXPERT_BLOCK, (lo, hi)
    d["held"] = (int(lo), int(hi))
    return d


def _hashable(dims: dict):
    return tuple(sorted(dims.items()))


def kind_of(dims: dict, i: int) -> str:
    return dims["hybrid_override_pattern"][i]


def inner_of(dims: dict) -> int:
    """Channels of a state-space layer's x, z and y."""
    return dims["mamba_num_heads"] * dims["mamba_head_dim"]


def conv_width_of(dims: dict) -> int:
    """Channels the convolution runs over: x, B and C."""
    return inner_of(dims) + 2 * dims["n_groups"] * dims["ssm_state_size"]


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


def layer_weights(key, dims: dict, kind: str) -> dict:
    """One layer in the published layout, `(in, out)` oriented: its
    norm and its ONE mixer — less, for an `E` layer, its routed
    experts (`expert_weights`)."""
    h = dims["hidden_size"]
    ks = jax.random.split(key, 12)
    w = {"ln": _norm_weight(ks[0], h)}
    if kind == SSM:
        n, c, cw = (dims["mamba_num_heads"], inner_of(dims),
                    conv_width_of(dims))
        taps = dims["conv_kernel"]
        dt = jax.random.uniform(ks[5], (n,), jnp.float32, 0.001, 0.1)
        w.update(
            w_in=_normal(ks[1], (h, c + cw + n), h ** -0.5),
            conv=_normal(ks[2], (taps, cw), 0.5),
            conv_bias=_normal(ks[3], (cw,), 0.1),
            a_log=jnp.log(jax.random.uniform(ks[4], (n,), jnp.float32,
                                             1.0, 16.0)),
            dt_bias=jnp.log(jnp.expm1(dt)),
            d=1.0 + 0.1 * jax.random.normal(ks[6], (n,), jnp.float32),
            norm=_norm_weight(ks[7], c),
            w_out=_normal(ks[8], (c, h), c ** -0.5))
    elif kind == ATTN:
        d = dims["head_dim"]
        nq, nkv = dims["num_attention_heads"], dims["num_key_value_heads"]
        w.update(q=_normal(ks[1], (h, nq * d), h ** -0.5),
                 k=_normal(ks[2], (h, nkv * d), h ** -0.5),
                 v=_normal(ks[3], (h, nkv * d), h ** -0.5),
                 o=_normal(ks[4], (nq * d, h), (nq * d) ** -0.5))
    else:
        e, lat = dims["experts_of_layer"], dims["moe_latent_size"]
        fs = dims["moe_shared_expert_intermediate_size"]
        w.update(
            router=_normal(ks[1], (h, e), h ** -0.5).astype(jnp.float32),
            e_bias=_normal(ks[2], (e,), 0.05).astype(jnp.float32),
            latent_down=_normal(ks[3], (h, lat), h ** -0.5),
            latent_up=_normal(ks[4], (lat, h), lat ** -0.5),
            shared_up=_normal(ks[5], (h, fs), h ** -0.5),
            shared_down=_normal(ks[6], (fs, h), fs ** -0.5))
    return w


def expert_weights(key, dims: dict, block: int) -> dict:
    """Routed experts ``block * EXPERT_BLOCK ..`` OF THE LAYER (their
    published numbers, whichever chip holds them): up `(EXPERT_BLOCK,
    latent, f)`, down `(EXPERT_BLOCK, f, latent)`."""
    lat, f = dims["moe_latent_size"], dims["moe_intermediate_size"]
    ks = jax.random.split(jax.random.fold_in(key, 1000 + block), 2)
    n = EXPERT_BLOCK
    return {"up": _normal(ks[0], (n, lat, f), lat ** -0.5),
            "down": _normal(ks[1], (n, f, lat), f ** -0.5)}


def held_blocks(dims: dict):
    """The blocks an adapter stacks into the program's held experts."""
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
#: convolution's taps and bias, A, dt_bias, D, the embedding table,
#: which is looked up, and the float32 router).
MATMUL_WEIGHTS = ("w_in", "w_out", "q", "k", "v", "o", "latent_down",
                  "latent_up", "up", "down", "shared_up", "shared_down",
                  "lm_head")


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
    w = layer_weights(key, dict(dims), ATTN)["o"]
    a = w.astype(jnp.float32)
    b = fp8_rounded({"o": w})["o"].astype(jnp.float32)
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


def _rms(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def _relu2(x, up, down, fp8):
    return _mm(jnp.square(jax.nn.relu(_mm(x, up, fp8))), down, fp8)


def mamba_features(u, w, dm: dict, fp8: bool = False):
    """z (S, H P), x (S, H, P), B, C (S, G, N), dt (S, H) of the
    recurrence: the in-projection, the convolution, the step size."""
    s = u.shape[0]
    n, p = dm["mamba_num_heads"], dm["mamba_head_dim"]
    g, ns, taps = dm["n_groups"], dm["ssm_state_size"], dm["conv_kernel"]
    c = n * p
    z, xbc, dt = jnp.split(_mm(u, w["w_in"], fp8),
                           [c, c + conv_width_of(dm)], axis=1)
    padded = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))
    taps_w = w["conv"].astype(jnp.float32)
    xbc = jax.nn.silu(w["conv_bias"].astype(jnp.float32) + sum(
        padded[i:i + s] * taps_w[i] for i in range(taps)))
    x, b, cc = jnp.split(xbc, [c, c + g * ns], axis=1)
    return (z, x.reshape(s, n, p), b.reshape(s, g, ns),
            cc.reshape(s, g, ns), jax.nn.softplus(dt + w["dt_bias"]))


def state_space(x, b, c, dt, a_log):
    """The recurrence a token at a time from S_0 = 0: x (S, H, P), b,
    c (S, G, N), dt (S, H) -> y (S, H, P), without the skip."""
    n, p = x.shape[1:]
    rep = n // b.shape[1]
    rate = -jnp.exp(a_log)

    def step(state, v):
        x_t, b_t, c_t, dt_t = v
        b_t, c_t = jnp.repeat(b_t, rep, axis=0), jnp.repeat(c_t, rep,
                                                            axis=0)
        state = (state * jnp.exp(rate * dt_t)[:, None, None]
                 + (dt_t[:, None] * x_t)[..., None] * b_t[:, None, :])
        return state, jnp.einsum("hpn,hn->hp", state, c_t,
                                 precision="highest")

    _, y = jax.lax.scan(step, jnp.zeros((n, p, b.shape[-1]), jnp.float32),
                        (x, b, c, dt))
    return y


def mamba_mixer(u, w, dm: dict, fp8: bool = False):
    """u (S, hidden) -> (S, hidden): a Mamba-2 layer."""
    s = u.shape[0]
    z, x, b, c, dt = mamba_features(u, w, dm, fp8)
    y = state_space(x, b, c, dt, w["a_log"]) + w["d"][:, None] * x
    y = y.reshape(s, -1) * jax.nn.silu(z)
    y = y.reshape(s, dm["n_groups"], -1)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True)
                          + dm["layer_norm_epsilon"])
    return _mm(y.reshape(s, -1) * w["norm"].astype(jnp.float32),
               w["w_out"], fp8)


def attention_mixer(u, w, dm: dict, fp8: bool = False):
    """u (S, hidden) -> (S, hidden): softmax attention, no positions."""
    s = u.shape[0]
    d, nq = dm["head_dim"], dm["num_attention_heads"]
    nkv = dm["num_key_value_heads"]
    q = _mm(u, w["q"], fp8).reshape(s, nq, d)
    k = _mm(u, w["k"], fp8).reshape(s, nkv, d)
    v = _mm(u, w["v"], fp8).reshape(s, nkv, d)
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
    return _mm(att.transpose(1, 0, 2).reshape(s, nq * d), w["o"], fp8)


def router_weights(u, w, dm: dict):
    """Dense (tokens, experts of the layer) float32 combine weights:
    zero off each token's chosen experts."""
    s = jax.nn.sigmoid(jnp.dot(u, w["router"], precision="highest"))
    _, chosen = jax.lax.top_k(s + w["e_bias"], dm["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, chosen, axis=1)
    if dm["norm_topk_prob"]:
        picked = picked / (picked.sum(axis=-1, keepdims=True) + 1e-20)
    picked = picked * dm["routed_scaling_factor"]
    rows = jnp.arange(u.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, chosen].set(picked)


@functools.partial(jax.jit, static_argnames=("dims", "fp8", "kind"))
def _dense_layer(x, key, *, dims, fp8, kind):
    """A layer that is not an expert layer."""
    dm = dict(dims)
    w = layer_weights(key, dm, kind)
    u = _rms(x, w["ln"], dm["layer_norm_epsilon"])
    mixer = mamba_mixer if kind == SSM else attention_mixer
    return x + mixer(u, w, dm, fp8)


@functools.partial(jax.jit, static_argnames=("dims", "fp8"))
def _moe_ends(x, key, *, dims, fp8):
    """x -> (the latent input of the experts, the dense combine
    weights, the shared expert's output)."""
    dm = dict(dims)
    w = layer_weights(key, dm, MOE)
    u = _rms(x, w["ln"], dm["layer_norm_epsilon"])
    shared = _relu2(u, w["shared_up"], w["shared_down"], fp8)
    return _mm(u, w["latent_down"], fp8), router_weights(u, w, dm), shared


@functools.partial(jax.jit, static_argnames=("dims", "fp8"))
def _held_experts(v, combine, key, *, dims, fp8):
    """(sum over the experts held of combine[:, e] * expert_e(v)) W_up:
    every held expert over every token, in the latent, a block of
    weights made and one expert computed at a time.  ONE program a
    shape: the block is a loop's index, not a static argument (sixteen
    programs a shape take minutes to compile on the chip)."""
    dm = dict(dims)
    lo, hi = dm["held"]
    f32 = lambda t: t.astype(jnp.float32)    # noqa: E731
    if fp8:
        v = _fp8(v)

    def block(b, total):
        w = expert_weights(key, dm, b)
        if fp8:
            w = {k: _fp8(f32(t), batched=True) for k, t in w.items()}

        def one(total, e):
            act = jnp.square(jax.nn.relu(jnp.dot(v, f32(w["up"][e]),
                                                 precision="highest")))
            if fp8:
                act = _fp8(act)
            y = jnp.dot(act, f32(w["down"][e]), precision="highest")
            return total + y * combine[:, b * EXPERT_BLOCK + e][:, None], None

        return jax.lax.scan(one, total, jnp.arange(EXPERT_BLOCK))[0]

    y = jax.lax.fori_loop(lo // EXPERT_BLOCK, hi // EXPERT_BLOCK, block,
                          jnp.zeros_like(v))
    return _mm(y, layer_weights(key, dm, MOE)["latent_up"], fp8)


def routed_part(v, combine, key, dims: dict, fp8: bool = False):
    """What the experts `dims["held"]` add for each token, in the
    hidden stream: their weighted sum in the latent through W_up."""
    return _held_experts(v, combine, key, dims=_hashable(dims), fp8=fp8)


def moe_parts(x, key, dims: dict, fp8: bool = False):
    """(routed, shared) of an `E` layer for its input x (S, hidden)."""
    v, combine, shared = _moe_ends(x, key, dims=_hashable(dims), fp8=fp8)
    return routed_part(v, combine, key, dims, fp8), shared


def layer_forward(x, key, i: int, dims: dict, fp8: bool = False):
    """x (S, hidden) float32 through layer ``i``."""
    kind = kind_of(dims, i)
    if kind != MOE:
        return _dense_layer(x, key, dims=_hashable(dims), fp8=fp8,
                            kind=kind)
    routed, shared = moe_parts(x, key, dims, fp8)
    return x + routed + shared


@functools.partial(jax.jit, static_argnames=("dims",))
def _embed(tokens, key, *, dims):
    return end_weights(key, dict(dims))["embed"][tokens].astype(
        jnp.float32)


@functools.partial(jax.jit, static_argnames=("dims", "fp8", "n_out"))
def _head(x, first, key, *, dims, fp8, n_out):
    dm = dict(dims)
    w = end_weights(key, dm)
    rows = jax.lax.dynamic_slice_in_dim(x, first, n_out, 0)
    rows = _rms(rows, w["ln_f"], dm["layer_norm_epsilon"])
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
