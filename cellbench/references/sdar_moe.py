"""Plain reference for the SDAR-MoE family (`model_type` `sdar_moe`):
weights from a seed, the forward pass under the block-causal mask, and
generation by diffusion over blocks as the `sequential` schedule leaves
it — written from the published `config.json` keys and the
configuration's `generation` / `assumed` sizes.

Nothing of the program is imported here, and nothing the program made
is taken: the benchmark makes the weights (this file), hands them to
the program in the published layout through its adapter, and this file
computes the same model from the same seed in float32 with
`precision="highest"` — no kernels, no cache, no batching, a few heads
and a block of `EXPERT_BLOCK` experts at a time (every expert over
every token) so that it fits beside the program on the chip.

The equations (every size from `config.json`; no biases, untied head,
`rope_scaling` null, `decoder_sparse_step` 1 and `mlp_only_layers` []:
every layer is sparse; `intermediate_size` is published and unused):

- block l: x <- x + Attn(RMSNorm(x)); x <- x + MoE(RMSNorm(x)); final
  RMSNorm, head.
- Attn: q = x W_q (H heads of `head_dim`), k, v = x W_k, x W_v
  (`num_key_value_heads`); RMSNorm over each head of q and of k; RoPE
  on the whole head; softmax(q k^T / sqrt(d) + M) v with H / Hkv query
  heads a KV head; W_o.  **M is block-causal**: with block length B
  position i sees j iff j // B <= i // B.
- MoE: p = softmax(x W_r) over all experts (float32); chosen = top-k;
  w = p[chosen] / sum p[chosen] (`norm_topk_prob`); y = sum_i w_i
  SwiGLU_i(x), width `moe_intermediate_size`.  No shared expert, no
  bias, no token dropped.
- Generation: position p's logits predict the token AT p (no shift).
  A block starts as B mask tokens (`mask_token_id`) — the first block
  of a request holds the prompt's tail, revealed — and each denoise
  pass reveals the B / T leftmost masked positions with their arg-max
  (`denoising_steps` T); a revealed token is never masked again.

`forward` is the definition: one block-causal pass over a sequence AS
IT STANDS (mask ids where a position is not revealed).  `logits_at`
gives, for every served position, the logits of the state in which the
program revealed it, and computes them the way the family is trained:
one pass over the CLEAN sequence (whose K/V are what a committed block
leaves), then for each denoise pass g one pass over a NOISED copy —
every block with the positions of passes < g revealed and the rest
masked — whose block n attends the clean blocks < n and itself.  A
tier-1 test holds the two to each other.

Weights are bfloat16 values, the type they are served in (the router's
float32 values of bfloat16 draws); projections normal with standard
deviation fan_in ** -0.5, the embedding normal(0, 1), norm weights
1 + 0.1 * normal.

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
DIM_KEYS = ("hidden_size", "moe_intermediate_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "num_experts", "num_experts_per_tok", "norm_topk_prob",
            "vocab_size", "rms_norm_eps", "rope_theta")

#: Experts made at a time (computed one by one); query heads computed
#: at a time; column blocks the head is made and multiplied in.
EXPERT_BLOCK = 8
HEAD_BLOCK = 2
VOCAB_BLOCKS = 8


def dims_of(config: dict) -> dict:
    """The sizes the mathematics needs: the published ones by their
    names, the generation's (`generation`, `mask_token_id`: the
    configuration's assumed sizes) beside them."""
    d = {k: config[k] for k in DIM_KEYS}
    gen = config["generation"]
    d.update(block_length=gen["block_length"],
             denoising_steps=gen["denoising_steps"],
             remasking=gen["remasking"],
             mask_token_id=config["mask_token_id"])
    assert d["num_experts"] % EXPERT_BLOCK == 0, d
    assert d["vocab_size"] % VOCAB_BLOCKS == 0, d
    assert d["block_length"] % d["denoising_steps"] == 0, d
    assert config.get("decoder_sparse_step", 1) == 1, config
    assert not config.get("mlp_only_layers"), config
    return d


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
    """One layer in the published layout, `(in, out)` oriented, less
    its experts (`expert_weights`): the four projections, the two head
    norms, the two layer norms and the router."""
    h, d = dims["hidden_size"], dims["head_dim"]
    nq, nkv = dims["num_attention_heads"], dims["num_key_value_heads"]
    ks = jax.random.split(key, 9)
    return {
        "q": _normal(ks[0], (h, nq * d), h ** -0.5),
        "k": _normal(ks[1], (h, nkv * d), h ** -0.5),
        "v": _normal(ks[2], (h, nkv * d), h ** -0.5),
        "o": _normal(ks[3], (nq * d, h), (nq * d) ** -0.5),
        "q_norm": _norm_weight(ks[4], d),
        "k_norm": _norm_weight(ks[5], d),
        "ln1": _norm_weight(ks[6], h),
        "ln2": _norm_weight(ks[7], h),
        "router": _normal(ks[8], (h, dims["num_experts"]), h ** -0.5
                          ).astype(jnp.float32),
    }


def expert_weights(key, dims: dict, block: int) -> dict:
    """Experts ``block * EXPERT_BLOCK ..`` of the layer whose key is
    ``key``: gate and up `(EXPERT_BLOCK, h, f)`, down
    `(EXPERT_BLOCK, f, h)`; ``block`` may be traced."""
    h, f = dims["hidden_size"], dims["moe_intermediate_size"]
    ks = jax.random.split(jax.random.fold_in(key, 1000 + block), 3)
    n = EXPERT_BLOCK
    return {"gate": _normal(ks[0], (n, h, f), h ** -0.5),
            "up": _normal(ks[1], (n, h, f), h ** -0.5),
            "down": _normal(ks[2], (n, f, h), f ** -0.5)}


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
    experts).  `reduce_precision` is the rounding the compiler may not
    take out (PERF.md)."""
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
    rounded to float8_e4m3 and handed back in its own type: what a
    program serving float8 weights would hold."""
    return {k: (_fp8(w.astype(jnp.float32), batched=w.ndim == 3
                     ).astype(w.dtype)
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


# ---------------------------------------------------------------------------
# the forward pass
# ---------------------------------------------------------------------------

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


def router_weights(hdn, w, dm: dict):
    """Dense (tokens, experts) float32 combine weights: zero off each
    token's chosen experts."""
    p = jax.nn.softmax(jnp.dot(hdn, w["router"], precision="highest"),
                       axis=-1)
    picked, chosen = jax.lax.top_k(p, dm["num_experts_per_tok"])
    if dm["norm_topk_prob"]:
        picked = picked / picked.sum(axis=-1, keepdims=True)
    rows = jnp.arange(hdn.shape[0])[:, None]
    return jnp.zeros_like(p).at[rows, chosen].set(picked)


def _qkv(hdn, w, pos, dm, fp8):
    """q (S, H, d), k, v (S, Hkv, d): projected, normed a head, rotated."""
    s = hdn.shape[0]
    d, eps = dm["head_dim"], dm["rms_norm_eps"]
    q = _mm(hdn, w["q"], fp8).reshape(s, -1, d)
    k = _mm(hdn, w["k"], fp8).reshape(s, -1, d)
    v = _mm(hdn, w["v"], fp8).reshape(s, -1, d)
    q = _rope(_rms(q, w["q_norm"], eps), pos, dm["rope_theta"])
    k = _rope(_rms(k, w["k_norm"], eps), pos, dm["rope_theta"])
    return q, k, v


def _attend(q, keys, values, masks, dm):
    """softmax over the concatenation of several key sets, each with
    its own mask: q (S, H, d); keys / values: lists of (Sk, Hkv, d);
    masks: lists of (S, Sk) bool.  `HEAD_BLOCK` query heads at a time."""
    s, nq, d = q.shape
    group = nq // keys[0].shape[1]
    k = jnp.concatenate(keys, axis=0)
    v = jnp.concatenate(values, axis=0)
    mask = jnp.concatenate(masks, axis=1)

    def heads(args):
        qb, hb = args                                # (HB, S, d), (HB,)
        kb = k[:, hb // group].transpose(1, 0, 2)    # (HB, Sk, d)
        vb = v[:, hb // group].transpose(1, 0, 2)
        sc = jnp.einsum("hsd,hkd->hsk", qb, kb,
                        precision="highest") * d ** -0.5
        p = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("hsk,hkd->hsd", p, vb, precision="highest")

    nb = nq // HEAD_BLOCK
    out = jax.lax.map(heads, (
        q.transpose(1, 0, 2).reshape(nb, HEAD_BLOCK, s, d),
        jnp.arange(nq).reshape(nb, HEAD_BLOCK)))
    return out.reshape(nq, s, d).transpose(1, 0, 2).reshape(s, nq * d)


@functools.partial(jax.jit, static_argnames=("dims", "fp8"))
def _attention(x, pos, clean_kv, key, *, dims, fp8):
    """x (S, hidden) at positions ``pos`` after the attention block,
    the normed input of the experts, the dense combine weights, and
    this pass's own (k, v).  ``clean_kv`` None: the positions attend
    one another block-causally.  Else ``(k, v, pos)`` of the CLEAN
    sequence: a position attends the clean blocks before its own, and
    its own block among the positions given."""
    dm = dict(dims)
    w = layer_weights(key, dm)
    eps, n = dm["rms_norm_eps"], dm["block_length"]
    hdn = _rms(x, w["ln1"], eps)
    q, k, v = _qkv(hdn, w, pos, dm, fp8)
    blk = pos // n
    if clean_kv is None:
        att = _attend(q, [k], [v], [blk[None, :] <= blk[:, None]], dm)
    else:
        ck, cv, cpos = clean_kv
        att = _attend(q, [ck, k], [cv, v],
                      [(cpos // n)[None, :] < blk[:, None],
                       blk[None, :] == blk[:, None]], dm)
    x = x + _mm(att, w["o"], fp8)
    hdn = _rms(x, w["ln2"], eps)
    return x, hdn, router_weights(hdn, w, dm), (k, v)


@functools.partial(jax.jit, static_argnames=("dims", "fp8"))
def _experts(hdn, combine, key, *, dims, fp8):
    """sum over the layer's experts of combine[:, e] * SwiGLU_e(hdn):
    every expert over every token, a block of weights made and one
    expert computed at a time.  ONE program a shape: the block is a
    loop's index, not a static argument (sixteen programs a shape took
    seven minutes to compile on the chip)."""
    dm = dict(dims)

    def block(b, total):
        w = expert_weights(key, dm, b)

        def one(total, e):
            y = _mm(jax.nn.silu(_mm(hdn, w["gate"][e], fp8))
                    * _mm(hdn, w["up"][e], fp8), w["down"][e], fp8)
            return total + y * combine[:, b * EXPERT_BLOCK + e][:, None], None

        return jax.lax.scan(one, total, jnp.arange(EXPERT_BLOCK))[0]

    return jax.lax.fori_loop(0, dm["num_experts"] // EXPERT_BLOCK, block,
                             jnp.zeros_like(hdn))


def layer_forward(x, pos, key, dims: dict, fp8: bool = False,
                  clean_kv=None):
    """x (S, hidden) float32 through one layer; returns (x, (k, v))."""
    hd = _hashable(dims)
    x, hdn, combine, kv = _attention(x, pos, clean_kv, key, dims=hd,
                                     fp8=fp8)
    return x + _experts(hdn, combine, key, dims=hd, fp8=fp8), kv


@functools.partial(jax.jit, static_argnames=("dims",))
def _embed(tokens, key, *, dims):
    return end_weights(key, dict(dims))["embed"][tokens].astype(
        jnp.float32)


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
    rows = _rms(rows, ln_f, dm["rms_norm_eps"])
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


def _check(precision: str) -> bool:
    if precision not in ("f32", "fp8"):
        raise ValueError(f"unknown precision {precision!r}")
    return precision == "fp8"


def forward(dims: dict, seed: int, tokens, first: int, n_out: int,
            precision: str = "f32"):
    """THE DEFINITION.  Logits `(n_out, vocab)` float32 at positions
    ``first .. first + n_out - 1`` of ONE sequence ``tokens`` (1-D) as
    it stands — the mask id wherever a position is not revealed —
    under the block-causal mask.  Whole blocks only: what lies past
    the block of the last position read never reaches it."""
    fp8 = _check(precision)
    hd = _hashable(dims)
    key = base_key(seed)
    tokens = jnp.asarray(np.asarray(tokens), jnp.int32)
    assert tokens.shape[0] % dims["block_length"] == 0, tokens.shape
    pos = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    x = _embed(tokens, key, dims=hd)
    for i in range(dims["num_hidden_layers"]):
        x, _ = layer_forward(x, pos, layer_key(key, i), dims, fp8)
    return _head(x, jnp.int32(first), key, dims=hd, fp8=fp8,
                 n_out=int(n_out))


def reveal_pass(dims: dict, prompt_len: int, positions) -> np.ndarray:
    """Which denoise pass of its block reveals each of ``positions``
    (numpy) of a request whose prompt has ``prompt_len`` tokens, under
    the sequential schedule: -1 for a prompt position (always
    revealed); else the block's masked positions — from ``prompt_len``
    on in the request's first block, all of them in the others —
    leave B / T at a time, leftmost first."""
    n = dims["block_length"]
    share = n // dims["denoising_steps"]
    p = np.asarray(positions, np.int64)
    masked_from = np.maximum(p // n * n, prompt_len)
    return np.where(p < prompt_len, -1, (p - masked_from) // share)


def logits_at(dims: dict, seed: int, tokens, first: int, n_out: int,
              precision: str = "f32"):
    """Row k: the logits FOR position ``first + 1 + k`` of ONE request
    — ``tokens`` (1-D, padded by the caller to the length it wants
    compiled) its prompt, ``first + 1`` tokens, then what was served —
    IN THE STATE IN WHICH THE PROGRAM REVEALED IT: in that position's
    block everything an earlier pass revealed stands with the served
    tokens, its own pass's positions and everything after them are
    masked (`reveal_pass`); earlier blocks are committed.  So
    ``argmax(row k)`` is what exact arithmetic serves at that position.
    Padding on the right is masked or in later blocks either way.

    ``precision``: "f32" is the reference; "fp8" the control."""
    fp8 = _check(precision)
    assert dims["remasking"] == "sequential", (
        "the state a position was revealed in follows from positions "
        "alone under the sequential schedule only")
    hd = _hashable(dims)
    key = base_key(seed)
    n, mask_id = dims["block_length"], dims["mask_token_id"]
    prompt_len = int(first) + 1
    start = prompt_len // n * n          # the request's first block
    # (whole blocks, and one length whatever the prompt's tail)
    span = -(-(n - 1 + int(n_out)) // n) * n
    tokens = np.asarray(tokens, np.int64)
    if start + span > len(tokens):
        tokens = np.concatenate(
            [tokens, np.zeros(start + span - len(tokens), np.int64)])
    when = reveal_pass(dims, prompt_len, start + np.arange(span))
    noised = [jnp.asarray(np.where(when < g, tokens[start:start + span],
                                   mask_id), jnp.int32)
              for g in range(dims["denoising_steps"])]

    cpos = jnp.arange(len(tokens), dtype=jnp.int32)
    npos = start + jnp.arange(span, dtype=jnp.int32)
    x = _embed(jnp.asarray(tokens, jnp.int32), key, dims=hd)
    xs = [_embed(t, key, dims=hd) for t in noised]
    for i in range(dims["num_hidden_layers"]):
        lk = layer_key(key, i)
        x, (ck, cv) = layer_forward(x, cpos, lk, dims, fp8)
        xs = [layer_forward(xn, npos, lk, dims, fp8,
                            clean_kv=(ck, cv, cpos))[0] for xn in xs]
    del x
    # each position from the pass that revealed it
    rows = xs[0]
    for g in range(1, len(xs)):
        rows = jnp.where(jnp.asarray(when == g)[:, None], xs[g], rows)
    return _head(rows, jnp.int32(prompt_len - start), key, dims=hd,
                 fp8=fp8, n_out=int(n_out))
