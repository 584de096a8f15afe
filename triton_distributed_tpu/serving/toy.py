"""Toy single-layer attention LM implementing the engine contract.

Same interface as `models.qwen.Qwen3` (`create_cache` /
`make_prefill_fn` / `make_decode_fn`, prefill sets the offset, decode
writes KV at per-row offsets and attends positions ``< offset+1``) but
pure jnp — no shard_map, no mesh — so the serving scheduler, its
tier-1 tests and the CPU benchmark exercise the REAL continuous-
batching machinery (bucketed prefill, slot insert, masked step) on any
host.  Position embeddings make the logits depend on absolute
position, so a wrong slot offset or a consumed pad tail shows up as
wrong tokens, not silence.

The toy also implements the PAGED half of the contract
(`create_paged_cache` / `make_paged_decode_fn` /
`make_prefill_suffix_fn`), reading KV through a page table the same
way `kernels.flash_decode.flash_decode_paged` does on TPU — so the
paged scheduler, radix prefix cache and page allocator are exercised
token-for-token against the slot engine on CPU.  Both dense and paged
paths support the int8-quantized cache (per-token symmetric scales,
`quantize_kv`): writes quantize, reads dequantize, so the two engines
see bit-identical dequantized values.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from triton_distributed_tpu.models.kv_cache import (
    KVCache, PagedKVCache, write_token_rows)


@dataclasses.dataclass
class ToyConfig:
    vocab_size: int = 97
    hidden: int = 32
    max_seq_len: int = 128
    quantize_kv_cache: bool = False
    #: Tokens a chunk of a long prompt's prefill (`ToyModel.
    #: prefill_chunk`, read by the scheduler); 0: never chunked.
    prefill_chunk: int = 0


def _quantize_token(k, v):
    """Per-token int8 quantization of one decode step's K/V (B, H):
    returns int8 (B, 1, 1, H) + f32 scales (B, 1, 1) — the same
    `quantize_kv` scheme the prefill write path uses."""
    from triton_distributed_tpu.kernels.flash_decode import quantize_kv

    return quantize_kv(k[:, None, None, :], v[:, None, None, :])


class ToyModel:
    def __init__(self, config: Optional[ToyConfig] = None):
        self.config = config or ToyConfig()
        self.prefill_chunk = self.config.prefill_chunk

    def init_params(self, key):
        cfg = self.config
        ks = jax.random.split(key, 6)
        h, v = cfg.hidden, cfg.vocab_size
        n = lambda k, shape: (jax.random.normal(k, shape)  # noqa: E731
                              * h ** -0.5).astype(jnp.float32)
        return {
            "embed": n(ks[0], (v, h)),
            "pe": n(ks[1], (cfg.max_seq_len, h)),
            "wq": n(ks[2], (h, h)),
            "wk": n(ks[3], (h, h)),
            "wv": n(ks[4], (h, h)),
            "wo": n(ks[5], (h, v)),
        }

    def create_cache(self, batch: int, max_seq: Optional[int] = None):
        cfg = self.config
        return KVCache.create(
            num_layers=1, batch=batch, num_kv_heads=1,
            max_seq=max_seq or cfg.max_seq_len, head_dim=cfg.hidden,
            dtype=jnp.float32, quantized=cfg.quantize_kv_cache)

    def create_paged_cache(self, batch: int, num_pages: int,
                           page_size: int, max_pages_per_seq: int):
        cfg = self.config
        return PagedKVCache.create(
            num_layers=1, num_pages=num_pages, batch=batch,
            num_kv_heads=1, page_size=page_size,
            head_dim=cfg.hidden, max_pages_per_seq=max_pages_per_seq,
            dtype=jnp.float32, quantized=cfg.quantize_kv_cache)

    def make_prefill_fn(self):
        scale = self.config.hidden ** -0.5

        def prefill(params, ids, cache: KVCache):
            b, s = ids.shape
            x = params["embed"][ids] + params["pe"][:s][None]
            q = x @ params["wq"]
            k = x @ params["wk"]
            v = x @ params["wv"]
            scores = jnp.einsum("bqh,bkh->bqk", q, k) * scale
            causal = jnp.tril(jnp.ones((s, s), bool))
            att = jax.nn.softmax(
                jnp.where(causal[None], scores, -jnp.inf), axis=-1)
            out = jnp.einsum("bqk,bkh->bqh", att, v)
            logits = out[:, -1] @ params["wo"]
            cache = cache.write_prefill(0, k[:, None], v[:, None])
            return logits, cache.set_offset(s)

        return prefill

    def make_prefill_suffix_fn(self):
        """Prefix-cache-aware prefill: compute KV for suffix positions
        ``[start, start + S)`` of a prompt whose first ``start`` tokens
        are already cached (their pages are shared via the radix
        cache) — or, a chunk at a time, of a prompt the scheduler
        prefills in chunks of ``prefill_chunk`` tokens.  ``pools`` is
        the paged cache's ``(ks, vs)`` and ``page_ids`` (T,) the
        request's pages in logical order: where a multi-layer model
        attends its suffix queries over the rows below ``start``
        (`models.glm4_moe_lite`).  The toy's K/V at position i depend
        only on token i and position i, so it reads neither.  Returns
        the row cache with the suffix
        KV at LOCAL positions [0, S) — the paged insert scatters local
        pages to physical pages.  No logits: the serving insert path
        recomputes position s-1 and never consumes prefill logits."""

        def prefill_suffix(params, ids, start, cache: KVCache, pools,
                           page_ids):
            b, s = ids.shape
            pos = jnp.asarray(start, jnp.int32) + jnp.arange(s)
            x = params["embed"][ids] + params["pe"][pos][None]
            k = x @ params["wk"]
            v = x @ params["wv"]
            cache = cache.write_prefill(0, k[:, None], v[:, None])
            return cache.set_offset(s)

        return prefill_suffix

    def make_decode_fn(self):
        scale = self.config.hidden ** -0.5

        def decode(params, tokens, cache: KVCache):
            offset = cache.offset                       # (B,)
            x = params["embed"][tokens] + params["pe"][offset]
            q = x @ params["wq"]
            k = x @ params["wk"]
            v = x @ params["wv"]
            upd = lambda c, u, o: jax.lax.dynamic_update_slice(  # noqa: E731
                c, u, (0, o, 0))
            if cache.quantized:
                kq, vq, ksn, vsn = _quantize_token(k, v)
                ks = jax.vmap(upd)(cache.ks[0], kq, offset)
                vs = jax.vmap(upd)(cache.vs[0], vq, offset)
                upd2 = lambda c, u, o: jax.lax.dynamic_update_slice(  # noqa: E731
                    c, u, (0, o))
                kss = jax.vmap(upd2)(cache.kss[0], ksn, offset)
                vss = jax.vmap(upd2)(cache.vss[0], vsn, offset)
                kf = ks.astype(jnp.float32) * kss[..., None]
                vf = vs.astype(jnp.float32) * vss[..., None]
                cache = cache.set_layer(0, ks, vs, kss, vss)
            else:
                ks = jax.vmap(upd)(cache.ks[0], k[:, None, None, :],
                                   offset)
                vs = jax.vmap(upd)(cache.vs[0], v[:, None, None, :],
                                   offset)
                kf, vf = ks, vs
                cache = cache.set_layer(0, ks, vs)
            smax = ks.shape[2]
            mask = jnp.arange(smax)[None, :] <= offset[:, None]
            scores = jnp.einsum("bh,bsh->bs", q, kf[:, 0]) * scale
            att = jax.nn.softmax(
                jnp.where(mask, scores, -jnp.inf), axis=-1)
            out = jnp.einsum("bs,bsh->bh", att, vf[:, 0])
            logits = out @ params["wo"]
            return logits, cache.inc_offset(1)

        return decode

    def make_paged_decode_fn(self, page_size: int = 16):
        """Decode through the page table: the new token's KV is
        scattered into ``page_table[b, offset // page]`` at row
        ``offset % page``, and attention gathers the pool back into
        logical order.  Masked rows (frozen offsets, NULL-mapped
        tables) write into the reserved null page — never read.

        Token-for-token identical to `make_decode_fn` on the slot
        cache when T × page_size equals the dense max_seq: the
        attention sees the same values at the same logical positions,
        masked positions contribute exactly 0 in both layouts.
        """
        scale = self.config.hidden ** -0.5

        def decode(params, tokens, cache: PagedKVCache):
            offset = cache.offset                       # (B,)
            b = offset.shape[0]
            ps = cache.page_size
            x = params["embed"][tokens] + params["pe"][offset]
            q = x @ params["wq"]
            k = x @ params["wk"]
            v = x @ params["wv"]
            bidx = jnp.arange(b)
            phys = cache.page_table[bidx, offset // ps]  # (B,)
            within = offset % ps
            if cache.quantized:
                kq, vq, ksn, vsn = _quantize_token(k, v)
                ks = write_token_rows(cache.ks[0], phys, within,
                                      kq[:, :, 0])
                vs = write_token_rows(cache.vs[0], phys, within,
                                      vq[:, :, 0])
                kss = write_token_rows(cache.kss[0], phys, within,
                                       ksn[:, :, 0])
                vss = write_token_rows(cache.vss[0], phys, within,
                                       vsn[:, :, 0])
                cache = dataclasses.replace(
                    cache, ks=[ks], vs=[vs], kss=[kss], vss=[vss])
                kseq = ks[cache.page_table]   # (B, T, Hkv, page, H)
                vseq = vs[cache.page_table]
                ksseq = kss[cache.page_table]  # (B, T, Hkv, page)
                vsseq = vss[cache.page_table]
                kf = (kseq.astype(jnp.float32)
                      * ksseq[..., None])
                vf = (vseq.astype(jnp.float32)
                      * vsseq[..., None])
            else:
                ks = write_token_rows(cache.ks[0], phys, within,
                                      k[:, None, :])
                vs = write_token_rows(cache.vs[0], phys, within,
                                      v[:, None, :])
                cache = dataclasses.replace(cache, ks=[ks], vs=[vs])
                kf = ks[cache.page_table]
                vf = vs[cache.page_table]
            # (B, T, Hkv, page, H) -> (B, Hkv, T*page, H)
            h = kf.shape[-1]
            kf = jnp.moveaxis(kf, 2, 1).reshape(b, 1, -1, h)
            vf = jnp.moveaxis(vf, 2, 1).reshape(b, 1, -1, h)
            smax = kf.shape[2]
            mask = jnp.arange(smax)[None, :] <= offset[:, None]
            scores = jnp.einsum("bh,bsh->bs", q, kf[:, 0]) * scale
            att = jax.nn.softmax(
                jnp.where(mask, scores, -jnp.inf), axis=-1)
            out = jnp.einsum("bs,bsh->bh", att, vf[:, 0])
            logits = out @ params["wo"]
            return logits, cache.inc_offset(1)

        return decode
