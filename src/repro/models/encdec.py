"""Whisper-style encoder-decoder (whisper-tiny backbone).

The audio conv frontend is a STUB per the assignment: `input_specs()`
supplies precomputed frame embeddings (B, encoder_seq, d_model) — the output
the two-conv mel frontend would produce. Everything downstream (encoder
blocks, decoder self+cross attention, LM head) is real and quantizable.

Encoder: pre-LN transformer, learned positions, non-causal.
Decoder: pre-LN transformer, learned positions, causal self-attn + cross.
"""
from __future__ import annotations

from typing import Optional

import dataclasses
import jax
import jax.numpy as jnp

from repro.runtime.kv_cache import PagedState, write_cross_pages

from .layers import ParamDef, linear, norm, norm_params
from .transformer import (
    SegmentSpec,
    _segment_scan,
    _stack_defs,
    block_params,
    init_block_cache,
    lm_logits,
)

__all__ = ["build_encdec", "encode", "encode_cross_pages", "encdec_forward",
           "init_encdec_cache"]


def _enc_seg(cfg) -> SegmentSpec:
    return SegmentSpec("gqa", "mlp", cfg.encoder_layers)


def _dec_seg(cfg) -> SegmentSpec:
    return SegmentSpec("gqa", "mlp", cfg.n_layers, cross=True)


def build_encdec(cfg):
    d, dt = cfg.d_model, cfg.param_dtype
    return {
        "enc_pos": ParamDef((cfg.encoder_seq, d), (None, "embed"), dt, "embed"),
        "encoder": _stack_defs(block_params(cfg, _enc_seg(cfg)), cfg.encoder_layers),
        "enc_ln": norm_params(cfg),
        "embed": ParamDef((cfg.vocab_size, d), ("vocab", "embed"), dt, "embed"),
        "pos_embed": ParamDef((cfg.max_position, d), (None, "embed"), dt, "embed"),
        "decoder": _stack_defs(block_params(cfg, _dec_seg(cfg)), cfg.n_layers),
        "final_ln": norm_params(cfg),
        # whisper ties the output head to the token embedding
    }


def encode(params, cfg, frames, a_fmt: Optional[str] = None, remat: bool = False):
    """frames: (B, encoder_seq, d) stub embeddings -> (B, T_enc, d)."""
    b, t, _ = frames.shape
    frames = frames.astype(jnp.dtype(cfg.param_dtype))
    x = frames + params["enc_pos"][None, :t].astype(frames.dtype)
    positions = jnp.arange(t)
    enc_cfg = dataclasses.replace(cfg, causal=False, pos_embedding="learned_applied")
    x, _, _ = _segment_scan(
        params["encoder"], x, enc_cfg, _enc_seg(cfg), positions, None, None, a_fmt, None, remat
    )
    return norm(params["enc_ln"], x, cfg.norm_kind, cfg.norm_eps)


def encode_cross_pages(params, cfg, frames, caches, cross_table,
                       a_fmt: Optional[str] = None):
    """Run the encoder once and quantize every decoder layer's cross K/V
    into its *write-once* cross pages (the paged engine's admission step).

    frames: (1, T_enc, d) stub frame embeddings; caches: the paged cache
    list — ``caches[0]["cross"]`` holds the decoder's cross pool, leaves
    (L, P+1, page, KV, hd); cross_table: (1, cross_pp) page ids reserved
    for this request. Returns the cache list with the cross pool written;
    the pages are never touched again for the request's lifetime (decode
    only reads them — see kv_cache.init_cross_pool).
    """
    enc_out = encode(params, cfg, frames, a_fmt=a_fmt)  # (1, T_enc, d)
    b, t = enc_out.shape[:2]
    kv, hd = cfg.n_kv_heads, cfg.resolved_head_dim

    def body(cross, xs):
        p_layer, layer = xs
        pc = p_layer["mixer"]["cross"]
        ek = linear(pc["wk"], enc_out).reshape(b, t, kv, hd)
        ev = linear(pc["wv"], enc_out, pc.get("bv")).reshape(b, t, kv, hd)
        return write_cross_pages(cross, layer, {"k": ek, "v": ev},
                                 cross_table), None

    # the cross pool rides the carry whole: each layer writes only its
    # request's pages (see transformer._segment_scan)
    new_cross, _ = jax.lax.scan(body, caches[0]["cross"],
                                (params["decoder"], jnp.arange(cfg.n_layers)))
    return [dict(caches[0], cross=new_cross)]


def encdec_forward(
    params,
    cfg,
    tokens,
    enc_out,
    caches=None,
    cache_index=None,
    a_fmt: Optional[str] = None,
    remat: bool = False,
):
    """Decoder pass. Returns (hidden, new_caches, aux)."""
    b, s = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0)
    if isinstance(cache_index, PagedState):
        # per-row true lengths -> (B, S) positions (each slot decodes at
        # its own depth; no synchronized offset)
        positions = cache_index.lengths[:, None] + jnp.arange(s)[None]
        x = x + jnp.take(params["pos_embed"], positions, axis=0).astype(x.dtype)
    else:
        offset = 0 if cache_index is None else cache_index
        positions = jnp.arange(s) + offset
        x = x + jax.lax.dynamic_slice_in_dim(
            params["pos_embed"], offset, s, axis=0)[None].astype(x.dtype)
    dec_cfg = dataclasses.replace(cfg, pos_embedding="learned_applied")
    paged = isinstance(cache_index, PagedState)
    seg_caches = caches[0] if (paged and caches is not None) else caches
    x, aux, new_caches = _segment_scan(
        params["decoder"], x, dec_cfg, _dec_seg(cfg), positions, seg_caches,
        cache_index, a_fmt, enc_out, remat,
    )
    x = norm(params["final_ln"], x, cfg.norm_kind, cfg.norm_eps)
    return x, ([new_caches] if paged else new_caches), aux


def init_encdec_cache(cfg, batch: int, max_seq: int):
    one = init_block_cache(cfg, _dec_seg(cfg), batch, max_seq, enc_seq=cfg.encoder_seq)
    return jax.tree.map(lambda a: jnp.broadcast_to(a, (cfg.n_layers,) + a.shape), one)
