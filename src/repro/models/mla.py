"""Multi-head Latent Attention (DeepSeek-V2/V3, MiniCPM3).

Two execution forms, chosen statically by mode:
  * train/prefill: materialized — expand the compressed latent into
    per-head K/V and run standard chunked attention (cheapest at large S).
  * decode: absorbed — the k_up projection is folded into the query and
    v_up into the output, so attention runs in the (kv_lora_rank +
    qk_rope_dim)-dim latent space against the *compressed* cache. The cache
    stores only (c_kv, k_rope): (kv_lora_rank + qk_rope_dim) per token per
    layer — MLA's whole point for serving.

All projections are quantizable linears (the paper's W4A8 path applies).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.runtime.kv_cache import (PagedState, append_paged,
                                    append_prefill_chunk, gather_history)

from .layers import (ParamDef, PackedLinear, accum_dtype, apply_rope, as_dense,
                     batched_linear, linear, norm, packed_head_view, quant_act,
                     shard_heads)
from .attention import block_mask, _sdpa_chunked, _sdpa_full

__all__ = ["mla_params", "mla_attention", "init_mla_cache"]


def mla_params(cfg):
    d, dt = cfg.d_model, cfg.param_dtype
    m = cfg.mla
    h = cfg.n_heads
    dq = m.qk_nope_dim + m.qk_rope_dim
    p = {}
    if m.q_lora_rank:
        p["wq_a"] = ParamDef((m.q_lora_rank, d), ("lora", "embed"), dt)
        p["q_norm"] = {"scale": ParamDef((m.q_lora_rank,), ("lora",), dt, "ones")}
        p["wq_b"] = ParamDef((h * dq, m.q_lora_rank), ("heads", "lora"), dt)
    else:
        p["wq"] = ParamDef((h * dq, d), ("heads", "embed"), dt)
    p["wkv_a"] = ParamDef((m.kv_lora_rank + m.qk_rope_dim, d), ("lora", "embed"), dt)
    p["kv_norm"] = {"scale": ParamDef((m.kv_lora_rank,), ("lora",), dt, "ones")}
    p["wk_b"] = ParamDef((h * m.qk_nope_dim, m.kv_lora_rank), ("heads", "lora"), dt)
    p["wv_b"] = ParamDef((h * m.v_head_dim, m.kv_lora_rank), ("heads", "lora"), dt)
    p["wo"] = ParamDef((d, h * m.v_head_dim), ("embed", "heads"), dt)
    return p


def init_mla_cache(cfg, batch, max_seq, dtype=jnp.bfloat16):
    m = cfg.mla
    return {
        "ckv": jnp.zeros((batch, max_seq, m.kv_lora_rank), dtype),
        "krope": jnp.zeros((batch, max_seq, m.qk_rope_dim), dtype),
    }


def _project_q(p, xq, cfg):
    m, h = cfg.mla, cfg.n_heads
    dq = m.qk_nope_dim + m.qk_rope_dim
    if "wq_a" in p:
        ql = linear(p["wq_a"], xq)
        ql = norm(p["q_norm"], ql, "rmsnorm", cfg.norm_eps)
        q = linear(p["wq_b"], ql)
    else:
        q = linear(p["wq"], xq)
    b, s = xq.shape[:2]
    q = q.reshape(b, s, h, dq)
    return q[..., : m.qk_nope_dim], q[..., m.qk_nope_dim :]


def mla_attention(
    p,
    x,
    cfg,
    positions,
    kv_cache=None,
    cache_index=None,
    a_fmt: Optional[str] = None,
    layer=None,
):
    """Returns (out, new_cache_or_None). Paged (``cache_index`` a
    PagedState): ``kv_cache`` is the whole stacked latent page pool and
    ``layer`` the layer this call reads and writes."""
    m, h = cfg.mla, cfg.n_heads
    b, s, _ = x.shape
    scale_dim = m.qk_nope_dim + m.qk_rope_dim

    xq = quant_act(x, a_fmt)
    q_nope, q_rope = _project_q(p, xq, cfg)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    kv_a = linear(p["wkv_a"], xq)  # (B, S, r + dr)
    c_kv = norm(p["kv_norm"], kv_a[..., : m.kv_lora_rank], "rmsnorm", cfg.norm_eps)
    k_rope = kv_a[..., m.kv_lora_rank :][:, :, None, :]  # (B, S, 1, dr)
    k_rope = apply_rope(k_rope, positions, cfg.rope_theta)[:, :, 0]

    new_cache = None
    paged = isinstance(cache_index, PagedState)
    # the absorbed form serves both paged modes: single-token decode and
    # the streaming prefill chunk (s > 1) — its einsums are s-generic
    is_decode = kv_cache is not None and (s == 1 or paged)
    if paged:
        # paged decode / streaming prefill chunk: append the compressed
        # latent + rope key at each row's true position (one token) or the
        # whole page-aligned chunk. Single-token decode then runs entirely
        # inside the latent flash-decoding kernel (ops.paged_mla_decode_attn
        # — KV = 1 head, k = concat(ckv, krope), v = the ckv view); the
        # chunk path attends the dequantized page gather in jnp.
        if cache_index.prefill is not None:
            # mixed engine step: the fused batch-1 row is [one decode token
            # per slot | one request's bucketed prefill chunk]. Decode
            # latents split out onto axis 0 and append at each slot's true
            # position (mid-prefill slots have lengths zeroed, so their
            # append null-redirects); the chunk tail appends page-aligned.
            # The appends target disjoint pages, so committing both in one
            # program preserves every pool invariant.
            pre = cache_index.prefill
            nd = cache_index.lengths.shape[0]
            dec = cache_index._replace(prefill=None)
            ckv_dec = jnp.swapaxes(c_kv[:, :nd], 0, 1)  # (nd, 1, r)
            kr_dec = jnp.swapaxes(k_rope[:, :nd], 0, 1)
            with jax.named_scope("kv_write"):
                cache1 = append_paged(
                    kv_cache, layer, {"ckv": ckv_dec, "krope": kr_dec}, dec)
                new_cache = append_prefill_chunk(
                    cache1, layer,
                    {"ckv": c_kv[:, nd:], "krope": k_rope[:, nd:]}, pre)
            sc = s - nd
            hist, hist_len = gather_history(new_cache, layer, pre, sc)
            start = pre.lengths[0]
            ckv = c_kv[:, nd:].astype(jnp.bfloat16)
            krope = k_rope[:, nd:].astype(jnp.bfloat16)
            if hist_len:
                ckv = jnp.concatenate(
                    [hist["ckv"].astype(jnp.bfloat16), ckv], axis=1)
                krope = jnp.concatenate(
                    [hist["krope"].astype(jnp.bfloat16), krope], axis=1)
            ok = jnp.concatenate(
                [jnp.broadcast_to(jnp.arange(hist_len)[None, :] < start,
                                  (sc, hist_len)),
                 jnp.tril(jnp.ones((sc, sc), jnp.bool_))], axis=1)
            pmsk4 = jnp.where(ok, 0.0, -1e30)[None, None].astype(jnp.float32)
        elif s == 1 and cache_index.chunk_len is None:
            with jax.named_scope("kv_write"):
                new_cache = append_paged(
                    kv_cache, layer, {"ckv": c_kv, "krope": k_rope},
                    cache_index)
        else:
            # streaming prefill: write the page-aligned chunk in-graph, then
            # attend over the gathered table + the chunk's own exact latents
            # (no page-grid round trip for the chunk itself). Gathered
            # columns at or past the chunk start — the chunk's own pages or
            # bucketed null-page fill — are masked; true history key i sits
            # at absolute position i < start, always causally visible. The
            # chunk masks plain tril (bucketed pad columns are only visible
            # to pad rows, whose outputs are discarded).
            assert b == 1, "streaming paged prefill is row-wise (batch 1)"
            with jax.named_scope("kv_write"):
                new_cache = append_prefill_chunk(
                    kv_cache, layer, {"ckv": c_kv, "krope": k_rope},
                    cache_index)
            hist, hist_len = gather_history(new_cache, layer, cache_index, s)
            start = cache_index.lengths[0]
            ckv = c_kv.astype(jnp.bfloat16)
            krope = k_rope.astype(jnp.bfloat16)
            if hist_len:
                ckv = jnp.concatenate(
                    [hist["ckv"].astype(jnp.bfloat16), ckv], axis=1)
                krope = jnp.concatenate(
                    [hist["krope"].astype(jnp.bfloat16), krope], axis=1)
            ok = jnp.concatenate(
                [jnp.broadcast_to(jnp.arange(hist_len)[None, :] < start,
                                  (s, hist_len)),
                 jnp.tril(jnp.ones((s, s), jnp.bool_))], axis=1)
            pmsk4 = jnp.where(ok, 0.0, -1e30)[None, None].astype(jnp.float32)
    elif kv_cache is not None:
        idx = 0 if cache_index is None else cache_index
        ckv_c = jax.lax.dynamic_update_slice(
            kv_cache["ckv"], c_kv.astype(kv_cache["ckv"].dtype), (0, idx, 0)
        )
        kr_c = jax.lax.dynamic_update_slice(
            kv_cache["krope"], k_rope.astype(kv_cache["krope"].dtype), (0, idx, 0)
        )
        new_cache = {"ckv": ckv_c, "krope": kr_c}

    if is_decode:
        # ---- absorbed form against the compressed cache -------------------
        if not paged:
            ckv = new_cache["ckv"]  # (B, T, r) bf16
            krope = new_cache["krope"]  # (B, T, dr)
        # q absorbed into latent space: (B, S, H, r). The projection
        # contracts wk_b's *out* rows (per head), so a packed weight runs
        # the batched fused kernel in transposed orientation — no densify.
        if isinstance(p["wk_b"], PackedLinear):
            wk_v = packed_head_view(p["wk_b"], h)  # (H, nope, r) packed
            q_h = jnp.moveaxis(q_nope, 2, 0).reshape(h, b * s, m.qk_nope_dim)
            q_lat = batched_linear(wk_v, q_h, transpose_w=True, quantize_acts=False)
            q_lat = jnp.moveaxis(
                q_lat.reshape(h, b, s, m.kv_lora_rank), 0, 2).astype(x.dtype)
        else:
            wk_b = as_dense(p["wk_b"], x.dtype).reshape(h, m.qk_nope_dim, m.kv_lora_rank)
            # batch-major einsum outputs (hbsr) — the CPU DotThunk rejects
            # bf16xbf16->f32 dots whose output interleaves batch dims
            q_lat = jnp.moveaxis(
                jnp.einsum("bshn,hnr->hbsr", q_nope, wk_b,
                           preferred_element_type=accum_dtype()), 0, 2
            ).astype(x.dtype)
        if paged and cache_index.prefill is not None:
            # mixed step: decode rows run the latent flash-decoding kernel
            # exactly as a pure decode step (same shapes, same inputs — the
            # token streams stay bit-identical), the chunk tail runs the
            # masked einsum over the gathered history built above
            from repro.kernels import ops

            q_lat_d = shard_heads(jnp.swapaxes(q_lat[:, :nd], 0, 1))
            q_rope_d = shard_heads(jnp.swapaxes(q_rope[:, :nd], 0, 1))
            ctx_dec = ops.paged_mla_decode_attn(
                q_lat_d[:, 0], q_rope_d[:, 0], new_cache, layer,
                dec.page_table, dec.lengths + 1,
                scale=1.0 / float(scale_dim) ** 0.5,
            )  # (nd, H, r)
            s_lat = jnp.einsum(
                "bshr,btr->bhst", q_lat[:, nd:], ckv,
                preferred_element_type=accum_dtype()).astype(jnp.float32)
            s_rope = jnp.einsum(
                "bshr,btr->bhst", q_rope[:, nd:], krope.astype(q_rope.dtype),
                preferred_element_type=accum_dtype()).astype(jnp.float32)
            att = jax.nn.softmax(
                (s_lat + s_rope) / jnp.sqrt(scale_dim) + pmsk4, axis=-1)
            ctx_pre = jnp.moveaxis(
                jnp.einsum("bhst,btr->bhsr", att.astype(ckv.dtype), ckv,
                           preferred_element_type=accum_dtype()), 1, 2)
            ctx_lat = jnp.concatenate(
                [jnp.swapaxes(ctx_dec[:, None], 0, 1).astype(x.dtype),
                 ctx_pre.astype(x.dtype)], axis=1)  # (1, nd + S, H, r)
        elif paged and s == 1 and cache_index.chunk_len is None:
            # latent flash decoding over the page pool: the gather, FP8
            # dequant, score concat and online softmax all happen inside
            # the kernel (ref backend: the jnp oracle with identical
            # semantics) — no dequantized (B, T, r) latent gather in HBM
            from repro.kernels import ops

            # absorbed heads shard over 'model' on a serving mesh (the
            # latent pages themselves replicate — no head axis); these
            # hints are no-ops off-mesh
            q_lat = shard_heads(q_lat)
            q_rope = shard_heads(q_rope)
            ctx_lat = ops.paged_mla_decode_attn(
                q_lat[:, 0], q_rope[:, 0], new_cache, layer,
                cache_index.page_table, cache_index.lengths + 1,
                scale=1.0 / float(scale_dim) ** 0.5,
            )[:, None].astype(x.dtype)  # (B, 1, H, r)
        else:
            t = ckv.shape[1]
            s_lat = jnp.einsum("bshr,btr->bhst", q_lat, ckv,
                               preferred_element_type=accum_dtype()).astype(jnp.float32)
            s_rope = jnp.einsum("bshr,btr->bhst", q_rope, krope.astype(q_rope.dtype),
                                preferred_element_type=accum_dtype()).astype(jnp.float32)
            if paged:  # per-row masks built alongside the page gather above
                msk4 = pmsk4
            else:
                msk4 = block_mask(s, t, cache_index, 0, False, 0,
                                  kv_len=cache_index + s)[None, None]
            att = jax.nn.softmax((s_lat + s_rope) / jnp.sqrt(scale_dim) + msk4,
                                 axis=-1)
            ctx_lat = jnp.moveaxis(
                jnp.einsum("bhst,btr->bhsr", att.astype(ckv.dtype), ckv,
                           preferred_element_type=accum_dtype()), 1, 2
            ).astype(x.dtype)
        if isinstance(p["wv_b"], PackedLinear):
            wv_v = packed_head_view(p["wv_b"], h)  # (H, v, r) packed
            ctx_h = jnp.moveaxis(ctx_lat, 2, 0).reshape(h, b * s, m.kv_lora_rank)
            o = batched_linear(wv_v, ctx_h, quantize_acts=False)
            o = jnp.moveaxis(o.reshape(h, b, s, m.v_head_dim), 0, 2).astype(x.dtype)
        else:
            wv_b = as_dense(p["wv_b"], x.dtype).reshape(h, m.v_head_dim, m.kv_lora_rank)
            o = jnp.einsum("bshr,hvr->bshv", ctx_lat, wv_b,
                           preferred_element_type=accum_dtype()).astype(x.dtype)
    else:
        # ---- materialized form (train / prefill) --------------------------
        k_nope = linear(p["wk_b"], c_kv).reshape(b, s, h, m.qk_nope_dim)
        v = linear(p["wv_b"], c_kv).reshape(b, s, h, m.v_head_dim)
        k_rope_h = jnp.broadcast_to(k_rope[:, :, None, :], (b, s, h, m.qk_rope_dim))
        q_full = shard_heads(jnp.concatenate([q_nope, q_rope], axis=-1))
        k_full = shard_heads(jnp.concatenate([k_nope, k_rope_h.astype(k_nope.dtype)], axis=-1))
        v = shard_heads(v)
        # v padded to qk dim? no — chunked kernel handles distinct v dim via
        # separate head_dim; _sdpa_* use v's own last dim.
        if s > cfg.attn_chunk:
            o = _sdpa_chunked(q_full, k_full, v, cfg.causal, cfg.window,
                              cfg.attn_chunk, cfg.attn_chunk)
        else:
            o = _sdpa_full(q_full, k_full, v, block_mask(s, s, 0, 0, cfg.causal, 0))

    o = o.reshape(b, s, h * m.v_head_dim)
    out = linear(p["wo"], quant_act(o, a_fmt), p.get("bo"))
    return out, new_cache
