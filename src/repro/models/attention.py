"""GQA attention: grouped-query, rope, chunked (flash-style) softmax for long
sequences, KV-cache decode, optional cross-attention (enc-dec).

Memory strategy (CPU-compile friendly, TPU-realistic):
  * train/prefill: blockwise attention — outer scan over query chunks, inner
    scan over kv chunks with online softmax (m, l, o) accumulation. Scores
    never exceed (B, H, qc, kc). Fully-masked off-diagonal blocks are still
    computed (standard blockwise trade-off, <= 2x causal-optimal attention
    FLOPs — negligible vs GEMM FLOPs for every assigned arch; noted in
    EXPERIMENTS.md §Roofline).
  * decode: the single-token query attends to the whole cache directly
    (scores are (B, H, 1, T) — small).

SPMD design notes (validated against compiled HLO — EXPERIMENTS.md §Perf):
  * masks are built from iota + scalar block offsets — (qc, kc), no batch
    dim, no position tensors. Batch-shaped f32 masks were observed to drag
    256 MiB all-to-alls into the inner kv loop via GSPMD resharding.
  * q/k/v stay bf16 into the score einsum with f32 accumulation
    (preferred_element_type) — the MXU path; f32 casts before the loop
    double HBM + collective traffic.
  * K/V heads are replicated when n_kv < model-axis size and repeated to
    the query-head count (Megatron convention) — `jnp.repeat` of a
    replicated tensor propagates cleanly to the head-sharded layout.

``positions`` throughout is a 1-D (seq,) int32 vector (all rows share it;
no packing), used only for rope. Masks never see it.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.runtime.kv_cache import (PagedState, append_paged,
                                    append_prefill_chunk, gather_history,
                                    gather_pages)

from .layers import ParamDef, accum_dtype, apply_rope, linear, quant_act, shard_heads

__all__ = ["attn_params", "attention", "paged_cross_attention", "init_kv_cache"]

_NEG_INF = -1e30


def attn_params(cfg, d_model=None, n_heads=None, n_kv=None, head_dim=None):
    d = d_model or cfg.d_model
    h = n_heads or cfg.n_heads
    kv = n_kv or cfg.n_kv_heads
    hd = head_dim or cfg.resolved_head_dim
    dt = cfg.param_dtype
    p = {
        "wq": ParamDef((h * hd, d), ("heads", "embed"), dt),
        "wk": ParamDef((kv * hd, d), ("kv", "embed"), dt),
        "wv": ParamDef((kv * hd, d), ("kv", "embed"), dt),
        "wo": ParamDef((d, h * hd), ("embed", "heads"), dt),
    }
    if cfg.use_bias:
        p["bq"] = ParamDef((h * hd,), ("heads",), dt, "zeros")
        p["bv"] = ParamDef((kv * hd,), ("kv",), dt, "zeros")
        p["bo"] = ParamDef((d,), ("embed",), dt, "zeros")
    return p


def init_kv_cache(batch, max_seq, n_kv, head_dim, dtype=jnp.bfloat16):
    return {
        "k": jnp.zeros((batch, max_seq, n_kv, head_dim), dtype),
        "v": jnp.zeros((batch, max_seq, n_kv, head_dim), dtype),
    }


def block_mask(sq: int, sk: int, q_start, k_start, causal: bool, window: int,
               kv_len=None):
    """(sq, sk) additive f32 mask from scalar block offsets (iota-based)."""
    qi = q_start + jnp.arange(sq)[:, None]
    ki = k_start + jnp.arange(sk)[None, :]
    ok = jnp.ones((sq, sk), jnp.bool_)
    if causal:
        ok &= ki <= qi
    if window:
        ok &= ki > qi - window
    if kv_len is not None:
        ok &= ki < kv_len
    return jnp.where(ok, 0.0, _NEG_INF).astype(jnp.float32)


def _repeat_kv(k, g: int):
    """(B, T, KV, hd) -> (B, T, KV*g, hd) by head repetition."""
    if g == 1:
        return k
    return jnp.repeat(k, g, axis=2)


def _sdpa_full(q, k, v, mask):
    """q: (B, Sq, H, hd) bf16ish, k/v: (B, Sk, H, hd), mask: (Sq, Sk) f32."""
    scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(jnp.float32)
    s = jnp.einsum("bqhd,bthd->bhqt", q, k, preferred_element_type=accum_dtype())
    s = s.astype(jnp.float32) * scale + mask[None, None]
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqt,bthd->bqhd", p.astype(v.dtype), v,
                   preferred_element_type=accum_dtype())
    return o.astype(v.dtype)


def _sdpa_chunked(q, k, v, causal, window, q_chunk, kv_chunk, q_offset=0):
    """Query-chunked attention: lax.scan over query blocks; each block runs
    a full softmax row against the WHOLE (loop-invariant) K/V.

    Single-level looping on purpose: a nested kv-block online-softmax keeps
    (m, l, o) carries and slices K/V per step — observed to make GSPMD
    reshard 100+ MiB per inner iteration under SP sharding (EXPERIMENTS.md
    §Perf). With K/V loop-invariant and heads-sharded, every einsum is local
    to the 'model' axis; peak live scores are (B, H, qc, T) f32.

    q: (B, S, H, hd); k/v: (B, T, H, hd/dv). q position i sits at absolute
    position q_offset + i; k positions start at 0.
    """
    del kv_chunk  # single-level: kept for call-site compatibility
    b, s, h, hd = q.shape
    dv = v.shape[-1]  # may differ from hd (MLA: v_head_dim != qk dim)
    t = k.shape[1]
    q_chunk = min(q_chunk, s)
    nq = -(-s // q_chunk)
    pad_q = nq * q_chunk - s
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))

    scale = 1.0 / float(hd) ** 0.5
    qs = q.reshape(b, nq, q_chunk, h, hd)

    def q_block(_, qi):
        qb = qs[:, qi]  # (B, qc, H, hd)
        sblk = jnp.einsum("bqhd,bthd->bhqt", qb, k,
                          preferred_element_type=accum_dtype()).astype(jnp.float32) * scale
        msk = block_mask(q_chunk, t, q_offset + qi * q_chunk, 0, causal, window)
        p = jax.nn.softmax(sblk + msk[None, None], axis=-1)
        ob = jnp.einsum("bhqt,bthd->bqhd", p.astype(v.dtype), v,
                        preferred_element_type=accum_dtype())
        return _, ob.astype(v.dtype)  # (B, qc, H, dv)

    _, outs = jax.lax.scan(q_block, 0, jnp.arange(nq))
    # outs: (nq, B, qc, H, dv) -> (B, S, H, dv)
    outs = jnp.moveaxis(outs, 0, 1).reshape(b, nq * q_chunk, h, dv)
    return outs[:, :s]


def _paged_chunk_attn(q, k, v, pool, layer, state, g: int, window: int):
    """Attention for one (batch-1) streaming-prefill chunk over layer
    ``layer`` of the stacked paged pool: gathered history pages + the
    chunk's own exact K/V inline (the chunk never round-trips the FP8 grid
    early). Shared by the pure chunk branch and the mixed step's prefill
    rows — the mask/gather math must stay identical so the two engines are
    bit-identical.

    q/k/v: (1, S, ·, hd) — the chunk's queries and fresh K/V, rope applied.
    ``state`` is the batch-1 chunk PagedState (lengths[0] = chunk start,
    page-aligned). Gathered columns at or past the start — the chunk's own
    just-written pages, or null-page fill from bucketing — are masked; only
    true history is read from pages.
    """
    s = q.shape[1]
    hist, hist_len = gather_history(pool, layer, state, s)
    start = state.lengths[0]
    kc, vc = k, v
    if hist_len:
        kc = jnp.concatenate([hist["k"].astype(k.dtype), k], 1)
        vc = jnp.concatenate([hist["v"].astype(v.dtype), v], 1)
    kf, vf = _repeat_kv(kc, g), _repeat_kv(vc, g)
    # within the chunk the mask is plain tril (a bucketed chunk's pad
    # columns are only visible to pad rows, whose outputs are discarded);
    # history columns are visible iff truly history
    ok = jnp.concatenate(
        [jnp.broadcast_to(jnp.arange(hist_len)[None, :] < start,
                          (s, hist_len)),
         jnp.tril(jnp.ones((s, s), jnp.bool_))], axis=1)
    if window:
        qi = start + jnp.arange(s)
        ki = jnp.concatenate([jnp.arange(hist_len), qi])
        ok &= ki[None, :] > qi[:, None] - window
    return _sdpa_full(q, kf, vf,
                      jnp.where(ok, 0.0, _NEG_INF).astype(jnp.float32))


def attention(
    p,
    x,
    cfg,
    positions,
    kv_cache=None,
    cache_index=None,
    a_fmt: Optional[str] = None,
    cross_kv=None,
    causal: Optional[bool] = None,
    n_heads=None,
    n_kv=None,
    head_dim=None,
    layer=None,
):
    """Returns (out, new_kv_cache_or_None). ``positions``: (S,) int32.

    Paged (``cache_index`` a PagedState): ``kv_cache`` is the whole stacked
    page pool and ``layer`` the layer this call reads and writes; the
    returned cache is the same stack with only the touched pages changed.

    Modes (decided statically from shapes):
      * train forward: kv_cache None — chunked/full attention over x.
      * prefill: kv_cache given, s > 1 — attends within x (fresh k/v,
        assumes cache_index = 0) and writes the cache.
      * decode: kv_cache given, s == 1 — appends at cache_index, attends to
        the filled cache prefix.
      * cross attention: cross_kv = (k, v) from the encoder; no cache.
    """
    h = n_heads or cfg.n_heads
    kv = n_kv or cfg.n_kv_heads
    hd = head_dim or cfg.resolved_head_dim
    g = h // kv
    causal = cfg.causal if causal is None else causal
    b, s, _ = x.shape

    xq = quant_act(x, a_fmt)
    # head-dim layout hint (no-op off-mesh): in SP training this keeps the
    # seq-sharded residual from gathering early; on a serving mesh it pins
    # decode's (B, 1, H, hd) q to the same head partitioning the sharded
    # paged-attention shard_map consumes, avoiding a resharding round-trip
    q = linear(p["wq"], xq, p.get("bq")).reshape(b, s, h, hd)
    q = shard_heads(q)
    if cfg.pos_embedding == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)

    if cross_kv is not None:
        k, v = cross_kv  # (B, T, H_or_KV, hd) — precomputed by the encoder
        if k.shape[2] != h:
            k, v = _repeat_kv(k, h // k.shape[2]), _repeat_kv(v, h // v.shape[2])
        t = k.shape[1]
        msk = jnp.zeros((s, t), jnp.float32)
        o = _sdpa_full(q, k, v, msk)
        o = o.reshape(b, s, h * hd)
        return linear(p["wo"], quant_act(o, a_fmt), p.get("bo")), None

    k = linear(p["wk"], xq).reshape(b, s, kv, hd)
    v = linear(p["wv"], xq, p.get("bv")).reshape(b, s, kv, hd)
    if cfg.pos_embedding == "rope":
        k = apply_rope(k, positions, cfg.rope_theta)

    if isinstance(cache_index, PagedState):
        if cache_index.prefill is not None:
            # mixed engine step: one fused (batch-1) token row carrying one
            # decode token per slot followed by one request's bucketed
            # prefill chunk. The first ``nd`` positions split out into the
            # s == 1 decode path (slot batch restored on axis 0), the tail
            # runs the streaming-chunk path — both appends commit to
            # disjoint pages inside this same program (decode rows only
            # touch their private boundary pages, the chunk only its own
            # table; mid-prefill slots ride along with lengths zeroed, so
            # their decode append null-redirects).
            from repro.kernels import ops

            assert causal, "mixed step assumes causal decode LMs"
            assert b == 1, "mixed step is one fused token row (batch 1)"
            pre = cache_index.prefill
            dec = cache_index._replace(prefill=None)
            nd = dec.lengths.shape[0]
            k_dec = jnp.swapaxes(k[:, :nd], 0, 1)  # (nd, 1, KV, hd)
            v_dec = jnp.swapaxes(v[:, :nd], 0, 1)
            with jax.named_scope("kv_write"):
                cache1 = append_paged(kv_cache, layer,
                                      {"k": k_dec, "v": v_dec}, dec)
                new_cache = append_prefill_chunk(
                    cache1, layer, {"k": k[:, nd:], "v": v[:, nd:]}, pre)
            q_dec = jnp.swapaxes(q[:, :nd], 0, 1)
            o_dec = ops.paged_decode_attn(
                q_dec[:, 0], new_cache, layer, dec.page_table,
                dec.lengths + 1, window=cfg.window,
            )
            o_pre = _paged_chunk_attn(q[:, nd:], k[:, nd:], v[:, nd:],
                                      new_cache, layer, pre, g, cfg.window)
            o = jnp.concatenate(
                [jnp.swapaxes(o_dec[:, None], 0, 1).astype(x.dtype),
                 o_pre.astype(x.dtype)], axis=1)  # (1, nd + S, H, hd)
            o = o.reshape(b, s, h * hd)
            return linear(p["wo"], quant_act(o, a_fmt), p.get("bo")), new_cache
        # chunk_len distinguishes a (possibly length-1) streaming-prefill
        # chunk from a decode step: decode's append redirects lengths == 0
        # rows to the null page, which would silently drop a prompt's
        # first token if a 1-token chunk took that path
        if s == 1 and cache_index.chunk_len is None:
            # paged decode: append this token at each row's true length,
            # then run flash-decoding over the quantized page pool
            # (kernels.ops routes pallas kernel vs jnp oracle). Per-row
            # length masks replace the engine-level synchronized index.
            from repro.kernels import ops

            with jax.named_scope("kv_write"):
                new_cache = append_paged(kv_cache, layer, {"k": k, "v": v},
                                         cache_index)
            o = ops.paged_decode_attn(
                q[:, 0], new_cache, layer, cache_index.page_table,
                cache_index.lengths + 1, window=cfg.window,
            )
            o = o[:, None].astype(x.dtype)  # (B, 1, H, hd)
        else:
            # streaming paged prefill: write this page-aligned prompt chunk
            # straight into the pool in-graph, then attend over the gathered
            # table plus the chunk's own exact K/V (the chunk does not
            # round-trip through the page grid, matching the monolithic
            # prefill numerics). No contiguous max_seq scratch cache is ever
            # materialized; gathered columns at or past the chunk start —
            # the chunk's own pages, or null-page fill when the engine
            # bucketed the table width — are masked, so only true history
            # (token i of the gather at absolute position i < start) is read
            # from pages.
            assert causal, "streaming paged prefill assumes causal decode LMs"
            assert b == 1, "streaming paged prefill is row-wise (batch 1)"
            with jax.named_scope("kv_write"):
                new_cache = append_prefill_chunk(kv_cache, layer,
                                                 {"k": k, "v": v},
                                                 cache_index)
            o = _paged_chunk_attn(q, k, v, new_cache, layer, cache_index, g,
                                  cfg.window)
        o = o.reshape(b, s, h * hd)
        out = linear(p["wo"], quant_act(o, a_fmt), p.get("bo"))
        return out, new_cache

    new_cache = None
    is_decode = kv_cache is not None and s == 1
    if kv_cache is not None:
        idx = 0 if cache_index is None else cache_index
        kc = jax.lax.dynamic_update_slice(
            kv_cache["k"], k.astype(kv_cache["k"].dtype), (0, idx, 0, 0)
        )
        vc = jax.lax.dynamic_update_slice(
            kv_cache["v"], v.astype(kv_cache["v"].dtype), (0, idx, 0, 0)
        )
        new_cache = {"k": kc, "v": vc}

    if is_decode:
        kf = _repeat_kv(new_cache["k"], g)
        vf = _repeat_kv(new_cache["v"], g)
        t = kf.shape[1]
        msk = block_mask(1, t, cache_index, 0, causal, cfg.window,
                         kv_len=cache_index + s)
        o = _sdpa_full(q, kf, vf, msk)
    else:
        kf, vf = shard_heads(_repeat_kv(k, g)), shard_heads(_repeat_kv(v, g))
        if s > cfg.attn_chunk:
            o = _sdpa_chunked(q, kf, vf, causal, cfg.window,
                              cfg.attn_chunk, cfg.attn_chunk)
        else:
            o = _sdpa_full(q, kf, vf, block_mask(s, s, 0, 0, causal, cfg.window))

    o = o.reshape(b, s, h * hd)
    out = linear(p["wo"], quant_act(o, a_fmt), p.get("bo"))
    return out, new_cache


def paged_cross_attention(p, x, cfg, positions, cross_pool, layer,
                          state: PagedState, a_fmt: Optional[str] = None):
    """Enc-dec decoder cross-attention over *write-once* cross pages.

    The encoder ran once at admission and its per-layer K/V was quantized
    into immutable cross pages (``kv_cache.write_cross_pages``); here the
    decoder only ever reads them, from layer ``layer`` of the stacked
    ``cross_pool``. Decode (s == 1) runs the same paged
    flash-decoding kernel as self-attention with ``kv_lens =
    state.enc_lengths`` — cross-attention is non-causal, so the per-row
    length mask *is* the whole mask. Prefill chunks (s > 1, batch 1) gather
    the cross pages once and attend with the encoder-length mask.

    Returns the projected output (no cache: cross pages never change).
    """
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    g = h // kv
    b, s, _ = x.shape
    xq = quant_act(x, a_fmt)
    q = linear(p["wq"], xq, p.get("bq")).reshape(b, s, h, hd)
    if cfg.pos_embedding == "rope":  # mirror the legacy cross path
        q = apply_rope(q, positions, cfg.rope_theta)
    if s == 1 and state.chunk_len is None:
        from repro.kernels import ops

        o = ops.paged_decode_attn(q[:, 0], cross_pool, layer,
                                  state.cross_table, state.enc_lengths,
                                  window=0)
        o = o[:, None].astype(x.dtype)  # (B, 1, H, hd)
    else:
        assert b == 1, "streaming paged prefill is row-wise (batch 1)"
        cstate = PagedState(state.cross_table, state.enc_lengths)
        kf = gather_pages(cross_pool, layer, "k", cstate).astype(x.dtype)
        vf = gather_pages(cross_pool, layer, "v", cstate).astype(x.dtype)
        t = kf.shape[1]
        kf, vf = _repeat_kv(kf, g), _repeat_kv(vf, g)
        ok = jnp.arange(t)[None, :] < state.enc_lengths[:1, None]  # (1, t)
        msk = jnp.where(jnp.broadcast_to(ok, (s, t)), 0.0, _NEG_INF)
        o = _sdpa_full(q, kf, vf, msk.astype(jnp.float32))
    o = o.reshape(b, s, h * hd)
    return linear(p["wo"], quant_act(o, a_fmt), p.get("bo"))
