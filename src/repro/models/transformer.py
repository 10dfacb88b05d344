"""Decoder-LM assembly: generic (mixer x ffn) blocks, scanned segments.

Covers: minitron-8b, nemotron-4-340b, olmo-1b, llava-next-34b (gqa+mlp),
minicpm3-4b (mla+mlp), deepseek-v3-671b (mla + [dense mlp x3, moe x58] +
MTP), olmoe-1b-7b (gqa+moe), xlstm-125m (mlstm/slstm pairs). Whisper
(encdec.py) and Zamba2 (hybrid.py) build on the same block primitives.

Design notes:
  * layers are stacked and scanned (jax.lax.scan) so HLO size is O(1) in
    depth — essential for compiling 61..96-layer configs on the CPU host.
  * parameters are ParamDef trees (models/params.py): one builder serves
    init / dry-run ShapeDtypeStructs / PartitionSpecs.
  * `a_fmt` threads the paper's token-wise activation quantization through
    every linear; weights are swapped to PackedLinear leaves by the PTQ
    driver for W4A8 serving.
  * remat: full per-block rematerialization in train mode.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import jax
import jax.numpy as jnp

from repro.runtime.kv_cache import PagedState, gather_slabs, scatter_slabs

from .attention import attention, attn_params, init_kv_cache, paged_cross_attention
from .layers import (ParamDef, linear, mlp, mlp_params, norm, norm_params,
                     quant_act, shard_residual)
from .mla import init_mla_cache, mla_attention, mla_params
from .moe import moe_layer, moe_params
from .ssm import init_mamba2_cache, mamba2_block, mamba2_params
from .xlstm import (
    init_mlstm_cache,
    init_slstm_cache,
    mlstm_block,
    mlstm_params,
    slstm_block,
    slstm_params,
)

__all__ = [
    "SegmentSpec",
    "segments_for",
    "build_lm",
    "lm_forward",
    "init_lm_cache",
    "lm_logits",
    "block_params",
    "block_apply",
    "init_block_cache",
]


@dataclasses.dataclass(frozen=True)
class SegmentSpec:
    mixer: str  # 'gqa' | 'mla' | 'mamba2' | 'xlstm_pair'
    ffn: str  # 'mlp' | 'moe' | 'none'
    count: int
    d_ff: int = 0  # override cfg.d_ff (deepseek dense layers)
    cross: bool = False  # decoder cross-attention (whisper)


def segments_for(cfg) -> List[SegmentSpec]:
    if cfg.ssm is not None and cfg.ssm.kind == "xlstm":
        assert cfg.n_layers % 2 == 0
        return [SegmentSpec("xlstm_pair", "none", cfg.n_layers // 2)]
    if cfg.moe is not None:
        segs = []
        if cfg.moe.n_dense_layers:
            segs.append(
                SegmentSpec(cfg.attn_kind, "mlp", cfg.moe.n_dense_layers,
                            d_ff=cfg.moe.dense_d_ff or cfg.d_ff)
            )
        segs.append(SegmentSpec(cfg.attn_kind, "moe", cfg.n_layers - cfg.moe.n_dense_layers))
        return segs
    return [SegmentSpec(cfg.attn_kind, "mlp", cfg.n_layers, cross=bool(cfg.encoder_layers))]


# ---------------------------------------------------------------------------
# Block params
# ---------------------------------------------------------------------------
def _mixer_params(cfg, kind: str, cross: bool = False):
    if kind == "gqa":
        p = {"ln": norm_params(cfg), "attn": attn_params(cfg)}
        if cross:
            p["ln_cross"] = norm_params(cfg)
            p["cross"] = attn_params(cfg)
        return p
    if kind == "mla":
        return {"ln": norm_params(cfg), "attn": mla_params(cfg)}
    if kind == "mamba2":
        return {"ln": norm_params(cfg), "mamba": mamba2_params(cfg)}
    if kind == "xlstm_pair":
        return {
            "ln_m": norm_params(cfg),
            "mlstm": mlstm_params(cfg),
            "ln_s": norm_params(cfg),
            "slstm": slstm_params(cfg),
        }
    raise ValueError(kind)


def _ffn_params(cfg, kind: str, d_ff: int = 0):
    if kind == "mlp":
        return {"ln": norm_params(cfg), "mlp": mlp_params(cfg, d_ff=d_ff or cfg.d_ff)}
    if kind == "moe":
        return {"ln": norm_params(cfg), "moe": moe_params(cfg)}
    if kind == "none":
        return {}
    raise ValueError(kind)


def block_params(cfg, seg: SegmentSpec):
    p = {"mixer": _mixer_params(cfg, seg.mixer, seg.cross)}
    f = _ffn_params(cfg, seg.ffn, seg.d_ff)
    if f:
        p["ffn"] = f
    return p


def _stack_defs(tree, n: int):
    """Prepend a ('layers', n) dim to every ParamDef leaf."""
    return jax.tree.map(
        lambda d: ParamDef((n,) + d.shape, ("layers",) + d.axes, d.dtype, d.init, d.scale),
        tree,
        is_leaf=lambda x: isinstance(x, ParamDef),
    )


# ---------------------------------------------------------------------------
# Block apply
# ---------------------------------------------------------------------------
def block_apply(
    p,
    x,
    cfg,
    seg: SegmentSpec,
    positions,
    cache=None,
    cache_index=None,
    a_fmt=None,
    enc_out=None,
    layer=None,
):
    """Returns (x, new_cache, aux). ``layer``: for paged GQA/MLA blocks,
    ``cache`` is the segment's whole stacked pools and ``layer`` the index
    this block reads and writes (see ``_segment_scan``)."""
    aux = jnp.zeros((), jnp.float32)
    nk = cfg.norm_kind
    pm = p["mixer"]
    new_cache = None
    paged = isinstance(cache_index, PagedState)

    if seg.mixer == "gqa":
        with jax.named_scope("attn"):
            h, new_kv = attention(
                pm["attn"], norm(pm["ln"], x, nk, cfg.norm_eps), cfg,
                positions, kv_cache=None if cache is None else cache["kv"],
                cache_index=cache_index, a_fmt=a_fmt, layer=layer,
            )
        x = x + h
        if cache is not None:
            new_cache = dict(cache, kv=new_kv)
        if seg.cross:
            if paged:
                # write-once cross pages: the engine ran the encoder at
                # admission and quantized its K/V into cache["cross"];
                # decode and prefill chunks only ever read them
                with jax.named_scope("attn"):
                    h = paged_cross_attention(
                        pm["cross"],
                        norm(pm["ln_cross"], x, nk, cfg.norm_eps), cfg,
                        positions, cache["cross"], layer, cache_index,
                        a_fmt=a_fmt,
                    )
            else:
                is_decode = cache is not None and x.shape[1] == 1
                if is_decode:  # prefill computed + stored these from enc_out
                    cross_kv = (cache["cross_k"], cache["cross_v"])
                else:
                    b, t = x.shape[0], enc_out.shape[1]
                    kv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
                    ek = linear(pm["cross"]["wk"], enc_out).reshape(b, t, kv, hd)
                    ev = linear(pm["cross"]["wv"], enc_out, pm["cross"].get("bv")).reshape(b, t, kv, hd)
                    cross_kv = (ek, ev)
                    if cache is not None:
                        new_cache = dict(new_cache, cross_k=ek, cross_v=ev)
                h, _ = attention(
                    pm["cross"], norm(pm["ln_cross"], x, nk, cfg.norm_eps),
                    cfg, positions, a_fmt=a_fmt, cross_kv=cross_kv,
                )
            x = x + h
    elif seg.mixer == "mla":
        with jax.named_scope("attn"):
            h, new_kv = mla_attention(
                pm["attn"], norm(pm["ln"], x, nk, cfg.norm_eps), cfg,
                positions, kv_cache=None if cache is None else cache["kv"],
                cache_index=cache_index, a_fmt=a_fmt, layer=layer,
            )
        x = x + h
        if cache is not None:
            new_cache = dict(cache, kv=new_kv)
    elif seg.mixer == "mamba2":
        # slab-pooled recurrent state (paged engine): leaves are
        # (n_slabs + 1, ...); gather each row's slab, step, scatter back
        mc = None if cache is None else cache["ssm"]
        if paged and cache is not None:
            mc = gather_slabs(mc, cache_index.slabs)
        h, new_ssm = mamba2_block(
            pm["mamba"], norm(pm["ln"], x, nk, cfg.norm_eps), cfg,
            cache=mc, a_fmt=a_fmt,
        )
        x = x + h
        if cache is not None:
            if paged:
                new_ssm = scatter_slabs(cache["ssm"], cache_index.slabs, new_ssm)
            new_cache = dict(cache, ssm=new_ssm)
    elif seg.mixer == "xlstm_pair":
        mlc = None if cache is None else cache["mlstm"]
        slc = None if cache is None else cache["slstm"]
        if paged and cache is not None:
            mlc = gather_slabs(mlc, cache_index.slabs)
            slc = gather_slabs(slc, cache_index.slabs)
        h, new_m = mlstm_block(
            pm["mlstm"], norm(pm["ln_m"], x, nk, cfg.norm_eps), cfg,
            cache=mlc, a_fmt=a_fmt,
        )
        x = x + h
        h, new_s = slstm_block(
            pm["slstm"], norm(pm["ln_s"], x, nk, cfg.norm_eps), cfg,
            cache=slc, a_fmt=a_fmt,
        )
        x = x + h
        if cache is not None:
            if paged:
                new_m = scatter_slabs(cache["mlstm"], cache_index.slabs, new_m)
                new_s = scatter_slabs(cache["slstm"], cache_index.slabs, new_s)
            new_cache = dict(cache, mlstm=new_m, slstm=new_s)
    else:
        raise ValueError(seg.mixer)

    if seg.ffn == "mlp":
        pf = p["ffn"]
        with jax.named_scope("mlp"):
            x = x + mlp(pf["mlp"], norm(pf["ln"], x, nk, cfg.norm_eps), cfg, a_fmt=a_fmt)
    elif seg.ffn == "moe":
        from .moe_a2a import get_moe_impl, moe_decode_ep, moe_layer_a2a

        pf = p["ffn"]
        kind, mesh = get_moe_impl()
        # Serving (paged) routes per-token: group_size=1 puts every
        # token in its own dispatch group, so capacity never drops an
        # assignment and each token's experts depend only on its own
        # hidden state. Batch composition — which rows share the
        # program, decode lanes vs a piggybacked prefill chunk, chunk
        # bucketing — can then never change a token's routing, which
        # is what makes serving outputs independent of batchmates and
        # the mixed engine bit-identical to the alternating one.
        # Training keeps the capacity-bounded grouped dispatch.
        gs = 1 if paged else 1024
        ok_a2a = (
            kind == "a2a" and mesh is not None and not paged
            and x.shape[1] % mesh.shape.get("model", 1) == 0
            and x.shape[0] % mesh.shape.get("data", 1) == 0
        )
        with jax.named_scope("mlp"):
            x_ln = norm(pf["ln"], x, nk, cfg.norm_eps)
            if ok_a2a:  # MTP's S-1 path etc. fall back to einsum dispatch
                h, aux = moe_layer_a2a(pf["moe"], x_ln, cfg, mesh, a_fmt=a_fmt)
            elif kind == "ep_decode" and mesh is not None:
                # serving on a mesh: replicated einsum dispatch (token-
                # identical routing), expert FFNs sharded over the stack
                h, aux = moe_decode_ep(pf["moe"], x_ln, cfg, mesh,
                                       a_fmt=a_fmt, group_size=gs)
            else:
                h, aux = moe_layer(pf["moe"], x_ln, cfg, a_fmt=a_fmt,
                                   group_size=gs)
            x = x + h
    return x, new_cache, aux


def init_block_cache(cfg, seg: SegmentSpec, batch: int, max_seq: int, enc_seq: int = 0):
    """Per-layer cache structure for one segment's block."""
    if seg.mixer == "gqa":
        c = {"kv": init_kv_cache(batch, max_seq, cfg.n_kv_heads, cfg.resolved_head_dim)}
        if seg.cross:
            kv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
            c["cross_k"] = jnp.zeros((batch, enc_seq, kv, hd), jnp.bfloat16)
            c["cross_v"] = jnp.zeros((batch, enc_seq, kv, hd), jnp.bfloat16)
        return c
    if seg.mixer == "mla":
        return {"kv": init_mla_cache(cfg, batch, max_seq)}
    if seg.mixer == "mamba2":
        return {"ssm": init_mamba2_cache(cfg, batch)}
    if seg.mixer == "xlstm_pair":
        return {"mlstm": init_mlstm_cache(cfg, batch), "slstm": init_slstm_cache(cfg, batch)}
    raise ValueError(seg.mixer)


# ---------------------------------------------------------------------------
# Whole-LM build / forward
# ---------------------------------------------------------------------------
def build_lm(cfg):
    """ParamDef tree for a decoder LM (token embeddings + segments + head)."""
    d, dt = cfg.d_model, cfg.param_dtype
    p = {"embed": ParamDef((cfg.vocab_size, d), ("vocab", "embed"), dt, "embed")}
    if cfg.pos_embedding == "learned":
        p["pos_embed"] = ParamDef((cfg.max_position, d), (None, "embed"), dt, "embed")
    if cfg.frontend == "vision_patches":
        # LLaVA-style 2-layer MLP projector from the (stub) vision encoder dim
        p["mm_proj"] = {
            "fc1": ParamDef((d, 1024), ("embed", None), dt),
            "fc2": ParamDef((d, d), ("embed", None), dt),
        }
    p["segments"] = [
        _stack_defs(block_params(cfg, seg), seg.count) for seg in segments_for(cfg)
    ]
    p["final_ln"] = norm_params(cfg)
    if not cfg.tie_embeddings:
        p["lm_head"] = ParamDef((cfg.vocab_size, d), ("vocab", "embed"), dt, "embed")
    if cfg.mtp_depth:
        seg0 = segments_for(cfg)[-1]
        p["mtp"] = {
            "block": block_params(cfg, seg0),
            "ln": norm_params(cfg),
            "proj": ParamDef((d, 2 * d), ("embed", None), dt),
        }
    return p


def _embed_tokens(params, cfg, tokens, embeds_prefix=None):
    x = jnp.take(params["embed"], tokens, axis=0)
    if embeds_prefix is not None:
        if cfg.frontend == "vision_patches":
            pe = embeds_prefix
            h = jax.nn.gelu(linear(params["mm_proj"]["fc1"], pe), approximate=True)
            pe = linear(params["mm_proj"]["fc2"], h)
        else:  # audio frames arrive at d_model already (conv frontend stub)
            pe = embeds_prefix.astype(x.dtype)
        x = jnp.concatenate([pe.astype(x.dtype), x], axis=1)
    return x


def _segment_scan(p_stack, x, cfg, seg, positions, caches, cache_index, a_fmt, enc_out, remat):
    """Scan one segment's stacked params (and stacked caches) over depth.

    Paged GQA/MLA segments (``cache_index`` a PagedState) carry their page
    pools whole, ``(L, P+1, ...)``, through the scan and hand each layer
    the stacked pools plus its layer index: the appends update the touched
    pages in place and the attention reads its layer straight out of the
    stack. Passed as ``xs``/``ys`` instead, every layer's whole store would
    be copied out of the pool and written back into a fresh one on every
    step. Every other cache (contiguous decode caches, recurrent state
    slabs) rides ``xs``/``ys`` as one slice per layer."""
    carried = (caches is not None and isinstance(cache_index, PagedState)
               and seg.mixer in ("gqa", "mla"))

    if carried:
        def body(carry, layer_in):
            h, aux_acc, pools = carry
            p_layer, layer = layer_in
            h = shard_residual(h)  # sequence-parallel residual (no-op off-mesh)
            h, pools, aux = block_apply(
                p_layer, h, cfg, seg, positions, pools, cache_index, a_fmt,
                enc_out, layer=layer)
            return (h, aux_acc + aux, pools), None

        init = (x, jnp.zeros((), jnp.float32), caches)
        xs = (p_stack, jnp.arange(seg.count))
    else:
        def body(carry, layer_in):
            h, aux_acc, _ = carry
            p_layer, cache_layer = layer_in
            h = shard_residual(h)  # sequence-parallel residual (no-op off-mesh)
            h, new_cache, aux = block_apply(
                p_layer, h, cfg, seg, positions, cache_layer, cache_index,
                a_fmt, enc_out)
            return (h, aux_acc + aux, None), new_cache

        init = (x, jnp.zeros((), jnp.float32), None)
        xs = (p_stack, caches)

    if remat:
        body = jax.checkpoint(body)

    # device ops under "layers" and in none of the body's scopes are the
    # scan's own traffic: each layer's weight slices (and, off the paged
    # path, cache slices) coming in, per-layer caches going back out
    with jax.named_scope("layers"):
        (x, aux, pools), new_caches = jax.lax.scan(body, init, xs)
    return x, aux, (pools if carried else new_caches)


def lm_forward(
    params,
    cfg,
    tokens,
    positions=None,
    embeds_prefix=None,
    caches=None,
    cache_index=None,
    a_fmt: Optional[str] = None,
    enc_out=None,
    remat: bool = False,
):
    """Returns (hidden (B, S, d), new_caches, aux).

    caches: list (one per segment) of stacked per-layer caches, or None.
    """
    from repro.runtime.kv_cache import PagedState

    with jax.named_scope("embed"):
        x = _embed_tokens(params, cfg, tokens, embeds_prefix)
    b, s = x.shape[:2]
    paged = isinstance(cache_index, PagedState)
    if positions is None:
        if paged and cache_index.prefill is not None:
            # mixed step: one fused batch-1 row = [one decode token per
            # slot | one bucketed prefill chunk]; positions follow suit —
            # each decode token sits at its slot's true length, chunk
            # token j at (chunk start + j)
            nd = cache_index.lengths.shape[0]
            positions = jnp.concatenate(
                [cache_index.lengths,
                 cache_index.prefill.lengths[0] + jnp.arange(s - nd)])[None]
        elif paged:  # per-row true lengths -> (B, S) positions (rope
            # broadcasts them; the synchronized-offset hack is gone)
            positions = cache_index.lengths[:, None] + jnp.arange(s)[None]
        else:
            offset = 0 if cache_index is None else cache_index
            positions = jnp.arange(s) + offset
    if cfg.pos_embedding == "learned":
        with jax.named_scope("embed"):
            if paged:
                x = x + jnp.take(params["pos_embed"], positions, axis=0).astype(x.dtype)
            else:
                x = x + jax.lax.dynamic_slice_in_dim(
                    params["pos_embed"], 0 if cache_index is None else cache_index,
                    s, axis=0,
                )[None].astype(x.dtype)

    segs = segments_for(cfg)
    new_caches = []
    aux_total = jnp.zeros((), jnp.float32)
    for i, seg in enumerate(segs):
        cache_i = None if caches is None else caches[i]
        x, aux, nc = _segment_scan(
            params["segments"][i], x, cfg, seg, positions, cache_i, cache_index,
            a_fmt, enc_out, remat,
        )
        aux_total = aux_total + aux
        new_caches.append(nc)
    with jax.named_scope("head"):
        x = norm(params["final_ln"], x, cfg.norm_kind, cfg.norm_eps)
    return x, (new_caches if caches is not None else None), aux_total


def init_lm_cache(cfg, batch: int, max_seq: int, enc_seq: int = 0):
    """Stacked caches per segment (leading dim = layer count)."""
    caches = []
    for seg in segments_for(cfg):
        one = init_block_cache(cfg, seg, batch, max_seq, enc_seq)
        caches.append(jax.tree.map(lambda a: jnp.broadcast_to(a, (seg.count,) + a.shape), one))
    return caches


def lm_logits(params, cfg, hidden):
    w = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    from .layers import accum_dtype

    return jax.lax.dot_general(
        hidden, w, (((hidden.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=accum_dtype(),
    ).astype(jnp.float32)
