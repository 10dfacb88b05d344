"""Zamba2-style hybrid: Mamba2 backbone + one SHARED attention+MLP block
applied every `ssm.attn_every` layers (weight sharing is Zamba's signature —
the shared block's parameters are reused at every invocation, but each
invocation has its own KV cache because its inputs differ by depth).

Implementation: lax.scan over the stacked mamba2 layers; inside the body a
lax.cond fires the shared block when (layer_index % attn_every == 0). The
shared block's KV caches are stacked (n_invocations, ...) and indexed by
invocation = layer_index // attn_every.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.runtime.kv_cache import PagedState, gather_slabs, scatter_slabs

from .attention import attention, attn_params, init_kv_cache
from .layers import ParamDef, mlp, mlp_params, norm, norm_params, shard_residual
from .ssm import init_mamba2_cache, mamba2_block, mamba2_params
from .transformer import _stack_defs, lm_logits

__all__ = ["build_hybrid", "hybrid_forward", "init_hybrid_cache", "n_attn_invocations"]


def n_attn_invocations(cfg) -> int:
    k = cfg.ssm.attn_every
    return 0 if not k else -(-cfg.n_layers // k)


def build_hybrid(cfg):
    d, dt = cfg.d_model, cfg.param_dtype
    p = {
        "embed": ParamDef((cfg.vocab_size, d), ("vocab", "embed"), dt, "embed"),
        "mamba": _stack_defs(
            {"ln": norm_params(cfg), "mamba": mamba2_params(cfg)}, cfg.n_layers
        ),
        "final_ln": norm_params(cfg),
    }
    if cfg.ssm.attn_every:
        p["shared"] = {
            "ln1": norm_params(cfg),
            "attn": attn_params(cfg),
            "ln2": norm_params(cfg),
            "mlp": mlp_params(cfg),
        }
    if not cfg.tie_embeddings:
        p["lm_head"] = ParamDef((cfg.vocab_size, d), ("vocab", "embed"), dt, "embed")
    return p


def _shared_block(p, x, cfg, positions, kv_cache, cache_index, a_fmt,
                  layer=None):
    h, new_kv = attention(
        p["attn"], norm(p["ln1"], x, cfg.norm_kind, cfg.norm_eps), cfg, positions,
        kv_cache=kv_cache, cache_index=cache_index, a_fmt=a_fmt, layer=layer,
    )
    x = x + h
    x = x + mlp(p["mlp"], norm(p["ln2"], x, cfg.norm_kind, cfg.norm_eps), cfg, a_fmt=a_fmt)
    return x, new_kv


def hybrid_forward(
    params,
    cfg,
    tokens,
    caches=None,
    cache_index=None,
    a_fmt: Optional[str] = None,
    remat: bool = False,
):
    """Returns (hidden, new_caches, aux). caches = {'mamba': stacked ssm
    caches, 'shared_kv': (n_inv, B, S, kv, hd) x2} or None.

    Paged engine (``cache_index`` is a PagedState): 'mamba' leaves are
    slab-pooled — (L, n_slabs + 1, ...), gathered per row by
    ``cache_index.slabs`` — and 'shared_kv' is a paged KV pool with the
    invocation index in place of the layer axis ((n_inv, P+1, page, kv,
    hd) + scale leaves), so the shared block's per-invocation caches ride
    the same page table as any GQA layer stack. The pool rides the scan's
    carry whole and each invocation addresses it as ``(pool, inv)``, like
    a GQA segment's layers (``transformer._segment_scan``)."""
    b, s = tokens.shape
    paged = isinstance(cache_index, PagedState)
    if paged:
        positions = cache_index.lengths[:, None] + jnp.arange(s)[None]
    else:
        offset = 0 if cache_index is None else cache_index
        positions = jnp.arange(s) + offset
    x = jnp.take(params["embed"], tokens, axis=0)

    every = cfg.ssm.attn_every
    shared_p = params.get("shared")

    def body(carry, layer_in):
        h, shared_kv = carry
        (p_layer, mcache_pool), li = layer_in
        mcache = mcache_pool
        if paged and mcache_pool is not None:
            mcache = gather_slabs(mcache_pool, cache_index.slabs)
        h = shard_residual(h)  # sequence-parallel residual (no-op off-mesh)

        if shared_p is not None:

            def with_attn(h, shared_kv):
                inv = li // every
                if paged:  # the stacked page pool, addressed in place
                    return _shared_block(shared_p, h, cfg, positions,
                                         shared_kv, cache_index, a_fmt,
                                         layer=inv)
                if shared_kv is not None:
                    kv_i = jax.tree.map(lambda c: c[inv], shared_kv)
                else:
                    kv_i = None
                h2, new_kv = _shared_block(
                    shared_p, h, cfg, positions, kv_i, cache_index, a_fmt
                )
                if shared_kv is not None:
                    shared_kv = jax.tree.map(
                        lambda full, one: jax.lax.dynamic_update_index_in_dim(
                            full, one.astype(full.dtype), inv, 0
                        ),
                        shared_kv,
                        new_kv,
                    )
                return h2, shared_kv

            def without_attn(h, shared_kv):
                return h, shared_kv

            h, shared_kv = jax.lax.cond(
                li % every == 0, with_attn, without_attn, h, shared_kv
            )

        dh, new_m = mamba2_block(
            p_layer["mamba"], norm(p_layer["ln"], h, cfg.norm_kind, cfg.norm_eps), cfg,
            cache=mcache, a_fmt=a_fmt,
        )
        h = h + dh
        if paged and new_m is not None:
            new_m = scatter_slabs(mcache_pool, cache_index.slabs, new_m)
        return (h, shared_kv), new_m

    if remat:
        body = jax.checkpoint(body)

    mamba_caches = None if caches is None else caches["mamba"]
    shared_kv0 = None if caches is None else caches["shared_kv"]
    (x, shared_kv_f), new_mamba = jax.lax.scan(
        body, (x, shared_kv0), ((params["mamba"], mamba_caches), jnp.arange(cfg.n_layers))
    )
    x = norm(params["final_ln"], x, cfg.norm_kind, cfg.norm_eps)
    new_caches = None
    if caches is not None:
        new_caches = {"mamba": new_mamba, "shared_kv": shared_kv_f}
    return x, new_caches, jnp.zeros((), jnp.float32)


def init_hybrid_cache(cfg, batch: int, max_seq: int):
    one_m = {"_": init_mamba2_cache(cfg, batch)}
    mamba = jax.tree.map(lambda a: jnp.broadcast_to(a, (cfg.n_layers,) + a.shape), one_m["_"])
    c = {"mamba": mamba}
    n_inv = n_attn_invocations(cfg)
    if n_inv:
        kv = init_kv_cache(batch, max_seq, cfg.n_kv_heads, cfg.resolved_head_dim)
        c["shared_kv"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (n_inv,) + a.shape), kv
        )
    return c
