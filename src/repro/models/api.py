"""Model API — family dispatch for build / forward / prefill / decode / loss.

This is the single surface the launcher, PTQ driver, dry-run and tests use.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from . import encdec as _encdec
from . import hybrid as _hybrid
from . import transformer as _tf
from .losses import chunked_xent, mtp_loss
from .params import init_tree, pspec_tree, shape_tree

__all__ = [
    "build_def",
    "init_params",
    "param_shapes",
    "param_pspecs",
    "loss_fn",
    "prefill",
    "decode_step",
    "encode_cross_pages",
    "forward_hidden",
    "init_cache",
]


def encode_cross_pages(params, cfg, frames, caches, cross_table, a_fmt=None):
    """Enc-dec admission step: run the encoder once and write every decoder
    layer's cross K/V into its write-once cross pages (see encdec module)."""
    return _encdec.encode_cross_pages(params, cfg, frames, caches,
                                      cross_table, a_fmt=a_fmt)


def _is_encdec(cfg) -> bool:
    return cfg.encoder_layers > 0


def _is_hybrid(cfg) -> bool:
    return cfg.ssm is not None and cfg.ssm.kind == "mamba2" and cfg.family == "hybrid"


def build_def(cfg):
    if _is_encdec(cfg):
        return _encdec.build_encdec(cfg)
    if _is_hybrid(cfg):
        return _hybrid.build_hybrid(cfg)
    return _tf.build_lm(cfg)


def init_params(cfg, rng):
    return init_tree(build_def(cfg), rng)


def param_shapes(cfg):
    return shape_tree(build_def(cfg))


def param_pspecs(cfg, rules=None, mesh=None):
    from .params import DEFAULT_RULES

    return pspec_tree(build_def(cfg), rules or DEFAULT_RULES, mesh)


def _head_w(params, cfg):
    return params["embed"] if (cfg.tie_embeddings or "lm_head" not in params) else params["lm_head"]


def forward_hidden(params, cfg, batch, a_fmt=None, remat=False, caches=None, cache_index=None):
    """Full forward to final hidden states. Returns (hidden, new_caches, aux)."""
    if _is_encdec(cfg):
        enc = _encdec.encode(params, cfg, batch["frames"], a_fmt=a_fmt, remat=remat)
        return _encdec.encdec_forward(
            params, cfg, batch["tokens"], enc, caches=caches, cache_index=cache_index,
            a_fmt=a_fmt, remat=remat,
        )
    if _is_hybrid(cfg):
        return _hybrid.hybrid_forward(
            params, cfg, batch["tokens"], caches=caches, cache_index=cache_index,
            a_fmt=a_fmt, remat=remat,
        )
    prefix = batch.get("patches")
    if prefix is None:
        prefix = batch.get("frames_prefix")
    return _tf.lm_forward(
        params, cfg, batch["tokens"], embeds_prefix=prefix,
        caches=caches, cache_index=cache_index, a_fmt=a_fmt, remat=remat,
    )


def loss_fn(params, cfg, batch, a_fmt=None, remat=True, aux_weight=0.01, mtp_weight=0.0):
    """Scalar training loss (+ metrics dict)."""
    hidden, _, aux = forward_hidden(params, cfg, batch, a_fmt=a_fmt, remat=remat)
    labels = batch["labels"]
    if hidden.shape[1] != labels.shape[1]:  # vision/audio prefix tokens carry no loss
        hidden = hidden[:, hidden.shape[1] - labels.shape[1] :]
    loss, n_tok = chunked_xent(hidden, _head_w(params, cfg), labels, mask=batch.get("mask"))
    total = loss + aux_weight * aux
    metrics = {"nll": loss, "aux": aux, "tokens": n_tok}
    if mtp_weight and cfg.mtp_depth and "mtp" in params:
        seg = _tf.segments_for(cfg)[-1]
        ml = mtp_loss(
            params, cfg, hidden, batch["tokens"], labels, seg, _tf.block_apply,
            _head_w(params, cfg),
        )
        total = total + mtp_weight * ml
        metrics["mtp"] = ml
    return total, metrics


def init_cache(cfg, batch: int, max_seq: int):
    if _is_encdec(cfg):
        return _encdec.init_encdec_cache(cfg, batch, max_seq)
    if _is_hybrid(cfg):
        return _hybrid.init_hybrid_cache(cfg, batch, max_seq)
    return _tf.init_lm_cache(cfg, batch, max_seq)


def prefill(params, cfg, batch, max_seq: int, a_fmt=None):
    """Run the prompt through the model, filling caches.
    Returns (last_token_logits, caches)."""
    caches = init_cache(cfg, batch["tokens"].shape[0], max_seq)
    hidden, caches, _ = forward_hidden(
        params, cfg, batch, a_fmt=a_fmt, caches=caches, cache_index=0
    )
    w = _head_w(params, cfg)
    from .layers import accum_dtype

    logits = jax.lax.dot_general(
        hidden[:, -1], w, (((1,), (1,)), ((), ())), preferred_element_type=accum_dtype()
    ).astype(jnp.float32)
    return logits, caches


def decode_step(params, cfg, tokens, caches, cache_index, a_fmt=None):
    """One serving step: tokens (B, 1) + caches at cache_index.
    Returns (logits (B, V), new_caches).

    ``cache_index`` is either a scalar int (legacy contiguous caches, one
    synchronized position for every row) or a runtime.kv_cache.PagedState
    (paged pool: per-row true lengths + page table — each row gets its own
    positions and length masks). A PagedState with ``chunk_len`` set is a
    bucketed streaming-prefill chunk: positions past chunk_len are pad, so
    the logits row is the last *true* token, not the last row. A PagedState
    with ``prefill`` set is a *mixed* engine step — tokens is the fused
    (1, slots + chunk) row and the logits come back (slots + 1, V): one row
    per decode slot plus the chunk's last true token."""
    from repro.runtime.kv_cache import PagedState

    batch = {"tokens": tokens}
    if _is_encdec(cfg):
        hidden, caches, _ = _encdec_decode(params, cfg, tokens, caches, cache_index, a_fmt)
    else:
        hidden, caches, _ = forward_hidden(
            params, cfg, batch, a_fmt=a_fmt, caches=caches, cache_index=cache_index
        )
    from .layers import accum_dtype

    with jax.named_scope("head"):
        if isinstance(cache_index, PagedState) and cache_index.prefill is not None:
            nd = cache_index.lengths.shape[0]
            h_pre = hidden[0, nd + cache_index.prefill.chunk_len[0] - 1]
            h_last = jnp.concatenate([hidden[0, :nd], h_pre[None]], axis=0)
        elif isinstance(cache_index, PagedState) and cache_index.chunk_len is not None:
            h_last = hidden[:, cache_index.chunk_len[0] - 1]
        else:
            h_last = hidden[:, -1]
        w = _head_w(params, cfg)
        logits = jax.lax.dot_general(
            h_last, w, (((1,), (1,)), ((), ())), preferred_element_type=accum_dtype()
        ).astype(jnp.float32)
    return logits, caches


def _encdec_decode(params, cfg, tokens, caches, cache_index, a_fmt):
    # decode uses cached cross-k/v (computed at prefill); enc_out unused
    return _encdec.encdec_forward(
        params, cfg, tokens, enc_out=None, caches=caches, cache_index=cache_index, a_fmt=a_fmt
    )
