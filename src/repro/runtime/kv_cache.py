"""Quantized paged KV-cache pool for the serving engine.

The KV cache is the largest *activation* tensor the server holds, and decode
attention's dominant memory term. Following the paper's finding that FP
formats beat INT for LLM activations, this module stores K/V as packed FP8
E4M3 codes in fixed-size pages with per-(page, head) scales constrained by
the M2 machinery (core.scales.constrain_scales_m2): each page keeps one
full-precision s_max plus integer pow-2 shifts per head, so the decode
kernel applies scales as an exponent add (kernels.common.decode_fp8) and
multiplies by s_max once per page. Halving KV bytes doubles the slot pool
for the same HBM.

Layout (one pool dict per model segment, leading dim = stacked layers):

  GQA:  k/v        (L, P+1, page, KV, hd)  uint8 codes (fp8) | bf16 values
        k/v_smax   (L, P+1)                f32   per-page full-precision S_max
        k/v_shift  (L, P+1, KV)            int32 pow-2 ratio exponents k_i
  MLA:  ckv        (L, P+1, page, r)   + smax/shift with a single "head"
        krope      (L, P+1, page, dr)    (the latent has no head axis)

Page ids are *global across layers*: page p of every layer belongs to the
same logical page, so one host-side free list serves the whole stack. The
last page id (index P) is a reserved null page — in-graph appends from
inactive batch rows are redirected there instead of corrupting a live page.

The ``(pool, layer)`` contract: the in-graph readers and writers below
(``append_paged``, ``append_prefill_chunk``, ``write_cross_pages``,
``gather_pages``, ``gather_history``, and the decode kernels behind
``kernels.ops``) take the whole stacked pool plus a layer index, and
address ``store[layer, pid]``. The pool rides the per-segment lax.scan's
carry, so a step moves only the pages it touches: slicing a layer out of
the pool and stacking it back would copy every layer's whole store twice
per step.

Mixed precision (``CachePolicy(frozen_fmt="fp4_e2m1")``): the pool grows a
dedicated *frozen region* — half-width packed FP4 E2M1 stores (``k_fz`` et
al., two codes per byte, own M2 scales) of ``n_frozen`` pages. Frozen
logical page ids share the active id space above it: id ``(P+1) + fidx``
addresses frozen row ``fidx`` (row ``n_frozen`` is a dummy for clamped
gathers). A page enters the region exactly once, by ``transcode_page`` at
the moment the prefix cache freezes it, and is read-only afterwards — the
decode kernels select the per-page decode path from the id class.


Write paths:
  * prefill splice (host-side, ``splice_prefill``): quantize the prompt's
    contiguous K/V page by page and scatter into the slot's allocated pages.
    The splice walks the prompt in ``chunk_pages`` groups so the f32
    staging transient is bounded by the chunk, not the prompt.
  * streaming prefill (in-graph, ``append_prefill_chunk``): the serving
    engine's chunked prefill writes each page-aligned chunk of prompt K/V
    straight into the pool from inside the forward — no contiguous
    max_seq scratch cache ever exists, so transient HBM tracks the chunk
    size and admission cost tracks the true prompt length.
  * decode append (in-graph, ``append_paged``): the touched page is
    gathered, dequantized, the new token written at its row's true offset,
    the page's per-head scales recomputed (amax -> M2), and the page
    re-encoded. With unchanged scales decode->encode is the identity on the
    FP8 grid, so requantization only rounds (once, <= 1/2 ulp) on the few
    steps where a page's amax actually grows.

``PagedState`` (page_table + per-slot true lengths) is the per-row cache
index that replaces the old scalar ``cache_index = max(lengths)`` masking
hack in the serving engine; models treat it as an opaque pytree.
"""
from __future__ import annotations

import dataclasses
import math
import zlib
from collections import OrderedDict
from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.formats import fp_encode, pack_nibbles, quantize_to_grid
from repro.core.scales import constrain_scales_m2
from repro.kernels.common import PageFormat, page_format

__all__ = [
    "CachePolicy",
    "PagedState",
    "PrefixCache",
    "page_key",
    "init_gqa_pool",
    "init_mla_pool",
    "init_cross_pool",
    "pool_keys",
    "pool_format",
    "frozen_format",
    "n_frozen_pages",
    "quantize_pages",
    "dequantize_pages",
    "transcode_page",
    "splice_prefill",
    "append_prefill_chunk",
    "write_cross_pages",
    "append_paged",
    "gather_pages",
    "gather_history",
    "gather_slabs",
    "scatter_slabs",
    "pool_bytes_per_token",
    "bf16_bytes_per_token",
    "page_bytes",
    "payload_checksum",
]

_EPS = 1e-12


@dataclasses.dataclass(frozen=True)
class CachePolicy:
    """The KV-cache precision policy — per page *class*, not one global knob.

    Replaces the flat ``kv_fmt: Optional[str]`` string (still accepted
    through a ``DeprecationWarning`` shim on ``ServerConfig``). Page classes:

    * ``active_fmt`` — pages any write path can still touch: private prompt
      pages, the decode-grown tail, the boundary page. Decode appends
      requantize these in-graph, so the format must be writable:
      ``None`` (bf16) or ``"fp8_e4m3"``.
    * ``frozen_fmt`` — pages the prefix cache has registered: shared-frozen,
      read-only for the rest of their lives. ``None`` inherits
      ``active_fmt``; ``"fp4_e2m1"`` (requires FP8 active pages) transcodes
      each page FP8 -> packed FP4 exactly once, at the freeze point —
      requantize-error accumulation never applies to a page that is never
      written again.
    * ``cross_fmt`` — enc-dec cross-attention pages, write-once at encode
      time (frozen from birth, so FP4 is safe here too). ``None`` inherits
      ``active_fmt``.

    ``frozen_pages`` sizes the dedicated frozen-page region when
    ``frozen_fmt`` differs from ``active_fmt`` (``None``: match the active
    pool size).
    """

    active_fmt: Optional[str] = None
    frozen_fmt: Optional[str] = None  # None = inherit active_fmt
    cross_fmt: Optional[str] = None  # None = inherit active_fmt
    frozen_pages: Optional[int] = None

    def __post_init__(self):
        if self.active_fmt not in (None, "fp8_e4m3"):
            raise ValueError(
                f"active_fmt={self.active_fmt!r}: active pages are "
                "requantized in-graph by decode appends, so only None (bf16) "
                "or 'fp8_e4m3' are writable")
        page_format(self.frozen_fmt)  # fail fast with the allowed set
        page_format(self.cross_fmt)
        if self.frozen_fmt is not None and self.frozen_fmt != self.active_fmt:
            if (self.active_fmt, self.frozen_fmt) != ("fp8_e4m3", "fp4_e2m1"):
                raise ValueError(
                    f"unsupported transcode {self.active_fmt!r} -> "
                    f"{self.frozen_fmt!r}: the only mixed-precision policy "
                    "is FP8 active pages with 'fp4_e2m1' frozen pages")
        if self.cross_fmt == "fp4_e2m1" and self.active_fmt != "fp8_e4m3":
            raise ValueError(
                "cross_fmt='fp4_e2m1' requires quantized (fp8_e4m3) active "
                "pages — a bf16 engine has no quantization calibration path")
        if self.frozen_pages is not None and self.frozen_pages < 1:
            raise ValueError(f"frozen_pages={self.frozen_pages}: must be >= 1")

    # -- resolved per-class formats (inheritance applied) --------------------
    @property
    def active(self) -> PageFormat:
        return page_format(self.active_fmt)

    @property
    def frozen(self) -> PageFormat:
        return page_format(self.frozen_fmt if self.frozen_fmt is not None
                           else self.active_fmt)

    @property
    def cross(self) -> PageFormat:
        return page_format(self.cross_fmt if self.cross_fmt is not None
                           else self.active_fmt)

    @property
    def mixed(self) -> bool:
        """True when frozen pages live in a separate (FP4) region."""
        return self.frozen != self.active


class PagedState(NamedTuple):
    """Per-row cache index for paged decode: which pages each slot owns and
    how many tokens it has really generated (no synchronized-length hack).

    The optional fields extend the same index to every decode family:
      * ``chunk_len`` — (1,) true token count of a *bucketed* streaming
        prefill chunk (the engine pads chunks to powers of two so jit trace
        count is O(log max_seq), not O(distinct lengths); positions >=
        chunk_len are pad and must be masked out of page writes/logits).
      * ``cross_table``/``enc_lengths`` — enc-dec decoders: page ids of the
        write-once cross-attention pages and the true encoder lengths.
      * ``slabs`` — recurrent families (SSM/xLSTM): per-row state-slab ids
        into the fixed-size slab pool (the last slab id is the reserved
        null slab, like the null page).
      * ``prefill`` — the *mixed engine step*: a nested batch-1 chunk state
        (page_table/lengths/chunk_len of one streaming-prefill chunk) that
        piggybacks on a decode step. The outer state indexes the decode
        rows; the fused token row is ``[decode tokens | chunk tokens]`` and
        the models split it at ``lengths.shape[0]``. The nested state never
        nests again (``prefill.prefill`` is always None).
    Unused fields stay ``None``; models treat the tuple as an opaque pytree.
    """

    page_table: jnp.ndarray  # (B, pages_per_slot) int32 page ids
    lengths: jnp.ndarray  # (B,) int32 true per-slot lengths
    chunk_len: Optional[jnp.ndarray] = None  # (1,) true prefill-chunk tokens
    cross_table: Optional[jnp.ndarray] = None  # (B, cross_pp) int32 page ids
    enc_lengths: Optional[jnp.ndarray] = None  # (B,) int32 encoder lengths
    slabs: Optional[jnp.ndarray] = None  # (B,) int32 state-slab ids
    prefill: Optional["PagedState"] = None  # mixed step: nested chunk state


def pool_keys(pool: Dict):
    """The value-bearing leaf names of a pool ('k'/'v' or 'ckv'/'krope')."""
    return ("k", "v") if "k" in pool else ("ckv", "krope")


def pool_format(pool: Dict) -> PageFormat:
    """The active-store PageFormat, recovered from the pool's leaves —
    jit-safe: only dtypes and leaf *names* are inspected (the zero-size
    ``_fp4`` marker leaf distinguishes packed FP4 from FP8, both uint8), so
    the answer is a trace constant."""
    first = pool[pool_keys(pool)[0]]
    if first.dtype != jnp.uint8:
        return page_format(None)
    return page_format("fp4_e2m1" if "_fp4" in pool else "fp8_e4m3")


def frozen_format(pool: Dict) -> Optional[PageFormat]:
    """The frozen-region PageFormat, or None when the pool is homogeneous
    (no dedicated ``*_fz`` store: frozen pages live in the active store)."""
    if any(name.endswith("_fz") for name in pool):
        return page_format("fp4_e2m1")
    return None


def n_frozen_pages(pool: Dict) -> int:
    """Frozen-region page count of a stacked pool (0 when homogeneous)."""
    for name, leaf in pool.items():
        if name.endswith("_fz"):
            return leaf.shape[1] - 1
    return 0


# ---------------------------------------------------------------------------
# Pool construction
# ---------------------------------------------------------------------------
def _init_store(n_layers, n_pages, page_size, n_kv, head_dim, fmt: PageFormat):
    p1 = n_pages + 1  # + reserved null page
    if not fmt.quantized:
        return {"_": jnp.zeros((n_layers, p1, page_size, n_kv, head_dim), jnp.bfloat16)}
    width = fmt.width(head_dim)  # packed FP4 stores two codes per byte
    return {
        "_": jnp.zeros((n_layers, p1, page_size, n_kv, width), jnp.uint8),
        "_smax": jnp.zeros((n_layers, p1), jnp.float32),
        "_shift": jnp.zeros((n_layers, p1, n_kv), jnp.int32),
    }


def _named(store, name):
    return {(name if k == "_" else name + k): v for k, v in store.items()}


def _frozen_suffix(suffix: str) -> str:
    # k -> k_fz, k_smax -> k_fz_smax: every frozen-store leaf name contains
    # "_fz", which is what the engine's spill/scrub leaf filters key on
    return "_fz" + suffix


def _finish_pool(pool: Dict, fmt: PageFormat, frozen_fmt, n_frozen: int,
                 mk_store) -> Dict:
    """Attach the marker/frozen leaves shared by every pool constructor."""
    if fmt.packed:
        # zero-size marker: the jit-safe static channel that tells readers
        # this uint8 store is packed FP4, not FP8 (see pool_format). Leading
        # dim matches the stacked layers, like every other pool leaf.
        n_layers = pool[pool_keys(pool)[0]].shape[0]
        pool["_fp4"] = jnp.zeros((n_layers, 0), jnp.uint8)
    frozen_fmt = page_format(frozen_fmt) if frozen_fmt is not None else None
    if frozen_fmt is not None and frozen_fmt != fmt:
        if (fmt.name, frozen_fmt.name) != ("fp8_e4m3", "fp4_e2m1"):
            raise ValueError(
                f"unsupported frozen store {frozen_fmt.name!r} behind "
                f"{fmt.name!r} active pages (only fp4_e2m1 behind fp8_e4m3)")
        if n_frozen < 1:
            raise ValueError("a mixed-precision pool needs n_frozen >= 1")
        pool.update(mk_store(frozen_fmt, n_frozen))
    return pool


def init_gqa_pool(n_layers, n_pages, page_size, n_kv, head_dim,
                  fmt=page_format("fp8_e4m3"), frozen_fmt=None,
                  n_frozen: int = 0) -> Dict:
    """``fmt``/``frozen_fmt`` take a PageFormat (format-name strings and
    None are coerced through :func:`kernels.common.page_format`, which
    fails fast on unknown names). A distinct ``frozen_fmt`` adds the
    dedicated frozen-page region: half-width packed ``k_fz``/``v_fz``
    stores of ``n_frozen`` pages (+1 dummy row for clamped gathers) that
    frozen prefix pages are transcoded into (see ``transcode_page``)."""
    fmt = page_format(fmt)

    def mk(f, n):
        out = {}
        for name in ("k", "v"):
            store = _init_store(n_layers, n, page_size, n_kv, head_dim, f)
            out.update(_named({_frozen_suffix(k) if k != "_" else "_fz": v
                               for k, v in store.items()}, name))
        return out

    pool = {}
    for name in ("k", "v"):
        pool.update(_named(_init_store(n_layers, n_pages, page_size, n_kv,
                                       head_dim, fmt), name))
    return _finish_pool(pool, fmt, frozen_fmt, n_frozen, mk)


def init_mla_pool(n_layers, n_pages, page_size, kv_lora_rank, qk_rope_dim,
                  fmt=page_format("fp8_e4m3"), frozen_fmt=None,
                  n_frozen: int = 0) -> Dict:
    """Latent pages: the compressed c_kv and the shared rope key, each with a
    single scale 'head' (squeezed out of the stored value leaves)."""
    fmt = page_format(fmt)

    def build(f, n, frozen):
        out = {}
        for name, dim in (("ckv", kv_lora_rank), ("krope", qk_rope_dim)):
            store = _init_store(n_layers, n, page_size, 1, dim, f)
            store["_"] = store["_"][:, :, :, 0]  # (L, P+1, page, dim)
            if frozen:
                store = {_frozen_suffix(k) if k != "_" else "_fz": v
                         for k, v in store.items()}
            out.update(_named(store, name))
        return out

    pool = build(fmt, n_pages, frozen=False)
    return _finish_pool(pool, fmt, frozen_fmt, n_frozen,
                        lambda f, n: build(f, n, frozen=True))


def init_cross_pool(n_layers, n_pages, page_size, n_kv, head_dim,
                    fmt=page_format("fp8_e4m3")) -> Dict:
    """Immutable cross-attention pages (enc-dec decoders).

    Same storage layout as a GQA pool — k/v codes + per-(page, head) M2
    scales — but with *write-once* semantics: the encoder runs exactly once
    per request, so a slot's cross pages are written in one shot at encode
    time (``write_cross_pages``) and never touched again. There is no
    append path for them: decode only ever reads (``ops.paged_decode_attn``
    with ``kv_lens = enc_lengths``), which is what lets the per-page scales
    stay frozen at their encode-time amax for the request's whole lifetime.
    """
    return init_gqa_pool(n_layers, n_pages, page_size, n_kv, head_dim, fmt)


# ---------------------------------------------------------------------------
# Page quantization (the M2 machinery applied per (page, head))
# ---------------------------------------------------------------------------
def quantize_pages(vals, fmt="fp8_e4m3"):
    """vals: (..., page, KV, hd) f32 -> (codes uint8, s_max (...,), shifts
    (..., KV)). Scales are amax/fmt_max per (page, head), M2-constrained
    across the page's heads: S_i = s_max * 2^-k_i. For a packed format
    (fp4_e2m1) the returned codes hold two per byte on the last dim
    (odd head dims pad one zero nibble)."""
    pf = page_format(fmt)
    grid = pf.fmt
    amax = jnp.max(jnp.abs(vals), axis=(-3, -1))  # (..., KV)
    raw = jnp.maximum(amax * jnp.float32(1.0 / grid.max_value), _EPS)
    # floor-rounded ratios: S_hat >= raw scale, so page content never
    # saturates (FP grids keep the same relative step one binade up)
    m2 = constrain_scales_m2(raw, group_axis=-1, rounding="floor")
    q = quantize_to_grid(vals / m2.scales[..., None, :, None], grid)
    codes = fp_encode(q, grid)
    if pf.packed:
        if codes.shape[-1] % 2:
            codes = jnp.pad(codes, ((0, 0),) * (codes.ndim - 1) + ((0, 1),))
        codes = pack_nibbles(codes)
    return codes, m2.s_max[..., 0], m2.shifts


def dequantize_pages(codes, s_max, shifts, fmt="fp8_e4m3",
                     d: Optional[int] = None):
    """Inverse: exponent-add shift apply + one s_max multiply per page.
    codes (..., page, KV, width); s_max (...,); shifts (..., KV) -> f32.
    ``d`` recovers the logical head dim after a packed nibble unpack
    (required for packed formats when the head dim is odd)."""
    pf = page_format(fmt)
    if d is None:
        d = codes.shape[-1] * (2 if pf.packed else 1)
    v = pf.decode(codes, shifts[..., None, :, None], d)
    return v * s_max[..., None, None, None]


def transcode_page(pool: Dict, src_pid: int, dst_fidx: int) -> Dict:
    """Re-encode one active-store page into the frozen (packed FP4) store.

    Runs host-side, exactly once per page, at the moment the prefix cache
    freezes it: dequantize the FP8 page (all stacked layers at once),
    requantize onto the FP4 E2M1 grid with fresh per-(page, head) M2 scales,
    pack two codes per byte, and write frozen row ``dst_fidx``. The source
    page is untouched (the caller releases it to the free list). Frozen
    pages are read-only for the rest of their lives, so this is the only
    writer of the ``*_fz`` leaves — requantize-error accumulation never
    applies."""
    fz = frozen_format(pool)
    assert fz is not None, "transcode_page on a pool without a frozen store"
    assert pool_format(pool).name == "fp8_e4m3", "transcode source must be FP8"
    out = dict(pool)
    for name in pool_keys(pool):
        store = pool[name]
        has_heads = store.ndim == 5  # (L, P+1, page, KV, hd) vs (L, P+1, page, d)
        codes = _with_head_axis(store[:, src_pid], has_heads)  # (L, page, KV|1, hd)
        smax = pool[name + "_smax"][:, src_pid]  # (L,)
        shifts = pool[name + "_shift"][:, src_pid]  # (L, KV|1)
        vals = dequantize_pages(codes, smax, shifts)
        ncodes, nsmax, nshift = quantize_pages(vals, fz)
        if not has_heads:
            ncodes = ncodes[..., 0, :]
        out[name + "_fz"] = out[name + "_fz"].at[:, dst_fidx].set(ncodes)
        out[name + "_fz_smax"] = out[name + "_fz_smax"].at[:, dst_fidx].set(nsmax)
        out[name + "_fz_shift"] = out[name + "_fz_shift"].at[:, dst_fidx].set(nshift)
    return out


# ---------------------------------------------------------------------------
# Prefill splice (host-side: runs once per admitted request)
# ---------------------------------------------------------------------------
def _with_head_axis(arr, has_heads: bool):
    return arr if has_heads else arr[..., None, :]


def splice_prefill(pool: Dict, prefill_cache: Dict, page_ids: np.ndarray,
                   n_tokens: int, chunk_pages: int = 8) -> Dict:
    """Quantize a batch-1 prefill's contiguous K/V into this slot's pages.

    prefill_cache: the segment cache from ``models.prefill`` — leaves
    (L, 1, max_seq, KV, hd) (GQA) or (L, 1, max_seq, dim) (MLA).
    page_ids: (n_pages_used,) page ids covering ``n_tokens`` (tail zero-pad).
    chunk_pages: staging granularity — pages are quantized ``chunk_pages``
    at a time, so the f32 staging copy never exceeds one chunk (a long
    prompt no longer spikes a prompt-sized transient).
    """
    pf = pool_format(pool)
    out = dict(pool)
    n_total = len(page_ids)
    for c0 in range(0, n_total, chunk_pages):
        ids_np = np.asarray(page_ids[c0: c0 + chunk_pages], np.int32)
        npg = len(ids_np)
        for name in pool_keys(pool):
            has_heads = pool[name].ndim == 5
            page = pool[name].shape[2]
            t0 = c0 * page
            # the reserved pages may overhang the prefill cache's max_seq
            # (when max_seq is not a page multiple): take what exists, pad
            src = prefill_cache[name][:, 0, t0: t0 + npg * page].astype(jnp.float32)
            short = npg * page - src.shape[1]
            if short > 0:
                src = jnp.pad(src, ((0, 0), (0, short)) + ((0, 0),) * (src.ndim - 2))
            if t0 + npg * page > n_tokens:  # zero the tail beyond the prompt
                # so page amax stays clean
                mask = (t0 + jnp.arange(npg * page) < n_tokens).astype(jnp.float32)
                src = src * mask.reshape((1, npg * page) + (1,) * (src.ndim - 2))
            src = _with_head_axis(src, has_heads)
            nl, kv, hd = src.shape[0], src.shape[-2], src.shape[-1]
            vals = src.reshape(nl, npg, page, kv, hd)
            ids = jnp.asarray(ids_np)
            if pf.quantized:
                codes, smax, shifts = quantize_pages(vals, pf)
                if not has_heads:
                    codes = codes[..., 0, :]
                out[name] = out[name].at[:, ids].set(codes)
                out[name + "_smax"] = out[name + "_smax"].at[:, ids].set(smax)
                out[name + "_shift"] = out[name + "_shift"].at[:, ids].set(shifts)
            else:
                store = vals if has_heads else vals[..., 0, :]
                out[name] = out[name].at[:, ids].set(store.astype(pool[name].dtype))
    return out


# ---------------------------------------------------------------------------
# Decode append (in-graph: runs inside the jitted decode step, per layer)
# ---------------------------------------------------------------------------
def append_paged(pool: Dict, layer, new_vals: Dict, state: PagedState) -> Dict:
    """Write one new token per batch row at its row's true position.

    pool: a whole stacked pool (leading L dim); ``layer``: the (traced)
    layer index to write. Only the touched pages of that layer are read and
    written (``store[layer, pid]``); the returned pool is the same stack.
    new_vals: {"k": (B, 1, KV, hd), "v": ...} or {"ckv": (B, 1, r), ...}.
    Rows with lengths == 0 (empty slots) are redirected to the null page.
    """
    pf = pool_format(pool)
    # the no-write-to-FP4 invariant, enforced at trace time: a packed page
    # is frozen by definition (transcoded exactly once, read-only after),
    # and requantizing through the 3-bit E2M1 grid would compound error
    assert not pf.packed, \
        "decode append must never target packed FP4 pages (frozen pages are read-only)"
    b = state.lengths.shape[0]
    out = dict(pool)
    rows = jnp.arange(b)
    for name in pool_keys(pool):
        store = pool[name]
        has_heads = store.ndim == 5  # (L, P+1, page, KV, hd) vs (L, P+1, page, dim)
        page = store.shape[2]
        null = store.shape[1] - 1
        slot = state.lengths // page
        off = state.lengths % page
        pid = jnp.take_along_axis(state.page_table, slot[:, None], axis=1)[:, 0]
        pid = jnp.where(state.lengths > 0, pid, null).astype(jnp.int32)
        # a row's tail page is always private (boundary pages never freeze),
        # so pid is always an active-store id even in a mixed-format pool;
        # clamp anyway so a violation cannot index out of bounds in-graph
        pid = jnp.minimum(pid, null)
        new = new_vals[name].astype(jnp.float32)[:, 0]  # (B, KV, hd) | (B, dim)
        new = _with_head_axis(new, has_heads)  # (B, KV|1, hd)
        if not pf.quantized:
            val = new if has_heads else new[:, 0]
            out[name] = store.at[layer, pid, off].set(val.astype(store.dtype))
            continue
        codes = _with_head_axis(store[layer, pid], has_heads)  # (B, page, KV|1, hd)
        smax = pool[name + "_smax"][layer, pid]  # (B,)
        shifts = pool[name + "_shift"][layer, pid]  # (B, KV|1)
        vals = dequantize_pages(codes, smax, shifts)
        vals = vals.at[rows, off].set(new)
        # zero page slots past this row's position: a recycled page may
        # carry a previous owner's stale codes, which must not leak into
        # the page amax (and so the scales) of its new owner. where(), not
        # multiply: 0 * NaN = NaN, and a stale non-finite code must not
        # survive the zeroing
        live = jnp.arange(page)[None, :] <= off[:, None]
        vals = jnp.where(live[:, :, None, None], vals, 0.0)
        ncodes, nsmax, nshift = quantize_pages(vals)
        if not has_heads:
            ncodes = ncodes[..., 0, :]
        out[name] = store.at[layer, pid].set(ncodes)
        out[name + "_smax"] = pool[name + "_smax"].at[layer, pid].set(nsmax)
        out[name + "_shift"] = pool[name + "_shift"].at[layer, pid].set(nshift)
    return out


def append_prefill_chunk(pool: Dict, layer, new_vals: Dict,
                         state: PagedState) -> Dict:
    """Write one page-aligned chunk of a (batch-1) streaming prefill.

    pool: a whole stacked pool (leading L dim); ``layer``: the layer index
    to write (only the chunk's pages of that layer move).
    new_vals: {"k": (1, S, KV, hd), ...} or {"ckv": (1, S, r), ...} — S
    prompt tokens starting at position ``state.lengths[0]``, which must be
    a page-size multiple (the engine feeds page-aligned chunks; only the
    final chunk of a prompt may be partial). The tail of a partial last
    page is zero-padded so the page amax stays clean; a later decode
    append at that offset requantizes the page exactly as usual.

    Unlike ``splice_prefill`` this runs *inside* the jitted chunk forward:
    the prompt's K/V never exists as a contiguous max_seq scratch cache —
    transient memory is bounded by the chunk, and the pages written here
    are immediately the attention source for the next chunk.

    When ``state.chunk_len`` is set, the chunk was padded to a power-of-two
    bucket: positions >= chunk_len carry pad-token K/V and are zeroed here
    so they cannot leak into the page amax (and so the scales). Pages the
    pad region overhangs must point at the null page in ``page_table``.
    """
    pf = pool_format(pool)
    out = dict(pool)
    start = state.lengths[0]
    for name in pool_keys(pool):
        store = pool[name]
        has_heads = store.ndim == 5  # (L, P+1, page, KV, hd) vs (L, P+1, page, dim)
        page = store.shape[2]
        new = new_vals[name].astype(jnp.float32)[0]  # (S, KV, hd) | (S, dim)
        s = new.shape[0]
        if state.chunk_len is not None:  # zero the pad tail of a bucketed chunk
            # where(), not multiply: pad-position K/V sits downstream of the
            # real chunk through attention, so a non-finite activation in
            # the chunk makes the pad values NaN — and 0 * NaN = NaN. The
            # pad tail overhangs into the shared null page, which the mixed
            # engine's decode lanes read in the same fused program; the
            # zeroing must hold even for non-finite input
            live = jnp.arange(s) < state.chunk_len[0]
            new = jnp.where(live.reshape((s,) + (1,) * (new.ndim - 1)),
                            new, 0.0)
        npg = -(-s // page)
        pad = npg * page - s
        if pad:
            new = jnp.pad(new, ((0, pad),) + ((0, 0),) * (new.ndim - 1))
        new = _with_head_axis(new, has_heads)  # (npg * page, KV|1, hd)
        vals = new.reshape(npg, page, new.shape[-2], new.shape[-1])
        pid = jax.lax.dynamic_slice_in_dim(
            state.page_table[0], start // page, npg)
        # prefill writes only ever target private (active-class) pages; in
        # a mixed pool any frozen id here would be a bug — clamp to the
        # null page so it cannot index out of bounds in-graph (the engine's
        # assert_unfrozen catches the bug host-side)
        pid = jnp.minimum(pid, store.shape[1] - 1)
        if pf.quantized:
            codes, smax, shifts = quantize_pages(vals, pf)
            if not has_heads:
                codes = codes[..., 0, :]
            out[name] = store.at[layer, pid].set(codes)
            out[name + "_smax"] = pool[name + "_smax"].at[layer, pid].set(smax)
            out[name + "_shift"] = pool[name + "_shift"].at[layer, pid].set(shifts)
        else:
            stv = vals if has_heads else vals[..., 0, :]
            out[name] = store.at[layer, pid].set(stv.astype(store.dtype))
    return out


def write_cross_pages(pool: Dict, layer, new_vals: Dict,
                      cross_table: jnp.ndarray) -> Dict:
    """Write one layer's encoder-derived cross K/V into its (write-once)
    cross pages, in one shot at encode time.

    pool: a whole stacked ``init_cross_pool`` pool; ``layer``: the decoder
    layer whose pages are written.
    new_vals: {"k": (1, T_enc, KV, hd), "v": ...} — the full encoder
    sequence. cross_table: (1, cross_pp) page ids covering T_enc (tail
    entries past ceil(T_enc / page) are never written).

    This is the *only* writer of cross pages: decode never appends to them,
    so the per-(page, head) M2 scales computed here are final.
    """
    state = PagedState(cross_table, jnp.zeros((1,), jnp.int32))
    return append_prefill_chunk(pool, layer, new_vals, state)


# ---------------------------------------------------------------------------
# State slabs (SSM / xLSTM recurrent state)
# ---------------------------------------------------------------------------
def gather_slabs(pool_layer, slab_ids):
    """Recurrent-state read for one layer: slab-pool leaves (S+1, ...) ->
    per-row state (B, ...). ``slab_ids``: (B,) int32; the last slab (index
    S) is the reserved null slab inactive rows point at.

    A slab is the fixed-size analogue of a page for families whose decode
    state does not grow with context (SSM state + conv tail, xLSTM
    (c, n, m) cells): one slab per running request, allocated at admission,
    steal/spill-able like pages — just never grown."""
    return jax.tree.map(lambda a: a[slab_ids], pool_layer)


def scatter_slabs(pool_layer, slab_ids, new_rows):
    """Recurrent-state write-back: scatter each row's updated state into
    its slab. Rows sharing the null slab overwrite each other there —
    harmless by construction (the null slab is never read as live state)."""
    return jax.tree.map(
        lambda full, row: full.at[slab_ids].set(row.astype(full.dtype)),
        pool_layer, new_rows)


def gather_pages(pool: Dict, layer, name: str, state: PagedState):
    """Dequantized gather for the jnp paths: (B, PP * page, KV, hd) f32 for
    GQA leaves, (B, PP * page, dim) for MLA leaves, read from layer
    ``layer`` of the stacked ``pool`` (only the table's pages move).

    Mixed-format pools: table entries ``>= P+1`` are frozen-region logical
    ids (``base + fidx``). Both regions are gathered with clamped indices
    (frozen ids clamp to the null page in the active store and vice versa)
    and the per-page format select is a ``where`` on the id class — the same
    dataflow the Pallas kernels implement with a prefetched frozen mask."""
    store = pool[name]
    pf = pool_format(pool)
    fz = frozen_format(pool)
    has_heads = store.ndim == 5
    page = store.shape[2]
    b, pp = state.page_table.shape
    pt = state.page_table
    base = store.shape[1]  # P+1: first frozen logical id
    apt = jnp.minimum(pt, base - 1) if fz is not None else pt
    pages = store[layer, apt]  # (B, PP, page, ...)
    if pf.quantized:
        smax = pool[name + "_smax"][layer, apt]  # (B, PP)
        shifts = pool[name + "_shift"][layer, apt]  # (B, PP, KV|1)
        d = store.shape[-1] * (2 if pf.packed else 1)
        vals = dequantize_pages(_with_head_axis(pages, has_heads), smax,
                                shifts, pf, d=d)
        if not has_heads:
            vals = vals[..., 0, :]
    else:
        vals = pages.astype(jnp.float32)
    if fz is not None:
        fstore = pool[name + "_fz"]
        fpt = jnp.clip(pt - base, 0, fstore.shape[1] - 1)
        fsmax = pool[name + "_fz_smax"][layer, fpt]
        fshift = pool[name + "_fz_shift"][layer, fpt]
        fvals = dequantize_pages(_with_head_axis(fstore[layer, fpt], has_heads),
                                 fsmax, fshift, fz, d=store.shape[-1])
        if not has_heads:
            fvals = fvals[..., 0, :]
        frozen = (pt >= base).reshape(b, pp, *([1] * (vals.ndim - 2)))
        vals = jnp.where(frozen, fvals, vals)
    return vals.reshape(b, pp * page, *vals.shape[3:])


def gather_history(pool: Dict, layer, state: PagedState, chunk_len: int):
    """History gather for a streaming-prefill chunk (the shared page math
    for the GQA and MLA model glue — keep it in one place), from layer
    ``layer`` of the stacked ``pool``.

    The chunk starts page-aligned at ``state.lengths[0]``, so every token
    of the gather below that (dynamic) position is fully-packed history:
    token i sits at absolute position i. The *whole* (engine-trimmed or
    power-of-two-bucketed) table is gathered — including the chunk's own
    just-written pages and any null-page fill — and the caller masks
    columns ``>= lengths[0]``: those positions are covered exactly by the
    chunk's inline K/V (no early FP8 round trip) or are pad. Returns
    ``({name: (B, W * page, ...)}, W * page)``, or ``({}, 0)`` when the
    table is no wider than the chunk itself (prompt fits one chunk,
    nothing could be history).
    """
    page = pool[pool_keys(pool)[0]].shape[2]
    if state.page_table.shape[1] <= -(-chunk_len // page):
        return {}, 0
    return ({name: gather_pages(pool, layer, name, state)
             for name in pool_keys(pool)},
            state.page_table.shape[1] * page)


# ---------------------------------------------------------------------------
# Content-addressed shared-prefix cache (host-side index over frozen pages)
# ---------------------------------------------------------------------------
_PREFIX_ROOT = -1  # the parent node id of every depth-0 page


def page_key(parent: int, tokens: Sequence[int]) -> Tuple:
    """Content address of one *full* page: the page's token ids chained on
    the parent page's *node id* (an integer assigned at registration and
    never reissued), so the key identifies the whole prefix up to and
    including this page — two identical token windows at different depths,
    or under different histories, never collide. Keys are exact token
    tuples, not hashes, so there is no collision risk; the integer parent
    keeps each dict lookup O(page_size) instead of re-hashing the whole
    ancestor chain (a nested-tuple parent would make a d-page walk
    O(d^2 * page_size))."""
    return (parent, tuple(int(t) for t in tokens))


class PrefixCache:
    """Host-side radix index over *full, scale-frozen* KV pages.

    ZeroQuant-FP's scaling constraints make a full FP8 page an immutable,
    self-contained block: once the prefill stream (or the last decode
    append that filled it) has passed a page, its per-(page, head) M2
    scales are frozen at amax and the codes are never requantized again.
    That makes the page content a pure function of its token-id prefix, so
    full pages are content-addressable: requests sharing a prompt prefix
    (system prompts, few-shot headers) can map the same physical pages
    instead of re-prefilling and re-quantizing identical K/V.

    The index maps ``page_key(parent, tokens)`` -> page id, one entry per
    registered page (and one key per page: a page holds exactly one
    content). Ownership/refcounts live in the serving engine; the cache
    additionally tracks the **reusable LRU** — registered pages whose
    refcount dropped to zero. Those stay bit-reusable (a later request with
    the same prefix re-acquires them for free) until the allocator
    *reclaims* them, oldest-first, which drops the index entry and hands
    the physical page back as a blank. Reclaiming a mid-chain page strands
    its descendants (the walk can no longer reach them) — they simply age
    out of the LRU in turn.
    """

    def __init__(self, page_size: int):
        self.page_size = page_size
        # key -> (pid, node id). The node id stands in for the full chain
        # as the parent component of children's keys; it is monotonically
        # assigned and never reissued, so a reclaimed page's stranded
        # descendants can never be re-attached under recycled-pid content
        self._by_key: Dict[Tuple, Tuple[int, int]] = {}
        self._by_pid: Dict[int, Tuple] = {}
        # refcount-0 registered pages, oldest-parked first
        self._lru: "OrderedDict[int, None]" = OrderedDict()
        self._next_node = 0
        # conditioning-digest -> synthetic root node id. Requests whose KV
        # depends on more than the token ids (enc-dec: cross-attention makes
        # decoder K/V a function of the *encoder frames* too) chain off a
        # per-digest root instead of _PREFIX_ROOT, so identical decoder
        # prompts under different audio never alias (node ids are unique)
        self._roots: Dict[str, int] = {}
        self.reclaims = 0

    def root_for(self, digest: str) -> int:
        """Radix root node for an extra conditioning digest (e.g. a hash of
        the encoder frames). Monotonic node ids, memoized per digest —
        walks/inserts under the same digest share a chain, different
        digests get disjoint chains by construction."""
        root = self._roots.get(digest)
        if root is None:
            root = self._next_node
            self._next_node += 1
            self._roots[digest] = root
        return root

    def __len__(self) -> int:
        return len(self._by_key)

    @property
    def n_reusable(self) -> int:
        """Registered pages at refcount 0 — allocatable without stealing."""
        return len(self._lru)

    def reusable_ids(self) -> List[int]:
        """The parked refcount-0 page ids, oldest first (LRU order)."""
        return list(self._lru)

    def registered(self, pid: int) -> bool:
        return int(pid) in self._by_pid

    def walk(self, tokens: Sequence[int], max_pages: Optional[int] = None,
             root: int = _PREFIX_ROOT) -> List[int]:
        """Longest chain of consecutive full-page hits for this token
        prefix, from ``root`` (the global root, or a ``root_for`` node for
        digest-conditioned requests): returns the page ids holding
        ``tokens[:len(hits) * page_size]``. ``max_pages`` caps the walk
        (the engine always leaves at least the last context token to the
        prefill stream, so admission caps at ``(len - 1) // page_size``)."""
        page = self.page_size
        limit = len(tokens) // page
        if max_pages is not None:
            limit = min(limit, max_pages)
        pids: List[int] = []
        parent = root
        for i in range(limit):
            key = page_key(parent, tokens[i * page: (i + 1) * page])
            hit = self._by_key.get(key)
            if hit is None:
                break
            pids.append(hit[0])
            parent = hit[1]
        return pids

    def insert(self, tokens: Sequence[int], pids: Sequence[int],
               root: int = _PREFIX_ROOT) -> List[int]:
        """Register the full pages covering ``tokens[:len(pids) * page]``
        (``pids[i]`` holds page ``i``'s frozen content), chained off
        ``root``. Returns the *canonical* pid per page: where the chain key
        already exists (an identical prefix was registered first), the
        existing page wins and the caller is expected to adopt it —
        releasing its duplicate — which keeps every slot's shared pages one
        contiguous leading run."""
        page = self.page_size
        out: List[int] = []
        parent = root
        for i, pid in enumerate(pids):
            pid = int(pid)
            key = page_key(parent, tokens[i * page: (i + 1) * page])
            cur = self._by_key.get(key)
            if cur is None:
                assert pid not in self._by_pid, \
                    f"page {pid} already registered under another prefix"
                cur = (pid, self._next_node)
                self._next_node += 1
                self._by_key[key] = cur
                self._by_pid[pid] = key
            out.append(cur[0])
            parent = cur[1]
        return out

    def park(self, pid: int):
        """A registered page's refcount hit zero: keep it bit-reusable in
        the LRU instead of freeing it (reclaim drains oldest-first)."""
        pid = int(pid)
        assert pid in self._by_pid, f"parking unregistered page {pid}"
        self._lru[pid] = None
        self._lru.move_to_end(pid)

    def unpark(self, pid: int):
        """A parked page was re-acquired (refcount 0 -> 1 via a hit)."""
        self._lru.pop(int(pid), None)

    def reclaim(self) -> Optional[int]:
        """Hand the least-recently-used refcount-0 page back to the
        allocator as a blank: drop its index entry (the content is gone for
        sharing purposes) and return the pid. None when nothing is
        parked."""
        if not self._lru:
            return None
        pid, _ = self._lru.popitem(last=False)
        key = self._by_pid.pop(pid)
        del self._by_key[key]
        self.reclaims += 1
        return pid

    def assert_unfrozen(self, page_ids: Iterable[int],
                        frozen_base: Optional[int] = None):
        """Frozen-page invariant: a registered page is shared-frozen —
        content-addressed and possibly mapped by several slots — so no
        write path (prefill chunk, decode append, spill restore) may ever
        target it. The serving engine checks every write set against this
        before issuing the write.

        ``frozen_base`` extends the check to the page *format*: in a
        mixed-precision pool every id >= base addresses the packed FP4
        frozen region, whose pages are read-only from the moment they are
        transcoded — a write there is a format violation even if the index
        entry has since been reclaimed."""
        for pid in page_ids:
            pid = int(pid)
            if frozen_base is not None and pid >= frozen_base:
                raise AssertionError(
                    f"write targets frozen FP4 page {pid} (>= frozen base "
                    f"{frozen_base}): packed FP4 pages are transcoded once "
                    "at freeze time and never written again")
            if pid in self._by_pid:
                raise AssertionError(
                    f"write targets shared-frozen page {pid}: frozen "
                    "pages are immutable (copy-on-write means the boundary "
                    "page must be private)")


# ---------------------------------------------------------------------------
# Accounting
# ---------------------------------------------------------------------------
def pool_bytes_per_token(pool: Dict) -> float:
    """Bytes of *active-store* storage per token slot (all value + scale
    leaves, across the stacked layers), excluding the reserved null page.
    The dedicated frozen region (``*_fz`` leaves) is a separate residency
    pool with its own page count — see ``page_bytes`` for the per-class
    figure the engine's residency accounting is built on."""
    first = pool[pool_keys(pool)[0]]
    n_layers, p1, page = first.shape[:3]
    tokens = (p1 - 1) * page
    total = 0
    for name, leaf in pool.items():
        if "_fz" in name or leaf.size == 0:
            continue
        frac = (leaf.shape[1] - 1) / leaf.shape[1]
        total += leaf.size * leaf.dtype.itemsize * frac
    return total / tokens


def bf16_bytes_per_token(pool: Dict) -> float:
    """What the same pool geometry would cost holding bf16 values (the
    monolithic-cache baseline the fp8 pool replaces)."""
    total = 0
    for name in pool_keys(pool):
        leaf = pool[name]
        per_tok = int(np.prod(leaf.shape[3:])) * leaf.shape[0]  # feat x layers
        total += per_tok * 2
    return float(total)


def page_bytes(pool: Dict, frozen: bool = False) -> float:
    """Bytes one page costs across the stacked layers (values + scales) in
    the requested class: the active store (``frozen=False``) or the packed
    frozen region (``frozen=True``, 0.0 when the pool is homogeneous). The
    building block of the engine's residency accounting — a mixed pool's
    live bytes are ``n_active_live * page_bytes(pool) + n_frozen_live *
    page_bytes(pool, frozen=True)``."""
    total = 0.0
    for name, leaf in pool.items():
        if leaf.size == 0 or ("_fz" in name) != frozen:
            continue
        axis = 1 if leaf.ndim >= 2 else 0
        total += leaf.size * leaf.dtype.itemsize / leaf.shape[axis]
    return total


def pages_needed(n_tokens: int, page_size: int) -> int:
    return max(1, math.ceil(n_tokens / page_size))


def payload_checksum(payload: List[Dict[str, np.ndarray]]) -> int:
    """CRC32 over a spill payload (the per-unit leaf dicts ``_preempt``
    builds: codes + scales + recurrent state). Leaf names are folded into
    the checksum in sorted order so the value is independent of dict
    insertion order; computed at preemption on the pristine host bytes and
    re-verified before a resume commits, so bit rot while spilled is
    caught instead of silently restored into the pool."""
    crc = 0
    for part in payload:
        for name in sorted(part):
            arr = np.ascontiguousarray(part[name])
            crc = zlib.crc32(name.encode(), crc)
            crc = zlib.crc32(arr.tobytes(), crc)
    return crc
