"""Pallas TPU kernel: paged FP8/FP4 decode attention (flash-decoding dataflow).

Decode's dominant memory term is the KV-cache read; this kernel reads the
cache in its *deployed* form — packed FP8 E4M3 pages with per-(page, head)
M2 scales, plus (mixed-precision pools) a packed FP4 E2M1 frozen region —
and never materializes a dequantized cache in HBM:

  * the page table, per-row true lengths and the layer index ride in as
    scalar-prefetch operands (SMEM); each grid step's BlockSpec index_map
    *gathers* its page straight from the stacked pool via
    ``(layer, page_table[b, j])`` — the DMA engine fetches exactly the pages
    a row owns, in page-table order, and a lane-dense store is never sliced
    (a sliced operand would make XLA copy the layer's whole store); only a
    store narrower than a lane tile is sliced to the layer, since the TPU
    lays it out with another dim minor and XLA would otherwise relay the
    whole stack out for the kernel (see ``_code_operand``),
  * codes are dequantized in VMEM with the exponent-add scale apply
    (kernels.common.decode_fp8: per-head shift k is an integer add on the
    exponent; the full-precision s_max multiplies once per page),
  * online softmax (m, l, acc) accumulators live in VMEM scratch across the
    page loop (innermost grid dim), standard flash-decoding.

Formats ride in as one frozen ``PageFormat`` static per page class
(``fmt`` for the active store, ``frozen`` for the packed FP4 region) —
coerced through :func:`kernels.common.page_format`, which fails fast with
the allowed set instead of letting an unknown string surface as an opaque
``KeyError`` mid-trace. With ``frozen`` set, the per-page format select is
driven by the scalar-prefetched page table itself: logical ids >= the
active row count address the frozen store, so the index maps gather *both*
candidate pages with clamped indices and the kernel body selects the
decoded block by id class — no extra mask operand, no divergent grid.

Grid: (B, KV_heads, pages_per_slot). The g = H/KV query heads of a KV group
are processed together as the row block (padded to ``bq`` for VPU/MXU
tiling — the autotuner's block size for this kernel). Rows past a slot's
true length are masked by position, so per-slot lengths need no host-side
synchronization (this is what retires the engine's max-length hack).

On a serving mesh the kernel is *oblivious* to tensor parallelism: the
engine's shard_map wrapper (kernels.ops.paged_decode_attn) hands each
model-axis shard a contiguous KV-head block of the pool (codes and their
per-(page, head) shift scales co-sharded on the head dim; per-page s_max
replicated) plus the matching contiguous q-head block — GQA's g = H/KV
grouping is preserved locally because both head counts divide the axis —
so the kernel body, grid and index maps are identical per shard, just with
KV/m heads. MLA's latent pages have no head axis and stay replicated; its
wrapper shards the absorbed q heads only.

The jnp oracle is kernels.ref.paged_decode_attn_ref; interpret-mode parity
is asserted by tests/test_kv_cache.py (FP8 tier) and tests/test_fp4_cache.py
(packed FP4 tier).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import decode_fp8, page_format

__all__ = ["paged_decode_attn_pallas", "paged_mla_decode_attn_pallas"]

_NEG_INF = -1e30


def _split_pair(a, width: int):
    """(…, d) -> the even and odd elements, each zero-padded to ``width``
    (the packed FP4 store's byte count): operands that contract against the
    low and high nibbles of a packed page without interleaving them."""
    pad = 2 * width - a.shape[-1]
    if pad:
        a = jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, pad)])
    return a[..., 0::2], a[..., 1::2]


def _join_pair(lo, hi, d: int):
    """Inverse of :func:`_split_pair`: interleave and trim to ``d``."""
    out = jnp.stack([lo, hi], axis=-1)
    return out.reshape(*lo.shape[:-1], 2 * lo.shape[-1])[..., :d]


def _decode_parts(raw, fmt, shift):
    """Page bytes -> the decoded f32 parts (the residual s_max multiply is
    the caller's). Unpacked formats give one part in natural element
    order; packed FP4 gives the (low, high) nibble halves, which contract
    against the :func:`_split_pair` operands."""
    if not fmt.quantized:
        return [raw.astype(jnp.float32)]
    if not fmt.packed:
        return [decode_fp8(raw, fmt.fmt, shift)]
    c = raw.astype(jnp.int32)
    return [decode_fp8(nib, fmt.fmt, shift) for nib in (c & 0xF, (c >> 4) & 0xF)]


def _page_classes(fmt, frozen):
    """The page classes a table may address: the active store, then the
    packed frozen region when there is one."""
    return (fmt,) if frozen is None else (fmt, frozen)


def _kernel(*refs, page, pp, scale, classes, base, nfz, g, window):
    """One (row, page) grid step over every KV head of the page at once.

    Scores and the softmax statistics keep KV heads on sublanes with a unit
    lane dim — (page, KV, 1) — so q·k is a lane reduction and p·v a
    reduction over the page (major) dim: no relayout, no MXU needed for a
    single decode query per head. The g query heads of a KV group loop
    statically. With two page classes (active + frozen) both candidate
    pages are DMA'd via clamped index maps and the prefetched table's id
    class selects per page; each class accumulates its own context, in its
    own element order, and the wrapper sums them."""
    refs = list(refs)
    take = lambda n: [refs.pop(0) for _ in range(n)]
    pt_ref, len_ref, _layer_ref = take(3)
    smax = [take(2) if f.quantized else None for f in classes]
    q_nat = refs.pop(0) if any(not f.packed for f in classes) else None
    q_pair = take(2) if any(f.packed for f in classes) else None
    pages = [take(4) if f.quantized else take(2) + [None, None]
             for f in classes]
    n_parts = [2 if f.packed else 1 for f in classes]
    outs = [take(n) for n in n_parts]
    m_ref, l_ref = take(2)
    accs = [take(n) for n in n_parts]
    assert not refs
    b, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        for part in accs:
            for a in part:
                a[...] = jnp.zeros_like(a)

    pid = pt_ref[b, j]
    is_fz = pid >= base
    row = [jnp.minimum(pid, base - 1) if len(classes) > 1 else pid,
           jnp.clip(pid - base, 0, nfz)]
    kv_parts = []
    for c, f in enumerate(classes):
        k_ref, v_ref, ksh_ref, vsh_ref = pages[c]
        # exponent-add scale apply: an integer add of -k on the code
        # exponent (k per (page, head): a (KV, 1) column), then one
        # full-precision s_max multiply per page
        ks, vs = ((ksh_ref[0], vsh_ref[0]) if f.quantized else (0, 0))
        k = _decode_parts(k_ref[0], f, ks)
        v = _decode_parts(v_ref[0], f, vs)
        if f.quantized:
            ksm_ref, vsm_ref = smax[c]
            k = [x * ksm_ref[row[c]] for x in k]
            v = [x * vsm_ref[row[c]] for x in v]
        kv_parts.append((k, v))

    lane_dot = lambda qv, kv_: jnp.sum(qv[None] * kv_, axis=-1, keepdims=True)
    kvh = kv_parts[0][0][0].shape[1]
    pos = j * page + jax.lax.broadcasted_iota(jnp.int32, (page, kvh, 1), 0)
    valid = pos < len_ref[b]
    if window:  # sliding window: the query sits at position kv_len - 1
        valid &= pos > len_ref[b] - 1 - window
    for gi in range(g):
        qs = {1: [q_nat[0, gi]] if q_nat is not None else None,
              2: [r[0, gi] for r in q_pair] if q_pair is not None else None}
        s_cls = []
        for c, (k, _) in enumerate(kv_parts):
            qv = qs[n_parts[c]]
            s_cls.append(sum(lane_dot(qp.astype(jnp.float32), kp)
                             for qp, kp in zip(qv, k)))
        s = s_cls[0] if len(s_cls) == 1 else jnp.where(is_fz, s_cls[1],
                                                       s_cls[0])
        s = jnp.where(valid, s * scale, _NEG_INF)

        m_prev, l_prev = m_ref[gi], l_ref[gi]  # (KV, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0))
        # fully-masked pages leave m at -inf; exp(s - m) would be exp(0) = 1
        # for every masked lane, so the mask must hit p, not just s
        p = jnp.where(valid, jnp.exp(s - m_new[None]), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[gi] = l_prev * corr + jnp.sum(p, axis=0)
        m_ref[gi] = m_new
        p_cls = [p]
        if len(classes) > 1:
            p_fz = jnp.where(is_fz, p, 0.0)
            p_cls = [p - p_fz, p_fz]
        for c, (_, v) in enumerate(kv_parts):
            for a, vp in zip(accs[c], v):
                a[gi] = a[gi] * corr + jnp.sum(p_cls[c] * vp, axis=0)

    @pl.when(j == pp - 1)
    def _done():
        inv = 1.0 / jnp.maximum(l_ref[...], 1e-30)
        for c in range(len(classes)):
            for o, a in zip(outs[c], accs[c]):
                o[0] = a[...] * inv


_LANES = 128


def _code_operand(store, layer, pmap):
    """The operand and index map through which a kernel reads one layer of
    a stacked code store ``(L, rows, page, ...)``. A store whose minor dim
    fills whole lane tiles is read in place: ``pmap`` addresses (layer,
    page). The TPU lays a narrower store (MLA's ``krope``, FP4 codes of a
    64-wide head) out with another dim minor, which a per-page block cannot
    be DMA'd from, so XLA would copy the whole stack into the kernel's
    layout on every call: that store's layer is sliced first, and only the
    slice is copied."""
    if store.shape[-1] % _LANES == 0:
        return store, pmap
    one = jax.lax.dynamic_slice_in_dim(store, layer[0], 1)
    return one, lambda *ix: (0,) + tuple(pmap(*ix)[1:])


@functools.partial(jax.jit, static_argnames=("fmt", "frozen", "window",
                                             "interpret"))
def paged_decode_attn_pallas(q, k_pages, v_pages, k_smax, k_shift, v_smax,
                             v_shift, page_table, kv_lens, layer,
                             fmt=None, frozen=None,
                             k_fz=None, v_fz=None, k_fz_smax=None,
                             k_fz_shift=None, v_fz_smax=None, v_fz_shift=None,
                             window: int = 0, interpret: bool = False):
    """q: (B, H, hd) single-token queries; k_pages/v_pages: the stacked
    (L, P+1, page, KV, hd) uint8 codes (``fmt`` quantized) or bf16 values;
    k/v_smax: (L, P+1) f32; k/v_shift: (L, P+1, KV) int32 (ignored for bf16
    pages); page_table: (B, PP) int32; kv_lens: (B,) valid token counts;
    ``layer``: the layer to read, a scalar-prefetch operand, so the pages
    of a lane-dense store are DMA'd straight out of the stacked store (a
    narrower one is sliced to the layer, ``_code_operand``; so are the
    per-page scales, a few KB a layer: smax for SMEM, the shifts for their
    (KV, 1) blocks, whose tiled layout is a copy); ``window``:
    sliding-window size (0 = full history). ``fmt``/``frozen`` accept a
    PageFormat or a format name (coerced via ``page_format`` — unknown names
    fail fast with the allowed set). With ``frozen`` set the ``*_fz``
    operands carry the stacked packed FP4 region ((L, F+1, page, KV,
    ceil(hd/2)) codes + its own scales; row F is the dummy clamped gathers
    land on) and table entries >= P+1 select it per page. Returns (B, H, dv)
    f32. GQA head repetition is internal: each grid step reads one whole
    page (every KV head) once and serves all g = H/KV query heads of each
    group.
    """
    fmt = page_format(fmt)
    frozen = page_format(frozen) if frozen is not None else None
    assert frozen is None or (fmt.quantized and frozen.quantized), \
        "a frozen region requires quantized active pages"
    classes = _page_classes(fmt, frozen)
    b, h, hd = q.shape
    _, p1, page, kv, _ = k_pages.shape
    pp = page_table.shape[1]
    g = h // kv
    # (B, KV, g, hd) head order -> g-major blocks with KV on sublanes
    qg = jnp.swapaxes(q.reshape(b, kv, g, hd), 1, 2)
    nfz = 0 if k_fz is None else k_fz.shape[1] - 1
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))

    def page_map(bi, ji, pt, _lens, lyr, *_s):
        pid = pt[bi, ji]
        if frozen is not None:  # frozen ids clamp to the null page
            pid = jnp.minimum(pid, p1 - 1)
        return (lyr[0], pid, 0, 0, 0)

    def fz_page_map(bi, ji, pt, _lens, lyr, *_s):
        return (lyr[0], jnp.clip(pt[bi, ji] - p1, 0, nfz), 0, 0, 0)

    def q_map(bi, ji, *_s):
        return (bi, 0, 0, 0)

    stores = [(k_pages, v_pages, k_smax, v_smax, k_shift, v_shift, page_map)]
    if frozen is not None:
        stores.append((k_fz, v_fz, k_fz_smax, v_fz_smax, k_fz_shift,
                       v_fz_shift, fz_page_map))
    scalars = [page_table, kv_lens, layer]
    tensors, in_specs = [], []
    for f, st in zip(classes, stores):
        if f.quantized:
            scalars += [st[2][layer[0]], st[3][layer[0]]]
    if any(not f.packed for f in classes):
        tensors.append(qg)
        in_specs.append(pl.BlockSpec((1, g, kv, hd), q_map))
    if any(f.packed for f in classes):
        width = next(st[0].shape[-1] for f, st in zip(classes, stores)
                     if f.packed)
        tensors += list(_split_pair(qg, width))
        in_specs += [pl.BlockSpec((1, g, kv, width), q_map)] * 2
    out_shape, out_specs, acc_shapes = [], [], []
    for f, (kp, vp, _, _, ksh, vsh, pmap) in zip(classes, stores):
        for st in (kp, vp):
            st, m = _code_operand(st, layer, pmap)
            tensors.append(st)
            in_specs.append(pl.BlockSpec(
                (pl.Squeezed(), 1, page, kv, st.shape[-1]), m))
        if f.quantized:
            # the (KV, 1) shift column of the gathered page: a trailing unit
            # dim keeps the block's two minor dims whole-array. It changes
            # the tiled layout, so it is a copy: of the layer's slice only
            smap = lambda *ix, pmap=pmap: pmap(*ix)[1:4]
            tensors += [ksh[layer[0], ..., None], vsh[layer[0], ..., None]]
            in_specs += [pl.BlockSpec((1, kv, 1), smap)] * 2
        for _ in range(2 if f.packed else 1):
            out_shape.append(jax.ShapeDtypeStruct((b, g, kv, vp.shape[-1]),
                                                  jnp.float32))
            out_specs.append(pl.BlockSpec((1, g, kv, vp.shape[-1]), q_map))
            acc_shapes.append(pltpu.VMEM((g, kv, vp.shape[-1]), jnp.float32))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(b, pp),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((g, kv, 1), jnp.float32),
                        pltpu.VMEM((g, kv, 1), jnp.float32)] + acc_shapes,
    )
    outs = pl.pallas_call(
        functools.partial(_kernel, page=page, pp=pp,
                          scale=1.0 / float(hd) ** 0.5, classes=classes,
                          base=p1, nfz=nfz, g=g, window=window),
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(*scalars, *tensors)
    dv = v_pages.shape[-1] if not fmt.packed else hd
    out, i = 0.0, 0
    for f in classes:
        if f.packed:
            out = out + _join_pair(outs[i], outs[i + 1], dv)
            i += 2
        else:
            out = out + outs[i]
            i += 1
    return jnp.swapaxes(out, 1, 2).reshape(b, h, dv)


# ---------------------------------------------------------------------------
# MLA latent decode: KV = 1 head, k = concat(ckv, krope), v = ckv view
# ---------------------------------------------------------------------------
def _mla_kernel(*refs, page, pp, scale, classes, base, nfz):
    """One (row, head block, page) grid step. Scores contract the latent and
    rope halves of k = concat(ckv, krope) separately (no concat); v is the
    ckv view. Page classes work as in the GQA kernel: packed classes
    contract even/odd query halves against the nibble halves and
    accumulate their context apart."""
    refs = list(refs)
    take = lambda n: [refs.pop(0) for _ in range(n)]
    pt_ref, len_ref, _layer_ref = take(3)
    scal = [take(4) if f.quantized else None for f in classes]
    q_nat = take(2) if any(not f.packed for f in classes) else None
    q_pair = take(4) if any(f.packed for f in classes) else None
    pages = [take(2) for _ in classes]
    n_parts = [2 if f.packed else 1 for f in classes]
    outs = [take(n) for n in n_parts]
    m_ref, l_ref = take(2)
    accs = [take(n) for n in n_parts]
    assert not refs
    b, j = pl.program_id(0), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        for part in accs:
            for a in part:
                a[...] = jnp.zeros_like(a)

    pid = pt_ref[b, j]
    is_fz = pid >= base
    row = [jnp.minimum(pid, base - 1) if len(classes) > 1 else pid,
           jnp.clip(pid - base, 0, nfz)]
    dot = lambda x, y, cdims: jax.lax.dot_general(
        x, y, (cdims, ((), ())), preferred_element_type=jnp.float32)
    s_cls, ckv_cls = [], []
    for c, f in enumerate(classes):
        ckv_ref, kr_ref = pages[c]
        # the latent has no head axis: one M2 shift per page, applied as
        # the same exponent add + one s_max multiply per page
        if f.quantized:
            csm_ref, csh_ref, rsm_ref, rsh_ref = scal[c]
            ckv = [x * csm_ref[row[c]] for x in
                   _decode_parts(ckv_ref[0], f, csh_ref[row[c]])]
            kr = [x * rsm_ref[row[c]] for x in
                  _decode_parts(kr_ref[0], f, rsh_ref[row[c]])]
        else:
            ckv, kr = [ckv_ref[0].astype(jnp.float32)], [
                kr_ref[0].astype(jnp.float32)]
        if f.packed:
            ql, qr = [r_[0, 0].astype(jnp.float32) for r_ in q_pair[:2]], [
                r_[0, 0].astype(jnp.float32) for r_ in q_pair[2:]]
        else:
            ql, qr = [q_nat[0][0, 0].astype(jnp.float32)], [
                q_nat[1][0, 0].astype(jnp.float32)]
        s_cls.append(sum(dot(x, y, ((1,), (1,)))
                         for x, y in zip(ql + qr, ckv + kr)))
        ckv_cls.append(ckv)
    s = s_cls[0] if len(s_cls) == 1 else jnp.where(is_fz, s_cls[1], s_cls[0])
    s = s * scale
    pos = j * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = pos < len_ref[b]
    s = jnp.where(valid, s, _NEG_INF)

    m_prev, l_prev = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
    m_ref[...] = m_new
    p_cls = [p]
    if len(classes) > 1:
        p_fz = jnp.where(is_fz, p, 0.0)
        p_cls = [p - p_fz, p_fz]
    # v is the ckv view: the attention-weighted latent IS the context
    for c, ckv in enumerate(ckv_cls):
        for a, vp in zip(accs[c], ckv):
            a[...] = a[...] * corr + dot(p_cls[c], vp, ((1,), (0,)))

    @pl.when(j == pp - 1)
    def _done():
        inv = 1.0 / jnp.maximum(l_ref[...], 1e-30)
        for c in range(len(classes)):
            for o, a in zip(outs[c], accs[c]):
                o[0, 0] = a[...] * inv


@functools.partial(jax.jit, static_argnames=("scale", "fmt", "frozen", "bq",
                                             "interpret"))
def paged_mla_decode_attn_pallas(q_lat, q_rope, ckv_pages, krope_pages,
                                 ckv_smax, ckv_shift, krope_smax, krope_shift,
                                 page_table, kv_lens, layer, scale,
                                 fmt=None, frozen=None,
                                 ckv_fz=None, krope_fz=None, ckv_fz_smax=None,
                                 ckv_fz_shift=None, krope_fz_smax=None,
                                 krope_fz_shift=None,
                                 bq: int = 8, interpret: bool = False):
    """MLA absorbed decode over latent pages (flash-decoding dataflow).

    q_lat: (B, H, r) queries absorbed into the latent space; q_rope:
    (B, H, dr) rope-space queries; ckv_pages: the stacked (L, P+1, page, r)
    and krope_pages: (L, P+1, page, dr) uint8 codes (``fmt`` quantized) or
    bf16; c/r smax: (L, P+1) f32; c/r shift: (L, P+1, 1) int32 (single
    scale "head"); page_table: (B, PP) int32; kv_lens: (B,); ``layer``: the
    layer to read, a scalar-prefetch operand that the page index maps
    address the stacked stores with (``krope``, narrower than a lane tile,
    is sliced to the layer, ``_code_operand``; so are the per-page scales,
    a few KB a layer, for SMEM); ``scale``: softmax scale
    (1/sqrt(qk_nope + qk_rope)). ``fmt``/``frozen`` are PageFormats (or
    names — ``page_format`` coercion fails fast on unknowns); with
    ``frozen`` set the ``*_fz`` operands carry the stacked packed FP4
    latent region ((L, F+1, page, ceil(d/2)) codes + scales) and table
    entries >= P+1 select it per page. Returns the latent context (B, H, r)
    f32 — the caller applies the absorbed v_up projection.

    KV is a single head: every query head scores the same k =
    concat(ckv, krope) page block and v is the ckv view, so the grid is
    (B, ceil(H / bq), pages) with the page loop innermost and the latent
    never gathered into HBM.
    """
    fmt = page_format(fmt)
    frozen = page_format(frozen) if frozen is not None else None
    assert frozen is None or (fmt.quantized and frozen.quantized), \
        "a frozen region requires quantized active pages"
    classes = _page_classes(fmt, frozen)
    b, h, r = q_lat.shape
    dr = q_rope.shape[-1]
    _, p1, page, _ = ckv_pages.shape
    pp = page_table.shape[1]
    bq = -(-bq // 8) * 8  # a sublane multiple: the head block is tile-legal
    hb = -(-h // bq)
    pad = hb * bq - h
    if pad:
        q_lat = jnp.pad(q_lat, ((0, 0), (0, pad), (0, 0)))
        q_rope = jnp.pad(q_rope, ((0, 0), (0, pad), (0, 0)))
    ql = q_lat.reshape(b, hb, bq, r)
    qr = q_rope.reshape(b, hb, bq, dr)
    nfz = 0 if ckv_fz is None else ckv_fz.shape[1] - 1
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))

    def page_map(bi, hi, ji, pt, _lens, lyr, *_s):
        pid = pt[bi, ji]
        if frozen is not None:  # frozen ids clamp to the null page
            pid = jnp.minimum(pid, p1 - 1)
        return (lyr[0], pid, 0, 0)

    def fz_page_map(bi, hi, ji, pt, _lens, lyr, *_s):
        return (lyr[0], jnp.clip(pt[bi, ji] - p1, 0, nfz), 0, 0)

    def q_map(bi, hi, ji, *_s):
        return (bi, hi, 0, 0)

    stores = [(ckv_pages, krope_pages, ckv_smax, ckv_shift, krope_smax,
               krope_shift, page_map)]
    if frozen is not None:
        stores.append((ckv_fz, krope_fz, ckv_fz_smax, ckv_fz_shift,
                       krope_fz_smax, krope_fz_shift, fz_page_map))
    scalars = [page_table, kv_lens, layer]
    for f, st in zip(classes, stores):
        if f.quantized:  # the layer's per-page scalars, flat in SMEM
            scalars += [a[layer[0]].reshape(-1) for a in st[2:6]]
    qspec = lambda d: pl.BlockSpec((1, 1, bq, d), q_map)
    tensors, in_specs = [], []
    if any(not f.packed for f in classes):
        tensors += [ql, qr]
        in_specs += [qspec(r), qspec(dr)]
    if any(f.packed for f in classes):
        cw, rw = next((st[0].shape[-1], st[1].shape[-1])
                      for f, st in zip(classes, stores) if f.packed)
        tensors += list(_split_pair(ql, cw)) + list(_split_pair(qr, rw))
        in_specs += [qspec(cw)] * 2 + [qspec(rw)] * 2
    out_shape, out_specs, acc_shapes = [], [], []
    for f, (cp, rp, *_, pmap) in zip(classes, stores):
        for st in (cp, rp):
            st, m = _code_operand(st, layer, pmap)
            tensors.append(st)
            in_specs.append(pl.BlockSpec(
                (pl.Squeezed(), 1, page, st.shape[-1]), m))
        for _ in range(2 if f.packed else 1):
            out_shape.append(jax.ShapeDtypeStruct((b, hb, bq, cp.shape[-1]),
                                                  jnp.float32))
            out_specs.append(qspec(cp.shape[-1]))
            acc_shapes.append(pltpu.VMEM((bq, cp.shape[-1]), jnp.float32))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(b, hb, pp),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32)] + acc_shapes,
    )
    outs = pl.pallas_call(
        functools.partial(_mla_kernel, page=page, pp=pp, scale=scale,
                          classes=classes, base=p1, nfz=nfz),
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*scalars, *tensors)
    out, i = 0.0, 0
    for f in classes:
        if f.packed:
            out = out + _join_pair(outs[i], outs[i + 1], r)
            i += 2
        else:
            out = out + outs[i]
            i += 1
    return out.reshape(b, hb * bq, r)[:, :h]
