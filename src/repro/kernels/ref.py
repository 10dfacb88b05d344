"""Pure-jnp oracles for the Pallas kernels.

These define the exact semantics the TPU kernels must match bit-for-bit
(tests/test_kernels_* sweep shapes/dtypes and assert_allclose against these).
They are also the CPU fallback execution path for serving simulation.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.formats import FORMATS, fp_decode, pow2i, quantize_to_grid, unpack_nibbles
from repro.core.quantize import quantize_act_tokenwise
from .common import decode_fp8, page_format

__all__ = ["act_quant_ref", "dequant_packed_ref", "w4a8_matmul_ref",
           "w4a8_batched_matmul_ref", "paged_decode_attn_ref",
           "paged_mla_decode_attn_ref"]


def act_quant_ref(x, fmt_name: str = "fp8_e4m3"):
    """Token-wise FP8 quantization: returns (values_on_grid, scale).
    x: (..., d). scale: (..., 1) f32; values f32 on the E4M3 grid."""
    return quantize_act_tokenwise(x, fmt_name)


def _bf16(x):
    """Round to bf16 and keep the rounding: an f32 -> bf16 -> f32 convert
    pair may be elided inside a larger XLA program (excess precision),
    ``reduce_precision`` may not — the kernels round in VMEM."""
    x = jax.lax.reduce_precision(x.astype(jnp.float32), exponent_bits=8,
                                 mantissa_bits=7)
    return x.astype(jnp.bfloat16)


def dequant_packed_ref(codes, scale, fmt_name: str = "fp4_e2m1", group_size: int = 256):
    """codes: (..., out, in/2) packed nibbles; scale: (..., out, n_groups).
    Returns (..., out, in) BF16 dequantized weights — bf16 is what the TPU
    kernel materializes in VMEM (exact for E2M1's 1-mantissa-bit grid times
    a power of two; a general scale rounds)."""
    fmt = FORMATS[fmt_name]
    q = fp_decode(unpack_nibbles(codes), fmt)  # (..., out, in) f32
    out_f, in_f = q.shape[-2], q.shape[-1]
    n_groups = scale.shape[-1]
    gs = in_f // n_groups
    qg = q.reshape(*q.shape[:-1], n_groups, gs)
    w = (qg * scale[..., None].astype(jnp.float32)).reshape(*q.shape[:-2], out_f, in_f)
    return _bf16(w)


def _act_operand(x, a_fmt):
    """The bf16 activation operand: token-wise FP8 grid values x scale. A
    bf16 ``x`` is pinned to bf16 first: the kernel reads it from memory,
    where a fused producer's f32 intermediate never reaches it."""
    if x.dtype == jnp.bfloat16 or not a_fmt:
        x = _bf16(x)
    if not a_fmt:
        return x
    qx, sx = quantize_act_tokenwise(x, a_fmt)
    return _bf16(qx * sx)


def w4a8_matmul_ref(x, codes, scale, lorc_a=None, lorc_b=None,
                    w_fmt: str = "fp4_e2m1", a_fmt: str = "fp8_e4m3",
                    group_size: int = 256, s_max=None, shifts=None):
    """The W4A8 GEMM semantics: token-wise-FP8 activations x packed-FP4
    weights (+ optional LoRC low-rank side path).

    x: (..., in) float; codes: (out, in/2) uint8; scale: (out, G) f32.
    With the M2 decomposition (s_max (out, 1), shifts (out, G)) the weight
    operand is the exact bf16 ``q * 2^-shift`` and ``s_max`` scales the f32
    result, as the fused kernel applies it. Returns (..., out) in x.dtype.
    """
    from repro.models.layers import accum_dtype

    xq = _act_operand(x, a_fmt)
    if shifts is not None:
        w = dequant_packed_ref(codes, pow2i(-shifts.astype(jnp.int32)),
                               w_fmt, group_size)
    else:
        w = dequant_packed_ref(codes, scale, w_fmt, group_size)
    y = jax.lax.dot_general(xq, w, (((xq.ndim - 1,), (1,)), ((), ())),
                            preferred_element_type=accum_dtype())
    if shifts is not None:
        y = y * s_max.reshape(-1).astype(y.dtype)
    if lorc_a is not None:
        y = y + jax.lax.dot_general(
            _bf16(jax.lax.dot_general(xq, lorc_b, (((xq.ndim - 1,), (1,)), ((), ())),
                                      preferred_element_type=accum_dtype())),
            lorc_a, (((xq.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=accum_dtype()).astype(y.dtype)
    return y.astype(x.dtype)


def w4a8_batched_matmul_ref(x, codes, scale, lorc_a=None, lorc_b=None,
                            w_fmt: str = "fp4_e2m1", a_fmt=None,
                            group_size: int = 256, transpose_w: bool = False,
                            s_max=None, shifts=None):
    """Oracle for the batched fused kernel (MoE expert stacks, MLA absorbed
    heads). x: (E, M, D); codes: (E, N, In/2); scale: (E, N, G).

    normal: D == In, y[e] = x[e] @ W[e]^T -> (E, M, N);
    transposed: D == N, y[e] = x[e] @ W[e] -> (E, M, In) (the MLA absorbed q
    path contracts the packed weight's out rows).
    LoRC is the same low-rank *side path* as the fused epilogue. With the M2
    decomposition (s_max (E, N, 1), shifts (E, N, G)) the normal
    orientation scales the f32 result by s_max, as the kernel does; the
    transposed one folds the whole scale into the bf16 weight. Returns f32.
    """
    xq = _act_operand(x, a_fmt)
    post = shifts is not None and not transpose_w
    if post:
        scale = pow2i(-shifts.astype(jnp.int32))
    w = dequant_packed_ref(codes, scale, w_fmt, group_size)  # (E, N, In) bf16
    if transpose_w:
        y = jnp.einsum("emn,eni->emi", xq, w, preferred_element_type=jnp.float32)
        if lorc_a is not None:
            xr = _bf16(jnp.einsum("emn,enr->emr", xq, lorc_a.astype(jnp.bfloat16),
                                  preferred_element_type=jnp.float32))
            y = y + jnp.einsum("emr,eri->emi", xr, lorc_b.astype(jnp.bfloat16),
                               preferred_element_type=jnp.float32)
    else:
        y = jnp.einsum("emk,enk->emn", xq, w, preferred_element_type=jnp.float32)
        if post:
            y = y * s_max.reshape(s_max.shape[0], 1, -1).astype(jnp.float32)
        if lorc_a is not None:
            xr = _bf16(jnp.einsum("emk,erk->emr", xq, lorc_b.astype(jnp.bfloat16),
                                  preferred_element_type=jnp.float32))
            y = y + jnp.einsum("emr,enr->emn", xr, lorc_a.astype(jnp.bfloat16),
                               preferred_element_type=jnp.float32)
    return y


def paged_decode_attn_ref(q, k_pages, v_pages, k_smax, k_shift, v_smax,
                          v_shift, page_table, kv_lens, fmt=None,
                          window: int = 0, frozen=None,
                          k_fz=None, v_fz=None, k_fz_smax=None,
                          k_fz_shift=None, v_fz_smax=None, v_fz_shift=None):
    """Oracle for the paged decode-attention kernel.

    q: (B, H, hd); k_pages/v_pages: (P+1, page, KV, hd) uint8 codes
    (``fmt`` quantized) or bf16 values (``fmt`` None); k/v_smax: (P+1,) f32
    per-page full-precision scales; k/v_shift: (P+1, KV) int32 M2 exponent
    shifts; page_table: (B, PP) int32; kv_lens: (B,) valid token counts.
    ``fmt``/``frozen`` take a PageFormat or format name (coerced via
    ``page_format``); with ``frozen`` set the ``*_fz`` operands carry the
    packed FP4 region and table entries >= P+1 are frozen logical ids —
    gathered with clamped indices and selected per page by id class,
    exactly the kernel's dataflow. Returns (B, H, dv) f32 — the
    gathered-page, dequantized softmax attention with per-row length masks
    (GQA repetition internal).

    Shape-polymorphic in H and KV (only g = H/KV is load-bearing), so the
    serving mesh's shard_map wrapper runs this same oracle per model-axis
    shard on its contiguous head block — H/m query heads against KV/m
    kv heads with the co-sharded ``*_shift`` rows — with no sharded
    variant needed.
    """
    fmt = page_format(fmt)
    frozen = page_format(frozen) if frozen is not None else None
    b, h, hd = q.shape
    base, page, kv, _ = k_pages.shape
    dv = v_pages.shape[-1]
    pp = page_table.shape[1]
    g = h // kv

    def dq(pages, smax, shift, fpages, fsmax, fshift):
        apt = (jnp.minimum(page_table, base - 1) if frozen is not None
               else page_table)
        gathered = pages[apt]  # (B, PP, page, KV, d)
        if not fmt.quantized:
            return gathered.astype(jnp.float32).reshape(b, pp * page, kv, -1)
        d = pages.shape[-1] * (2 if fmt.packed else 1)
        vals = fmt.decode(gathered, shift[apt][:, :, None, :, None], d)
        vals = vals * smax[apt][:, :, None, None, None]
        if frozen is not None:
            fpt = jnp.clip(page_table - base, 0, fpages.shape[0] - 1)
            fvals = frozen.decode(fpages[fpt],
                                  fshift[fpt][:, :, None, :, None],
                                  pages.shape[-1])
            fvals = fvals * fsmax[fpt][:, :, None, None, None]
            mask = (page_table >= base)[:, :, None, None, None]
            vals = jnp.where(mask, fvals, vals)
        return vals.reshape(b, pp * page, kv, -1)

    kf = dq(k_pages, k_smax, k_shift, k_fz, k_fz_smax, k_fz_shift)
    vf = dq(v_pages, v_smax, v_shift, v_fz, v_fz_smax, v_fz_shift)
    qg = q.reshape(b, kv, g, hd).astype(jnp.float32)
    s = jnp.einsum("bkgd,btkd->bkgt", qg, kf) * (1.0 / float(hd) ** 0.5)
    t = pp * page
    pos = jnp.arange(t)[None, None, None, :]
    valid = pos < kv_lens[:, None, None, None]
    if window:  # sliding window: the query sits at position kv_len - 1
        valid &= pos > (kv_lens - 1 - window)[:, None, None, None]
    s = jnp.where(valid, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(valid, p, 0.0)
    o = jnp.einsum("bkgt,btkd->bkgd", p, vf)
    return o.reshape(b, h, dv)


def paged_mla_decode_attn_ref(q_lat, q_rope, ckv_pages, krope_pages,
                              ckv_smax, ckv_shift, krope_smax, krope_shift,
                              page_table, kv_lens, scale, fmt=None,
                              frozen=None, ckv_fz=None, krope_fz=None,
                              ckv_fz_smax=None, ckv_fz_shift=None,
                              krope_fz_smax=None, krope_fz_shift=None):
    """Oracle for the MLA latent decode kernel.

    q_lat: (B, H, r) absorbed queries; q_rope: (B, H, dr); ckv_pages:
    (P+1, page, r) / krope_pages: (P+1, page, dr) uint8 codes (``fmt``
    quantized) or bf16; c/r smax: (P+1,) f32; c/r shift: (P+1, 1)
    int32 (the latent has a single scale "head"); page_table: (B, PP);
    kv_lens: (B,). ``fmt``/``frozen`` as in ``paged_decode_attn_ref``; the
    ``*_fz`` operands carry the packed FP4 latent region. Scores are the
    k = concat(ckv, krope) contraction, v is the ckv view. Returns the
    latent context (B, H, r) f32.
    """
    fmt = page_format(fmt)
    frozen = page_format(frozen) if frozen is not None else None
    b, h, r = q_lat.shape
    base, page, _ = ckv_pages.shape
    pp = page_table.shape[1]

    def dq(pages, smax, shift, fpages, fsmax, fshift):
        apt = (jnp.minimum(page_table, base - 1) if frozen is not None
               else page_table)
        gathered = pages[apt]  # (B, PP, page, d)
        if not fmt.quantized:
            return gathered.astype(jnp.float32).reshape(b, pp * page, -1)
        d = pages.shape[-1] * (2 if fmt.packed else 1)
        vals = fmt.decode(gathered, shift[apt][..., None], d)
        vals = vals * smax[apt][:, :, None, None]
        if frozen is not None:
            fpt = jnp.clip(page_table - base, 0, fpages.shape[0] - 1)
            fvals = frozen.decode(fpages[fpt], fshift[fpt][..., None],
                                  pages.shape[-1])
            fvals = fvals * fsmax[fpt][:, :, None, None]
            mask = (page_table >= base)[:, :, None, None]
            vals = jnp.where(mask, fvals, vals)
        return vals.reshape(b, pp * page, -1)

    ckv = dq(ckv_pages, ckv_smax, ckv_shift, ckv_fz, ckv_fz_smax,
             ckv_fz_shift)
    kr = dq(krope_pages, krope_smax, krope_shift, krope_fz, krope_fz_smax,
            krope_fz_shift)
    s = (jnp.einsum("bhr,btr->bht", q_lat.astype(jnp.float32), ckv)
         + jnp.einsum("bhd,btd->bht", q_rope.astype(jnp.float32), kr)) * scale
    t = pp * page
    valid = jnp.arange(t)[None, None, :] < kv_lens[:, None, None]
    s = jnp.where(valid, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(valid, p, 0.0)
    return jnp.einsum("bht,btr->bhr", p, ckv)
