"""Pallas TPU kernel: fused single-pass W4A8 GEMM pipeline.

The split deployment path costs three HBM round-trips: act_quant writes the
FP8-grid activations, the GEMM reads them back, and the LoRC correction runs
as two extra bf16 matmuls over the same activations. This kernel does the
whole quantize -> decode -> matmul -> correct chain in one pl.pallas_call:

  1. *in-kernel activation quantization*: the full K row of the M-tile is
     resident in VMEM (same layout contract as act_quant — feature dims fit
     one block), so when the first N-tile visits an M-tile the per-token
     absmax/scale is computed and the whole row is RNE-rounded onto the FP8
     grid into a bf16 VMEM scratch; later N-tiles of the same M-tile reuse
     the scratch. Nothing is materialized to HBM.
  2. packed E2M1/E3M0 nibbles are decoded in VMEM per (BN, BK=group) slice
     (copy-free bitwise unpack) and the per-(row, group) scale folds into
     the slice (M2: 2^-k from the exponent bit pattern + one per-row s_max
     multiply after the loop).
  3. the K loop lives *inside* the kernel (flash-attention style): a f32
     accumulator carried across the K steps in VMEM/registers, one single
     HBM write of the finished tile.
  4. *fused LoRC epilogue*: the rank-r correction (x @ B^T) @ A^T is applied
     to the accumulator before that single write.

A leading batch grid axis makes the same kernel serve stacked weights: MoE
expert stacks (E, out, in) and MLA per-head absorbed projections call it
directly instead of densifying through dequant_packed. Two orientations:

  * normal:     y[e] = x[e] @ W[e]^T — contraction over in-features (K),
                group scales along the contraction dim (the 2-D serving GEMM
                is this with E == 1);
  * transposed: y[e] = x[e] @ W[e]   — contraction over the weight's out
                rows (the MLA absorbed q path contracts wk_b's out dim);
                group scales then lie along the *output* dim, so the output
                tile is one scale group wide and s_max folds into the
                weight slice inside the loop.

Grid: (E, M/BM, N/BN) — output-tile programs, K internal. Block sizes come
from kernels.autotune; ``clamp_block`` makes them tile-legal for the TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.formats import FORMATS

from .common import DECODERS, pow2i, round_to_grid, token_scale

__all__ = ["w4a8_fused_matmul_pallas", "w4a8_fused_batched_pallas", "clamp_block"]


def _tile_divisor(dim: int, blk: int, align: int) -> int:
    """Largest divisor of ``dim`` that is <= blk and a multiple of
    ``align``; the whole ``dim`` when it has none."""
    b = (min(blk, dim) // align) * align
    while b >= align:
        if dim % b == 0:
            return b
        b -= align
    return dim


def clamp_block(m: int, n: int, bm: int, bn: int, transpose: bool = False):
    """The (bm, bn) the fused kernel actually runs for an (m, n) GEMM and a
    requested block pair — the TPU block rule: a block's two minor dims are
    multiples of the (8, 128) tile or span the whole array dim. bm becomes a
    sublane multiple no taller than M rounded up to one (the kernel pads M
    to whole blocks); bn becomes a tile-legal divisor of N's rows (rows are
    sublanes in the transposed orientation)."""
    up = lambda v, a: -(-v // a) * a
    return (min(up(max(bm, 1), 8), up(m, 8)),
            _tile_divisor(n, bn, 8 if transpose else 128))


def _split_groups(a, gs: int):
    """Reorder the last (contraction) dim group-wise into [even | odd]
    halves: column 2j of a group goes to j, column 2j+1 to gs/2 + j. This
    matches the packed-nibble layout (low nibble = even element), so the
    kernel contracts the low and high nibbles as two lane-aligned halves
    instead of interleaving them in VMEM."""
    lead, d = a.shape[:-1], a.shape[-1]
    a = a.reshape(*lead, d // gs, gs // 2, 2)
    return jnp.swapaxes(a, -1, -2).reshape(*lead, d)


def _merge_groups(a, gs: int):
    """Inverse of :func:`_split_groups`."""
    lead, d = a.shape[:-1], a.shape[-1]
    a = a.reshape(*lead, d // gs, 2, gs // 2)
    return jnp.swapaxes(a, -1, -2).reshape(*lead, d)


def _kernel(refs, *, w_fmt, a_fmt, m2, lorc, gs, bm, bn, nsteps, transpose):
    """One (BM, BN) output tile; the K/contraction loop runs inside.

    ``refs`` is the positional (inputs..., output, scratch...) list; which
    optional refs are present is decided by the static flags. Activations
    (normal) or outputs and B columns (transposed) arrive in the
    :func:`_split_groups` order, so each packed byte's low and high nibbles
    contract against two lane-aligned halves of a group.
    """
    refs = list(refs)
    x_ref = refs.pop(0)          # (1, BM, D) raw activations, full row
    codes_ref = refs.pop(0)      # (1, BN, K/2) | (1, O, gs/2)
    scale_ref = refs.pop(0)      # (1, BN, G)   | (1, O, 1)   (shifts when m2)
    smax_ref = refs.pop(0) if m2 else None  # (1, 1, BN) | (1, O, 1)
    a_ref = refs.pop(0) if lorc else None
    b_ref = refs.pop(0) if lorc else None
    o_ref = refs.pop(0)
    xq_scr = refs.pop(0) if a_fmt else None  # (BM, D) bf16 quantized slab
    lr_scr = refs.pop(0) if lorc else None   # (BM, r) f32 LoRC projection
    assert not refs
    decode = DECODERS[w_fmt]

    # ---- in-kernel FP8 quantization, once per M-tile -----------------------
    if a_fmt:
        fmt = FORMATS[a_fmt]

        @pl.when(pl.program_id(2) == 0)
        def _quantize_slab():
            xf = x_ref[0].astype(jnp.float32)
            sc = token_scale(xf, fmt)
            xq_scr[...] = (round_to_grid(xf / sc, fmt) * sc).astype(jnp.bfloat16)

        xq_at = lambda cols: xq_scr[:, cols]
    else:
        xq_at = lambda cols: x_ref[0, :, cols].astype(jnp.bfloat16)

    # ---- LoRC skinny projection, once per M-tile ---------------------------
    # xr depends only on the M-tile and the output-tile-invariant factor
    # (B^T in normal orientation, A in transposed), so it is computed by the
    # first output-tile program and reused from scratch by the rest.
    if lorc:

        @pl.when(pl.program_id(2) == 0)
        def _lorc_project():
            fac = a_ref[0] if transpose else b_ref[0]
            cdim = (0,) if transpose else (1,)
            lr_scr[...] = jax.lax.dot_general(
                xq_at(slice(None)), fac.astype(jnp.bfloat16),
                (((1,), cdim), ((), ())), preferred_element_type=jnp.float32)

    # ---- K loop: decode + scale a weight slice, accumulate in f32 ---------
    # nsteps is static, so the loop unrolls at trace time into static,
    # tile-aligned ref slices (Mosaic lowers no dynamic_slice of values).
    # The low and high nibbles decode to two (rows, gs/2) halves.
    half = gs // 2

    def halves(cod, gsc):
        c = cod.astype(jnp.int32)
        return [(decode(nib) * gsc).astype(jnp.bfloat16)
                for nib in (c & 0xF, (c >> 4) & 0xF)]

    if transpose:
        # y[:, group] = x @ W[:, group]: contract BN weight rows per step;
        # the two halves are the group's even and odd output columns
        acc = [jnp.zeros((bm, half), jnp.float32) for _ in range(2)]
        for s in range(nsteps):
            rows = slice(s * bn, (s + 1) * bn)
            gsc = scale_ref[0, rows, :]
            if m2:
                gsc = pow2i(-gsc.astype(jnp.int32)) * smax_ref[0, rows, :]
            xs = xq_at(rows)
            for t, w in enumerate(halves(codes_ref[0, rows, :], gsc)):
                acc[t] = acc[t] + jax.lax.dot_general(
                    xs, w, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
        acc = jnp.concatenate(acc, axis=1)
    else:
        acc = jnp.zeros((bm, bn), jnp.float32)
        for s in range(nsteps):
            gsc = scale_ref[0, :, s:s + 1]
            if m2:
                gsc = pow2i(-gsc.astype(jnp.int32))
            for t, w in enumerate(halves(
                    codes_ref[0, :, s * half:(s + 1) * half], gsc)):
                lo = s * gs + t * half
                acc = acc + jax.lax.dot_general(
                    xq_at(slice(lo, lo + half)), w, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
        if m2:
            acc = acc * smax_ref[0]  # per-row s_max as a (1, BN) row, once

    # ---- fused LoRC epilogue before the single HBM write -------------------
    if lorc:
        xr = lr_scr[...].astype(jnp.bfloat16)  # (BM, r) from the projection
        if transpose:
            corr = jax.lax.dot_general(
                xr, b_ref[0].astype(jnp.bfloat16), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        else:
            corr = jax.lax.dot_general(
                xr, a_ref[0].astype(jnp.bfloat16), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
        acc = acc + corr

    o_ref[0] = acc


def _vmem_limit(block_bytes: int, scratch_bytes: int) -> int:
    """Scoped-VMEM request for one fused call: double-buffered blocks plus
    scratch, with headroom for the compiler's temporaries, floored at the
    16 MiB default and capped well under v5e's 128 MiB."""
    need = 2 * block_bytes + scratch_bytes
    return int(min(max(2 * need, 16 << 20), 100 << 20))


@functools.partial(
    jax.jit,
    static_argnames=("w_fmt", "a_fmt", "group_size", "bm", "bn", "transpose_w",
                     "interpret"),
)
def w4a8_fused_batched_pallas(
    x,
    codes,
    scale,
    s_max=None,
    shifts=None,
    lorc_a=None,
    lorc_b=None,
    *,
    w_fmt: str = "fp4_e2m1",
    a_fmt=None,
    group_size: int = 256,
    bm: int = 128,
    bn: int = 128,
    transpose_w: bool = False,
    interpret: bool = False,
):
    """Batched fused W4A8 GEMM over stacked packed weights.

    x: (E, M, D) float — raw (unquantized) activations; quantized in-kernel
       when ``a_fmt`` is set.
    codes: (E, N, In/2) uint8; scale: (E, N, n_groups) f32.
    normal (transpose_w=False): D == In, returns (E, M, N) f32.
    transposed: D == N (contract the weight's out rows), returns (E, M, In).
    Optional M2 decomposition (s_max (E, N, 1), shifts (E, N, n_groups)) and
    LoRC factors (lorc_a (E, N, r), lorc_b (E, r, In)).
    Compiled for the TPU unless ``interpret=True`` asks for the Pallas
    interpreter. M is zero-padded to whole sublane-multiple blocks and the
    N block shrinks to a tile-legal divisor, so every M the engine emits
    tiles; padded rows quantize to zero and are sliced off.
    """
    ne, m, d = x.shape
    n_rows, half = codes.shape[1], codes.shape[2]
    in_f = half * 2
    gs = group_size
    assert scale.shape[-1] * gs == in_f, (scale.shape, gs, in_f)
    m2 = shifts is not None
    lorc = lorc_a is not None and lorc_a.shape[-1] > 0
    r = lorc_a.shape[-1] if lorc else 0

    # M is zero-padded to whole blocks (padding, not a smaller block: every
    # M-tile re-streams the weights)
    bm, bn = clamp_block(m, n_rows, bm, bn, transpose_w)
    mp = -(-m // bm) * bm
    if mp != m:
        x = jnp.pad(x, ((0, 0), (0, mp - m), (0, 0)))
    if transpose_w:
        assert d == n_rows, (d, n_rows)
        nsteps = n_rows // bn
        grid = (ne, mp // bm, in_f // gs)
        n_out, bn_out = in_f, gs
        codes_spec = pl.BlockSpec((1, n_rows, gs // 2), lambda e, i, j: (e, 0, j))
        # one scale column per grid step: a unit axis keeps the block's two
        # minor dims whole-array (tile-legal)
        scale_spec = pl.BlockSpec((1, None, n_rows, 1),
                                  lambda e, i, j: (e, j, 0, 0))
        smax_arg = s_max.reshape(ne, n_rows, 1) if m2 else None
        smax_spec = pl.BlockSpec((1, n_rows, 1), lambda e, i, j: (e, 0, 0))
        a_spec = pl.BlockSpec((1, n_rows, r), lambda e, i, j: (e, 0, 0))
        b_spec = pl.BlockSpec((1, r, gs), lambda e, i, j: (e, 0, j))
        if lorc:
            lorc_b = _split_groups(lorc_b, gs)
    else:
        assert d == in_f, (d, in_f)
        assert d % gs == 0, (d, gs)
        nsteps = d // gs
        grid = (ne, mp // bm, n_rows // bn)
        n_out, bn_out = n_rows, bn
        x = _split_groups(x, gs)
        codes_spec = pl.BlockSpec((1, bn, half), lambda e, i, j: (e, j, 0))
        scale_spec = pl.BlockSpec((1, bn, nsteps), lambda e, i, j: (e, j, 0))
        smax_arg = s_max.reshape(ne, 1, n_rows) if m2 else None
        smax_spec = pl.BlockSpec((1, 1, bn), lambda e, i, j: (e, 0, j))
        a_spec = pl.BlockSpec((1, bn, r), lambda e, i, j: (e, j, 0))
        b_spec = pl.BlockSpec((1, r, d), lambda e, i, j: (e, 0, 0))
        if lorc:
            lorc_b = _split_groups(lorc_b, gs)

    sc = shifts.astype(jnp.int32) if m2 else scale
    if transpose_w:  # (E, N, G) -> (E, G, N, 1): column j is one block
        sc = jnp.swapaxes(sc, 1, 2)[..., None]
    args = [x, codes, sc]
    in_specs = [
        pl.BlockSpec((1, bm, d), lambda e, i, j: (e, i, 0)),  # full-row slab
        codes_spec,
        scale_spec,
    ]
    if m2:
        args.append(smax_arg)
        in_specs.append(smax_spec)
    if lorc:
        args += [lorc_a, lorc_b]
        in_specs += [a_spec, b_spec]

    scratch_shapes = []
    if a_fmt:
        scratch_shapes.append(pltpu.VMEM((bm, d), jnp.bfloat16))
    if lorc:
        scratch_shapes.append(pltpu.VMEM((bm, r), jnp.float32))

    itemsize = lambda a: jnp.dtype(a.dtype).itemsize
    block_bytes = (bm * d * itemsize(x) + bm * bn_out * 4
                   + (n_rows * gs // 2 if transpose_w else bn * half)
                   + (n_rows if transpose_w else bn * nsteps) * 4
                   + (n_rows if transpose_w else bn) * (4 + 2 * r)
                   + r * (gs if transpose_w else d) * 2)
    scratch_bytes = (bm * d * 2 if a_fmt else 0) + bm * r * 4

    kernel = functools.partial(
        _kernel, w_fmt=w_fmt, a_fmt=a_fmt, m2=m2, lorc=lorc, gs=gs, bm=bm,
        bn=bn, nsteps=nsteps, transpose=transpose_w,
    )
    out = pl.pallas_call(
        lambda *refs: kernel(refs),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bm, bn_out), lambda e, i, j: (e, i, j)),
        out_shape=jax.ShapeDtypeStruct((ne, mp, n_out), jnp.float32),
        scratch_shapes=scratch_shapes,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(block_bytes, scratch_bytes)),
        interpret=interpret,
    )(*args)
    if transpose_w:
        out = _merge_groups(out, gs)
    return out[:, :m]


@functools.partial(
    jax.jit,
    static_argnames=("w_fmt", "a_fmt", "group_size", "bm", "bn", "interpret"),
)
def w4a8_fused_matmul_pallas(
    x,
    codes,
    scale,
    s_max=None,
    shifts=None,
    lorc_a=None,
    lorc_b=None,
    *,
    w_fmt: str = "fp4_e2m1",
    a_fmt="fp8_e4m3",
    group_size: int = 256,
    bm: int = 128,
    bn: int = 128,
    interpret: bool = False,
):
    """2-D fused deployment GEMM: y[m, n] = sum_k q8(x)[m, k] * deq(w)[n, k]
    [+ LoRC]. x: (M, K) raw activations; codes: (N, K/2). Returns (M, N) f32.
    This is the batched kernel with a unit leading axis."""
    none = lambda v: None if v is None else v[None]
    out = w4a8_fused_batched_pallas(
        x[None], codes[None], scale[None], none(s_max), none(shifts),
        none(lorc_a), none(lorc_b), w_fmt=w_fmt, a_fmt=a_fmt,
        group_size=group_size, bm=bm, bn=bn, transpose_w=False,
        interpret=interpret,
    )
    return out[0]
