"""Pallas TPU kernel: split W4A8 GEMM — packed-FP4 weights x *pre-quantized*
FP8 activations, decoded in VMEM.

This is the original two-pass deployment kernel (act_quant writes the FP8
activations to HBM, this GEMM reads them back). It is kept as the baseline
the fused single-pass kernel (w4a8_fused.py) is benchmarked against, and as
the building block for callers that already hold quantized activations.
The decode / scale semantics are shared with the fused kernel via
kernels.common (DESIGN.md §2):

  * weights live in HBM as packed E2M1 nibbles (2/byte) + per-(row, group)
    scales — the HBM read per weight is 4 bits, which is the whole point on
    a bandwidth-bound decode step;
  * each (BM, BN, BK=group) tile is decoded to bf16 *in VMEM*: copy-free
    bitwise nibble unpack + a closed-form E2M1 decode (4 VPU ops), then an
    MXU bf16 matmul with f32 accumulation;
  * scales: the per-group multiply folds into the tile's partial sum. With
    M2 (pow-2 constrained) scales the multiplier is 2^-k built directly from
    the exponent bit pattern (integer VPU op — the TPU equivalent of the
    paper's "bit shift" cast) and one final per-row s_max multiply;
  * activations arrive already token-wise FP8-quantized (values on the E4M3
    grid times their scale, stored bf16) from the act_quant kernel.

Grid: (M/BM, N/BN, K/BK), K innermost; out tile (BM, BN) f32 accumulates
across the K steps and is written once (revisiting semantics).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .common import DECODERS, decode_e2m1, decode_e3m0, pow2i as _pow2i, unpack_nibbles as _unpack

__all__ = ["w4a8_matmul_pallas", "decode_e2m1", "decode_e3m0"]


def _kernel(x_ref, codes_ref, scale_ref, o_ref, *, w_fmt, nsteps, m2, smax_ref=None):
    """One (BM, BN) tile accumulating over the K grid dimension.

    x_ref: (BM, BK) bf16 — FP8-grid activation values (x scale)
    codes_ref: (BN, BK/2) uint8; scale_ref: (BN, 1) f32 (or shifts when m2)
    o_ref: (BM, BN) f32 accumulator
    """
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    decode = DECODERS[w_fmt]
    w_q = decode(_unpack(codes_ref[...]))  # (BN, BK) f32 on-grid
    if m2:
        # pow-2 group scale: multiplier from exponent bits (the bit-shift)
        gscale = _pow2i(-scale_ref[...].astype(jnp.int32))  # (BN, 1)
    else:
        gscale = scale_ref[...]  # (BN, 1) f32
    w = (w_q * gscale).astype(jnp.bfloat16)
    x = x_ref[...].astype(jnp.bfloat16)
    part = jax.lax.dot_general(
        x, w, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    o_ref[...] += part

    if m2:

        @pl.when(k_step == nsteps - 1)
        def _finalize():
            o_ref[...] = o_ref[...] * smax_ref[...].reshape(1, -1)


@functools.partial(
    jax.jit,
    static_argnames=("w_fmt", "group_size", "bm", "bn", "interpret"),
)
def w4a8_matmul_pallas(
    x_q,
    codes,
    scale,
    s_max=None,
    shifts=None,
    w_fmt: str = "fp4_e2m1",
    group_size: int = 256,
    bm: int = 128,
    bn: int = 128,
    interpret: bool = False,
):
    """y[m, n] = sum_k x_q[m, k] * dequant(codes, scale)[n, k].

    x_q: (M, K) bf16/f32 — already FP8-quantized activation values x scale.
    codes: (N, K/2) uint8; scale: (N, G) f32; optional M2 (s_max, shifts).
    Returns (M, N) f32. Shapes must tile: M % bm == 0 is relaxed by clamping
    bm to a divisor; K % group_size == 0 required (FGQ invariant).
    Compiled for the TPU unless ``interpret=True`` asks for the Pallas
    interpreter.
    """
    m, k = x_q.shape
    n = codes.shape[0]
    bk = group_size
    assert k % bk == 0, (k, bk)
    bm = min(bm, m)
    while m % bm:
        bm -= 1
    bn = min(bn, n)
    while n % bn:
        bn -= 1
    nsteps = k // bk
    m2 = shifts is not None

    scale_in = shifts.astype(jnp.int32) if m2 else scale
    args = [x_q.astype(jnp.bfloat16), codes, scale_in]
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, s: (i, s)),
        pl.BlockSpec((bn, bk // 2), lambda i, j, s: (j, s)),
        pl.BlockSpec((bn, 1), lambda i, j, s: (j, s)),
    ]
    if m2:
        args.append(s_max.reshape(n, 1))
        in_specs.append(pl.BlockSpec((bn, 1), lambda i, j, s: (j, 0)))

    kernel = functools.partial(_kernel, w_fmt=w_fmt, nsteps=nsteps, m2=m2)
    if m2:
        def kernel(x_ref, c_ref, s_ref, sm_ref, o_ref):  # noqa: F811
            _kernel(x_ref, c_ref, s_ref, o_ref, w_fmt=w_fmt, nsteps=nsteps,
                    m2=True, smax_ref=sm_ref)

    out = pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn, nsteps),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
    )(*args)
    return out
