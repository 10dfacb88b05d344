"""Pallas TPU kernel: fused token-wise FP8 (E4M3) activation quantization.

One VMEM pass per row-block: per-token absmax -> scale = absmax / fmt.max
-> RNE rounding onto the saturating ExMy grid. The grid math lives in
kernels.common (shared with the fused single-pass GEMM, which runs the same
quantization *inside* its M-tile) and matches core.formats.quantize_to_grid
exactly.

This standalone kernel remains for call-sites that need the quantized
activations themselves (calibration capture, compression); the serving GEMM
no longer round-trips through it — see w4a8_fused.py.

Target layout: rows are tokens, the full feature row lives in one block
(feature dims here are <= 73728 -> <= 288 KiB f32 per 8-row block, well
inside VMEM).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.formats import FORMATS

from .common import quantize_rows as _quantize_rows

__all__ = ["act_quant_pallas"]


def _kernel(x_ref, q_ref, s_ref, *, fmt):
    x = x_ref[...].astype(jnp.float32)
    q, scale = _quantize_rows(x, fmt)
    q_ref[...] = q.astype(q_ref.dtype)
    s_ref[...] = scale


@functools.partial(jax.jit, static_argnames=("fmt_name", "block_rows", "interpret"))
def act_quant_pallas(x, fmt_name: str = "fp8_e4m3", block_rows: int = 8,
                     interpret: bool = False):
    """x: (..., d) -> (values_on_grid f32, scale (..., 1) f32).

    Semantics identical to kernels.ref.act_quant_ref (asserted by the
    sweep tests). Compiled for the TPU unless ``interpret=True`` asks for
    the Pallas interpreter."""
    fmt = FORMATS[fmt_name]
    lead = x.shape[:-1]
    d = x.shape[-1]
    x2 = x.reshape(-1, d)
    t = x2.shape[0]
    bt = min(block_rows, t)
    while t % bt:
        bt -= 1

    q, s = pl.pallas_call(
        functools.partial(_kernel, fmt=fmt),
        grid=(t // bt,),
        in_specs=[pl.BlockSpec((bt, d), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((bt, d), lambda i: (i, 0)),
            pl.BlockSpec((bt, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t, d), jnp.float32),
            jax.ShapeDtypeStruct((t, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x2)
    return q.reshape(*lead, d), s.reshape(*lead, 1)
