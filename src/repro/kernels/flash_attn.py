"""Pallas TPU kernel: flash attention (forward), online softmax in VMEM.

The dry-run shows attention softmax materialization dominating the memory
term of large train/prefill cells (EXPERIMENTS.md §Perf: deepseek train —
~7.5 TB/device of (S, S)-class f32 traffic across mask-add / sub-exp /
divide / convert passes). This kernel keeps the (q_block, kv_block) score
tile in VMEM, carries (m, l, acc) accumulators across kv blocks, and writes
ONLY the (S, d) output — the standard flash-attention dataflow mapped to
the TPU: MXU for the two tile matmuls, VPU for the online-softmax updates,
one HBM pass over q/k/v and one output write.

Forward-only: the training path's backward uses XLA autodiff over the
q-chunked jnp attention (models/attention.py); serving (prefill) is where
this kernel slots in. Validated against the jnp oracle in interpret mode
(tests/test_kernels.py) over shape/dtype sweeps.

Grid: (n_q_blocks,) with the kv loop INSIDE the kernel body (fori_loop) so
the accumulators live in registers/VMEM for the whole row block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["flash_attention_pallas", "flash_attention_ref"]

_NEG_INF = -1e30


def flash_attention_ref(q, k, v, causal: bool = True):
    """Oracle: plain softmax attention. q/k/v: (B, S|T, H, hd)."""
    b, s, h, hd = q.shape
    t = k.shape[1]
    scale = 1.0 / float(hd) ** 0.5
    sc = jnp.einsum("bqhd,bthd->bhqt", q.astype(jnp.float32),
                    k.astype(jnp.float32)) * scale
    if causal:
        msk = jnp.where(jnp.arange(t)[None] > jnp.arange(s)[:, None], _NEG_INF, 0.0)
        sc = sc + msk[None, None]
    p = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("bhqt,bthd->bqhd", p, v.astype(jnp.float32))
    return o.astype(v.dtype)


def _kernel(q_ref, k_ref, v_ref, o_ref, *, causal, block_q, block_k, t):
    """One (batch*head, q-block) program: loop kv blocks inside."""
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)  # (block_q, hd); leading dim 1 = bh block
    scale = 1.0 / float(q.shape[-1]) ** 0.5
    q = q * scale
    nk = t // block_k
    # whole-block VMEM reads once; the kv loop slices the loaded values
    # (pl.load with a scalar leading index trips the interpret-mode
    # discharge rule on this jax version)
    k_all = k_ref[0]
    v_all = v_ref[0]

    def body(ki, carry):
        m_run, l_run, acc = carry
        k_blk = jax.lax.dynamic_slice(
            k_all, (ki * block_k, 0), (block_k, k_all.shape[-1]))
        v_blk = jax.lax.dynamic_slice(
            v_all, (ki * block_k, 0), (block_k, v_all.shape[-1]))
        s_blk = jax.lax.dot_general(
            q, k_blk.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (block_q, block_k)
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s_blk.shape, 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s_blk.shape, 1)
            s_blk = jnp.where(k_pos > q_pos, _NEG_INF, s_blk)
        m_new = jnp.maximum(m_run, jnp.max(s_blk, axis=-1))
        p = jnp.exp(s_blk - m_new[:, None])
        corr = jnp.exp(m_run - m_new)
        l_new = l_run * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[:, None] + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc

    m0 = jnp.full((block_q,), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    a0 = jnp.zeros((block_q, v_ref.shape[-1]), jnp.float32)
    m_f, l_f, acc = jax.lax.fori_loop(0, nk, body, (m0, l0, a0))
    o_ref[0] = (acc / jnp.maximum(l_f[:, None], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret"))
def flash_attention_pallas(q, k, v, causal: bool = True, block_q: int = 128,
                           block_k: int = 128, interpret: bool = False):
    """q: (B, S, H, hd); k/v: (B, T, H, hd|dv). Returns (B, S, H, dv)."""
    b, s, h, hd = q.shape
    t = k.shape[1]
    dv = v.shape[-1]
    block_q = min(block_q, s)
    while s % block_q:
        block_q -= 1
    block_k = min(block_k, t)
    while t % block_k:
        block_k -= 1

    # fold (B, H) into the grid's leading axis; layout (BH, S, hd)
    qf = jnp.moveaxis(q, 2, 1).reshape(b * h, s, hd)
    kf = jnp.moveaxis(k, 2, 1).reshape(b * h, t, hd)
    vf = jnp.moveaxis(v, 2, 1).reshape(b * h, t, dv)

    out = pl.pallas_call(
        functools.partial(_kernel, causal=causal, block_q=block_q,
                          block_k=block_k, t=t),
        grid=(b * h, s // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, t, hd), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, t, dv), lambda i, j: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, dv), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s, dv), v.dtype),
        interpret=interpret,
    )(qf, kf, vf)
    return jnp.moveaxis(out.reshape(b, h, s, dv), 1, 2)
