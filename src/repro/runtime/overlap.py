"""Compute/communication overlap: ring-decomposed all-gather matmul.

Megatron-SP inserts an all-gather of the seq-sharded residual before each
qkv/up projection. XLA can schedule that gather asynchronously, but the
matmul still waits for the FULL gathered tensor. This shard_map kernel
decomposes the gather into ring steps (jax.lax.ppermute) and interleaves a
partial matmul with each hop — the classic latency-hiding collective-matmul
(Wang et al.; also in MaxText). On a dry-run the win shows up structurally:
the single big all-gather disappears in favour of P-1 collective-permutes
each 1/P the size, which the TPU scheduler can overlap with the P partial
matmuls (hypothesis->measure log: EXPERIMENTS.md §Perf).

y = x @ w.T with x (B, S, d) sharded P('data', 'model', None) over seq and
w (out, d) sharded P('model', None) over out: each step computes the local
shard's contribution to every output row block while the next x shard is in
flight.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ["ring_ag_matmul"]


def _ring_body(x_blk, w, axis_name: str):
    """x_blk: (B, s_loc, d) local seq shard; w: (out_loc, d) local rows.
    Returns (B, P*s_loc, out_loc): the full-seq output for local out rows."""
    p = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)

    def step(carry, i):
        x_cur, acc = carry
        # overlap: matmul on the shard we hold while the permute moves it on
        part = jax.lax.dot_general(
            x_cur, w, (((2,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(x_cur.dtype)
        src_pos = (idx - i) % p  # whose shard we just consumed
        acc = jax.lax.dynamic_update_slice(
            acc, part, (0, src_pos * x_blk.shape[1], 0)
        )
        x_nxt = jax.lax.ppermute(
            x_cur, axis_name, [(j, (j + 1) % p) for j in range(p)]
        )
        return (x_nxt, acc), None

    acc0 = jnp.zeros((x_blk.shape[0], p * x_blk.shape[1], w.shape[0]), x_blk.dtype)
    (_, acc), _ = jax.lax.scan(step, (x_blk, acc0), jnp.arange(p))
    return acc


def ring_ag_matmul(x, w, mesh, axis_name: str = "model", dp=("data",)):
    """Overlapped all-gather(x over seq) + matmul. x: (B, S, d) seq-sharded
    on ``axis_name``; w: (out, d) out-sharded on ``axis_name``.
    Returns (B, S, out) with out sharded on ``axis_name``."""
    fn = jax.shard_map(
        partial(_ring_body, axis_name=axis_name),
        mesh=mesh,
        in_specs=(P(dp, axis_name, None), P(axis_name, None)),
        out_specs=P(dp, None, axis_name),
        check_vma=False,
    )
    return fn(x, w)
