"""All-to-all expert parallelism (beyond-paper perf work — EXPERIMENTS.md
§Perf, deepseek-v3 train hillclimb).

The einsum-dispatch MoE (models/moe.py) keeps tokens data-sharded and
experts model-sharded; at deepseek-v3 scale that forces ZeRO-3 at rest and
GSPMD then ALL-GATHERS ~22 GB of expert weights per layer per direction —
the dominant collective term of the train_4k baseline (53.7 s).

This variant moves TOKENS instead of WEIGHTS (classic EP / DeepSpeed-MoE /
Switch):
  * experts shard over the WHOLE mesh (E == P ranks x E_loc); each rank's
    expert weights are fully local — no weight collectives at all;
  * each rank dispatches its own sequence shard (exactly the Megatron-SP
    residual shard, so no extra resharding on entry/exit);
  * dispatch is sort-based: assignments argsorted by expert id, packed into
    capacity-C per-destination slots (overflow dropped — same capacity
    semantics as the einsum path), moved with lax.all_to_all, FFN'd
    locally, moved back, combined by scatter-add with routing weights.

Traffic per layer per device ~= 2 directions x n_loc x top_k x d x cf
bytes — independent of expert count/size.

Enabled via set_moe_impl('a2a', mesh) (the launcher does this for train
cells when cfg.moe.n_experts is divisible by the mesh size).
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .layers import activation, batched_linear, linear, mlp, quant_act
from .moe import _dispatch_masks

__all__ = ["set_moe_impl", "get_moe_impl", "moe_layer_a2a", "moe_decode_ep"]

_MOE_IMPL = [("einsum", None)]  # ('einsum'|'a2a'|'ep_decode', mesh)


def set_moe_impl(kind: str, mesh=None):
    _MOE_IMPL[0] = (kind, mesh)


def get_moe_impl():
    return _MOE_IMPL[0]


def _dispatch_local(x, logits, top_k: int, capacity: int, n_experts: int):
    """Sort-based local dispatch. x: (n, d); logits: (n, E) f32.
    Returns (send (E, C, d), combine_idx (n*k,), slot (n*k,), weight (n*k,))."""
    n, d = x.shape
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, top_k)  # (n, k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    flat_e = top_i.reshape(-1)  # (n*k,)
    flat_t = jnp.repeat(jnp.arange(n), top_k)
    flat_p = top_p.reshape(-1)

    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    sorted_t = flat_t[order]
    sorted_p = flat_p[order]
    # position within expert run
    pos = jnp.arange(n * top_k) - jnp.searchsorted(sorted_e, sorted_e, side="left")
    keep = pos < capacity
    slot = jnp.where(keep, sorted_e * capacity + pos, n_experts * capacity)  # drop slot

    send = jnp.zeros((n_experts * capacity, d), x.dtype)
    send = send.at[slot].set(x[sorted_t], mode="drop")
    return send.reshape(n_experts, capacity, d), sorted_t, slot, sorted_p * keep


def _a2a2(x, axes):
    """all_to_all over one or two mesh axes. x: (P, C, d) with P = prod of
    axis sizes; returns the transposed exchange (P, C, d)."""
    if len(axes) == 1:
        return jax.lax.all_to_all(x, axes[0], split_axis=0, concat_axis=0,
                                  tiled=True)
    a, b = axes
    na = jax.lax.axis_size(a)
    nb = jax.lax.axis_size(b)
    p, c, d = x.shape
    # (na, nb, C, d): exchange the inner axis first, then the outer
    x = x.reshape(na, nb * c, d)
    x = jax.lax.all_to_all(x, a, split_axis=0, concat_axis=0, tiled=True)
    x = x.reshape(na, nb, c, d).swapaxes(0, 1).reshape(nb, na * c, d)
    x = jax.lax.all_to_all(x, b, split_axis=0, concat_axis=0, tiled=True)
    x = x.reshape(nb, na, c, d).swapaxes(0, 1).reshape(p, c, d)
    return x


def _expert_ffn(recv, wu, wg, wd, act_kind, a_fmt, e_loc, capacity):
    """recv: (P, E_loc*C, d): for each source rank, the C slots of each of
    our E_loc experts. Regroup to (E_loc, P*C, d) for batched expert FFNs."""
    p = recv.shape[0]
    d = recv.shape[-1]
    t = recv.reshape(p, e_loc, capacity, d).swapaxes(0, 1).reshape(e_loc, p * capacity, d)
    tq = quant_act(t, a_fmt)
    up = jnp.einsum("etd,efd->etf", tq, wu, preferred_element_type=jnp.float32).astype(t.dtype)
    if wg is not None:
        g = jnp.einsum("etd,efd->etf", tq, wg, preferred_element_type=jnp.float32).astype(t.dtype)
        h = activation(g, act_kind) * up
    else:
        h = activation(up, act_kind)
    hq = quant_act(h, a_fmt)
    out = jnp.einsum("etf,edf->etd", hq, wd, preferred_element_type=jnp.float32).astype(t.dtype)
    # inverse regroup: (E_loc, P*C, d) -> (P, E_loc*C, d)
    out = out.reshape(e_loc, p, capacity, d).swapaxes(0, 1).reshape(p, e_loc * capacity, d)
    return out


def _ep_axes(mesh, n_experts: int):
    """EP axes for the expert stack, mirroring the placement rule in
    launch.sharding.serve_rules: the whole mesh when the expert count
    divides it, else the ('data', 'model') subset, else None (no EP)."""
    total = 1
    for a in mesh.shape:
        total *= mesh.shape[a]
    if n_experts % total == 0:
        return tuple(mesh.shape.keys())
    dm = tuple(a for a in ("data", "model") if a in mesh.shape)
    size = 1
    for a in dm:
        size *= mesh.shape[a]
    if dm and n_experts % size == 0:
        return dm
    return None


def moe_decode_ep(p, x, cfg, mesh, a_fmt: Optional[str] = None,
                  group_size: int = 1024):
    """Expert-parallel MoE for the *paged decode/prefill* path (serving on
    a mesh). x: (B, S, d) replicated -> (out (B, S, d), aux scalar).

    Routing, capacity math and the dispatch/combine einsums are the exact
    einsum-path code from models/moe.moe_layer — replicated on every rank,
    so token->expert assignment is identical to the single-device engine by
    construction. Only the three expert FFN GEMMs run inside a shard_map
    over the expert stack (the layout serve_rules already placed the W4A8
    expert weights in: dim0 over the EP axes, fully local — no weight
    gather). The combine einsum contracts the expert dim *outside* the
    shard_map, so GSPMD inserts the one all-reduce this layer needs — the
    same collective class as the TP MLP.

    Unlike moe_layer_a2a this has no sequence-divisibility constraint
    (decode steps are (B, 1, d)): tokens stay replicated, experts move
    nothing. Weights whose leading dim is not the expert count (e.g. a
    shared LoRC factor) force the replicated fallback."""
    m = cfg.moe
    e = m.n_experts
    axes = _ep_axes(mesh, e)
    stacked = {k: p[k] for k in ("wu", "wg", "wd") if k in p}
    if axes is None or any(
            getattr(l, "ndim", 0) < 1 or l.shape[0] != e
            for l in jax.tree.leaves(stacked)):
        from .moe import moe_layer

        return moe_layer(p, x, cfg, a_fmt=a_fmt, group_size=group_size)

    # -- replicated dispatch: verbatim moe_layer math ----------------------
    b, s, d = x.shape
    n = b * s
    g = max(n // group_size, 1)
    sg = -(-n // g)
    pad = g * sg - n
    capacity = max(int(sg * m.top_k / e * m.capacity_factor), 1)

    xf = x.reshape(n, d)
    if pad:
        xf = jnp.pad(xf, ((0, pad), (0, 0)))
    xf = xf.reshape(g, sg, d)
    logits = linear(p["router"], xf.astype(jnp.float32))  # router in f32
    dispatch, combine, probs = _dispatch_masks(logits, m.top_k, capacity)
    dispatch = dispatch.astype(x.dtype)
    combine = combine.astype(jnp.float32)

    xq = quant_act(xf, a_fmt)
    ex_in = jnp.einsum("gsec,gsd->gecd", dispatch, xq)
    xe = jnp.moveaxis(ex_in, 1, 0).reshape(e, g * capacity, d)

    # -- expert FFNs: local shard of the expert stack ----------------------
    def ffn(xe_l, w):
        up = batched_linear(w["wu"], xe_l)
        if "wg" in w:
            h = activation(batched_linear(w["wg"], xe_l), cfg.act_kind) * up
        else:
            h = activation(up, cfg.act_kind)
        hq = quant_act(h, a_fmt)
        return batched_linear(w["wd"], hq)

    espec = jax.tree.map(
        lambda l: P(axes, *([None] * (l.ndim - 1))), stacked)
    eo = jax.shard_map(ffn, mesh=mesh,
                   in_specs=(P(axes, None, None), espec),
                   out_specs=P(axes, None, None),
                   check_vma=False)(xe, stacked)

    ex_out = jnp.moveaxis(eo.reshape(e, g, capacity, d), 0, 1)
    out = jnp.einsum("gsec,gecd->gsd", combine, ex_out.astype(jnp.float32))
    out = out.reshape(g * sg, d)
    if pad:
        out = out[:n]
    out = out.reshape(b, s, d).astype(x.dtype)

    if m.n_shared_experts:
        out = out + mlp(p["shared"], x, cfg, a_fmt=a_fmt)

    frac_tokens = jnp.mean(
        jnp.sum(dispatch, axis=-1).astype(jnp.float32), axis=(0, 1))
    frac_probs = jnp.mean(probs, axis=(0, 1))
    aux = e * jnp.sum(frac_tokens * frac_probs)
    return out, aux


def moe_layer_a2a(p, x, cfg, mesh, a_fmt: Optional[str] = None):
    """x: (B, S, d) with the residual in SP layout (batch over dp, seq over
    'model'). Returns (out, aux). Requires E % mesh_size == 0."""
    m = cfg.moe
    b, s, d = x.shape
    e = m.n_experts
    dp_only = tuple(a for a in ("pod", "data") if a in mesh.shape)

    def _size(ax):
        n = 1
        for a in ax:
            n *= mesh.shape[a]
        return n

    # widest EP degree that divides the expert count
    axes = None
    for cand in (("data", "model"), ("model",)):
        if all(a in mesh.shape for a in cand) and e % _size(cand) == 0:
            axes = cand
            break
    if axes is None:
        raise ValueError(f"E={e} not divisible by any mesh-axis product")
    psize = _size(axes)
    e_loc = e // psize

    msize = mesh.shape.get("model", 1)
    dsize = mesh.shape.get("data", 1)
    n_loc = (b // dsize) * (s // msize) if s % msize == 0 else None
    assert n_loc, "seq must divide the model axis for a2a MoE"
    capacity = max(int(n_loc * m.top_k / e * m.capacity_factor), 1)

    router_w = p["router"]
    wu, wd = p["wu"], p["wd"]
    wg = p.get("wg")

    def body(xb, rw, wu_l, wg_l, wd_l):
        # xb: (B_loc, S_loc, d) — this rank's residual shard
        bl, sl, _ = xb.shape
        xf = xb.reshape(bl * sl, d)
        logits = (xf.astype(jnp.float32) @ rw.astype(jnp.float32).T)
        send, sorted_t, slot, weight = _dispatch_local(
            quant_act(xf, a_fmt), logits, m.top_k, capacity, e
        )
        # (E, C, d) -> (P, E_loc*C, d): chunk p holds the slots of the
        # experts owned by rank p (expert dim is rank-major sharded)
        send2 = send.reshape(psize, e_loc * capacity, d)
        recv = _a2a2(send2, axes)  # (P, E_loc*C, d): sources x our experts
        out_recv = _expert_ffn(recv, wu_l, wg_l, wd_l, cfg.act_kind, a_fmt,
                               e_loc, capacity)
        back = _a2a2(out_recv, axes).reshape(e * capacity, d)
        gathered = back[jnp.clip(slot, 0, e * capacity - 1)]
        yf = jnp.zeros((bl * sl, d), jnp.float32)
        yf = yf.at[sorted_t].add(gathered.astype(jnp.float32) * weight[:, None])
        # aux load-balance stats (local)
        frac = jnp.mean(jax.nn.one_hot(jnp.argmax(logits, -1), e), axis=0)
        aux = e * jnp.sum(frac * jnp.mean(jax.nn.softmax(logits, -1), axis=0))
        aux = jax.lax.pmean(aux, axes)
        return yf.reshape(bl, sl, d).astype(xb.dtype), aux

    expert_spec = P(axes, None, None)
    if wg is not None:
        fn = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(dp_only, "model", None), P(None, None), expert_spec,
                      expert_spec, expert_spec),
            out_specs=(P(dp_only, "model", None), P()),
            check_vma=False,
        )
        out, aux = fn(x, router_w, wu, wg, wd)
    else:
        fn = jax.shard_map(
            lambda xb, rw, a, c: body(xb, rw, a, None, c), mesh=mesh,
            in_specs=(P(dp_only, "model", None), P(None, None), expert_spec,
                      expert_spec),
            out_specs=(P(dp_only, "model", None), P()),
            check_vma=False,
        )
        out, aux = fn(x, router_w, wu, wd)

    if m.n_shared_experts:
        out = out + mlp(p["shared"], x, cfg, a_fmt=a_fmt)
    return out, aux
