"""The W4A8 serving checkpoint, built on the device from ``--seed``.

Servers load a quantized checkpoint; they do not quantize when they start.
So this builds what ``repro.core.ptq.quantize_tree`` would hand the server
for the paper's policy (FP4-E2M1 codes, M2 scales, rank-r LoRC factors,
bf16 embeddings) with the same tree structure, shapes and dtypes, in one
jitted call:

* every quantizable weight is drawn N(0, 1/fan_in) (the model's init law),
  rounded to nearest on the E2M1 grid per group of ``group_size`` inputs
  with M2 scales (a row maximum and a power-of-two shift per group), and
  nibble-packed (low nibble = even column);
* LoRC factors are random rank-r factors whose product is about a tenth of
  the weight's size. Without the SVD of the rounding error they do not
  compensate it, but the served arithmetic (the side path of every GEMM) is
  the same, and the reference reads the same factors;
* stacked layers are generated one at a time inside the call (``lax.map``),
  so no more than one layer is ever held in f32.

Embeddings follow the init law (N(0, 0.02)), norm scales are ones and norm
offsets zeros; linear biases, zero at init, are drawn N(0, 0.02) here so the
check covers them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

E2M1_MAX = 6.0
# midpoints between the E2M1 magnitudes 0, .5, 1, 1.5, 2, 3, 4, 6
_E2M1_EDGES = (0.25, 0.75, 1.25, 1.75, 2.5, 3.5, 5.0)
_BIASES = ("bq", "bk", "bv", "bo", "up_b", "down_b")


def _e2m1_codes(x):
    """Nearest E2M1 code of each value of ``x`` (already scaled onto the
    grid's range): bit 3 sign, bits 0-2 the magnitude's index."""
    mag = jnp.abs(x)
    idx = sum((mag > e).astype(jnp.uint8) for e in _E2M1_EDGES)
    return idx | ((x < 0).astype(jnp.uint8) << 3)


def _pow2_neg(k):
    """Exact 2**-k for whole k in [0, 126], from the f32 bit pattern."""
    bits = ((127 - k).astype(jnp.uint32) << 23)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def pack_weight(key, out_f: int, in_f: int, group: int, rank: int):
    """One (out, in) weight as the packed fields (codes, scale, s_max,
    shifts, lorc_a, lorc_b)."""
    kw, ka, kb = jax.random.split(key, 3)
    w = jax.random.normal(kw, (out_f, in_f), jnp.float32) / jnp.sqrt(
        jnp.float32(in_f))
    ng = in_f // group
    wg = w.reshape(out_f, ng, group)
    raw = jnp.maximum(jnp.max(jnp.abs(wg), axis=-1), 1e-12) / E2M1_MAX
    s_max = jnp.max(raw, axis=-1, keepdims=True)
    k = jnp.clip(jnp.ceil(jnp.log2(s_max / raw)), 0, 31).astype(jnp.int32)
    scale = s_max * _pow2_neg(k)
    codes = _e2m1_codes(wg / scale[..., None]).reshape(out_f, in_f)
    packed = codes[:, 0::2] | (codes[:, 1::2] << 4)
    a = jax.random.normal(ka, (out_f, rank), jnp.float32) * (0.3 / rank ** 0.5)
    b = jax.random.normal(kb, (rank, in_f), jnp.float32) * (0.3 / in_f ** 0.5)
    return (packed.astype(jnp.uint8), scale.astype(jnp.float32),
            s_max.astype(jnp.float32), k.astype(jnp.int8),
            a.astype(jnp.bfloat16), b.astype(jnp.bfloat16))


def _path_str(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


def build(cfg, seed: int, quant: dict):
    """The serving tree for ``cfg`` under ``quant`` (``w_fmt``, ``a_fmt``,
    ``group_size``, ``lorc_rank``), on the default device."""
    from repro import models
    from repro.core.ptq import effective_group, is_quantizable
    from repro.models.layers import PackedLinear
    from repro.models.params import ParamDef

    assert quant["w_fmt"] == "fp4_e2m1" and quant["scale_mode"] == "m2"
    defs = models.build_def(cfg)
    is_def = lambda x: isinstance(x, ParamDef)
    flat, treedef = jax.tree_util.tree_flatten_with_path(defs, is_leaf=is_def)
    rank = int(quant["lorc_rank"])
    statics = []
    for path, d in flat:
        p = _path_str(path)
        if is_quantizable(d, p):
            statics.append(("packed", p, d,
                            effective_group(d.shape[-1], quant["group_size"])))
        else:
            statics.append(("dense", p, d, 0))

    def make(key):
        leaves = []
        for i, (kind, p, d, group) in enumerate(statics):
            k = jax.random.fold_in(key, i)
            if kind == "packed":
                out_f, in_f = d.shape[-2:]
                lead = d.shape[:-2]
                one = lambda kk: pack_weight(kk, out_f, in_f, group, rank)
                if lead:
                    n = 1
                    for s in lead:
                        n *= s
                    fields = jax.lax.map(one, jax.random.split(k, n))
                    fields = [f.reshape(lead + f.shape[1:]) for f in fields]
                else:
                    fields = one(k)
                leaves.append(PackedLinear(
                    *fields, w_fmt=quant["w_fmt"], a_fmt=quant["a_fmt"],
                    group_size=group))
                continue
            dt = jnp.dtype(d.dtype)
            name = p.rsplit("/", 1)[-1]
            if d.init == "ones":
                leaves.append(jnp.ones(d.shape, dt))
            elif d.init == "zeros" and name not in _BIASES:
                leaves.append(jnp.zeros(d.shape, dt))
            elif d.init in ("embed", "zeros"):
                leaves.append((jax.random.normal(k, d.shape, jnp.float32)
                               * 0.02).astype(dt))
            else:
                std = d.scale / float(max(d.shape[-1], 1)) ** 0.5
                leaves.append((jax.random.normal(k, d.shape, jnp.float32)
                               * std).astype(dt))
        return jax.tree.unflatten(treedef, leaves)

    key = jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)
    return jax.block_until_ready(jax.jit(make)(key))
