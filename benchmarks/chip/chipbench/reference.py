"""Plain reference: a dense decoder's forward in float32 ``jax.numpy``.

It imports nothing of the program. It reads the packed checkpoint the
benchmark built, as plain arrays, and dequantizes each weight itself:
E2M1 codes (bit 3 sign, bits 0-2 the magnitude index into
{0, .5, 1, 1.5, 2, 3, 4, 6}; low nibble = even column) times
``s_max * 2**-shift`` per group, plus the LoRC product ``A @ B``. Every
matrix product runs at ``precision="highest"``; activations, keys and
values stay in float32, with no quantization: this is the model the
checkpoint states, and the served W4A8 path (FP8 activations and KV pages,
bf16 residual stream) is compared against it.

The architecture follows the published descriptions of the two families
(OLMo, arXiv:2402.00838; OPT, arXiv:2205.01068) as the checkpoint's keys
lay them out: pre-norm blocks; LayerNorm (OLMo's without parameters);
rotary embeddings on the first and second halves of each head (OLMo) or
learned positions added to the token embedding (OPT); causal softmax
attention scaled by 1/sqrt(head_dim); SwiGLU (``silu(gate) * up``) or a
ReLU MLP; a final norm and a head tied to the embedding. Departures from
the published models, shared with the checkpoint format: OPT's learned
positions start at 0 (Hugging Face offsets them by 2) and its key
projection has no bias (under softmax a key bias cancels).

``act_bits=4`` quantizes the input of every linear layer token-wise onto
the FP4-E2M1 grid (scale = the token's absolute maximum / 6): the control,
one precision step below the FP8 activations the configuration states.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
E2M1 = jnp.array([0.0, 0.5, 1.0, 1.5, 2.0, 3.0, 4.0, 6.0], jnp.float32)


def dequant(w) -> jnp.ndarray:
    """(out, in) float32 weight of one packed linear (a dict of arrays)."""
    codes = w["codes"].astype(jnp.int32)
    nib = jnp.stack([codes & 15, codes >> 4], axis=-1).reshape(
        codes.shape[0], -1)
    val = E2M1[nib & 7] * jnp.where(nib >= 8, -1.0, 1.0)
    out_f, in_f = val.shape
    shifts = w["shifts"].astype(jnp.int32)
    scale = jnp.ldexp(w["s_max"].astype(jnp.float32), -shifts)  # (out, G)
    g = shifts.shape[-1]
    val = (val.reshape(out_f, g, in_f // g) * scale[..., None]).reshape(
        out_f, in_f)
    if w.get("lorc_a") is not None:
        val = val + jnp.matmul(w["lorc_a"].astype(jnp.float32),
                               w["lorc_b"].astype(jnp.float32), precision=HI)
    return val


def _fp4_act(x):
    """Token-wise quantization onto the E2M1 grid (round to nearest)."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-30) / 6.0
    y = jnp.abs(x) / s
    edges = jnp.array([0.25, 0.75, 1.25, 1.75, 2.5, 3.5, 5.0], jnp.float32)
    idx = jnp.sum(y[..., None] > edges, axis=-1)
    return jnp.sign(x) * E2M1[idx] * s


def _linear(w, x, b=None, act_bits=0):
    if act_bits == 4:
        x = _fp4_act(x)
    y = jnp.matmul(x, dequant(w).T, precision=HI)
    if b is not None:
        y = y + b.astype(jnp.float32)
    return y


def _norm(p, x, kind, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) / jnp.sqrt(var + eps)
    if kind == "layernorm":
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return y


def _rope(x, theta):
    """x: (S, H, hd), positions 0..S-1; rotates the halves of each head."""
    s, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _block(p, x, arch, act_bits):
    """One decoder layer over one sequence x: (S, d) float32."""
    kind, eps = arch["norm_kind"], arch.get("norm_eps", 1e-5)
    h_n, kv_n = arch["n_heads"], arch["n_kv_heads"]
    hd = arch["d_model"] // h_n
    s = x.shape[0]
    a = p["mixer"]["attn"]
    h = _norm(p["mixer"].get("ln", {}), x, kind, eps)
    q = _linear(a["wq"], h, a.get("bq"), act_bits).reshape(s, h_n, hd)
    k = _linear(a["wk"], h, a.get("bk"), act_bits).reshape(s, kv_n, hd)
    v = _linear(a["wv"], h, a.get("bv"), act_bits).reshape(s, kv_n, hd)
    if arch.get("pos_embedding", "rope") == "rope":
        theta = arch.get("rope_theta", 10000.0)
        q, k = _rope(q, theta), _rope(k, theta)
    k = jnp.repeat(k, h_n // kv_n, axis=1)
    v = jnp.repeat(v, h_n // kv_n, axis=1)
    sc = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / jnp.sqrt(
        jnp.float32(hd))
    causal = jnp.tril(jnp.ones((s, s), bool))
    sc = jnp.where(causal[None], sc, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1), v,
                   precision=HI).reshape(s, h_n * hd)
    x = x + _linear(a["wo"], o, a.get("bo"), act_bits)
    f = p["ffn"]["mlp"]
    h = _norm(p["ffn"].get("ln", {}), x, kind, eps)
    up = _linear(f["up"], h, f.get("up_b"), act_bits)
    act = {"silu": jax.nn.silu, "relu": jax.nn.relu}[arch["act_kind"]]
    if "gate" in f:
        z = act(_linear(f["gate"], h, None, act_bits)) * up
    else:
        z = act(up)
    return x + _linear(f["down"], z, f.get("down_b"), act_bits)


@partial(jax.jit, static_argnames=("arch_items", "act_bits"))
def _layer_all(p, xs, arch_items, act_bits):
    arch = dict(arch_items)
    return jax.lax.map(lambda x: _block(p, x, arch, act_bits), xs)


@partial(jax.jit, static_argnames=("arch_items",))
def _embed(params, toks, arch_items):
    arch = dict(arch_items)
    x = params["embed"].astype(jnp.float32)[toks]
    if arch.get("pos_embedding", "rope") == "learned":
        x = x + params["pos_embed"].astype(jnp.float32)[
            jnp.arange(toks.shape[1])][None]
    return x


@partial(jax.jit, static_argnames=("arch_items",))
def _logits(params, h, arch_items):
    """Final norm and tied head over one sequence: (S, V) float32."""
    arch = dict(arch_items)
    h = _norm(params.get("final_ln", {}), h, arch["norm_kind"],
              arch.get("norm_eps", 1e-5))
    return jnp.matmul(h, params["embed"].astype(jnp.float32).T, precision=HI)


def _items(arch: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in arch.items()
                        if isinstance(v, (int, float, str, bool))))


def _head(params) -> dict:
    return {k: params[k] for k in ("embed", "pos_embed", "final_ln")
            if k in params}


def hidden(params, arch: dict, toks, act_bits: int = 0):
    """Last hidden states (B, S, d) float32 of right-padded token rows
    ``toks`` (B, S), computed layer by layer."""
    items = _items(arch)
    x = _embed(_head(params), toks, items)
    stack = params["segments"][0]
    for i in range(arch["n_layers"]):
        p = jax.tree.map(lambda a: a[i], stack)
        x = _layer_all(p, x, items, act_bits)
    return x


def logits(params, arch: dict, h):
    """Logits (S, V) float32 of one sequence's hidden states."""
    return _logits(_head(params), h, _items(arch))
