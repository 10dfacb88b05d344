"""jit'd wrappers over the Pallas kernels with jnp-reference fallback.

Backend selection:
  * 'ref'      — pure-jnp oracle semantics (the default; also what the
                 dry-run lowers, so rooflines see realistic HLO).
  * 'pallas'   — pl.pallas_call TPU kernels, always compiled. Selecting it
                 where JAX's platform is not 'tpu' raises: the interpreter
                 never stands in for the chip unasked.
  * 'pallas_interpret' — the same kernels under the Pallas interpreter
                 (CPU tests, debugging on any platform).
Set via set_backend() or REPRO_KERNEL_BACKEND env var.

Under the pallas backend the hot path is the *fused single-pass* kernel
(kernels/w4a8_fused.py): FP8 activation quantization happens inside the GEMM
M-tile and the LoRC correction is a fused epilogue — nothing round-trips
through HBM between quantize, decode, matmul, and correct. Block sizes come
from the autotuner cache (kernels/autotune.py), with a shape heuristic on
cache miss. Stacked weights (MoE experts, MLA absorbed heads) go through
w4a8_matmul_batched instead of densifying via dequant_packed.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from . import ref as _ref

_BACKEND = os.environ.get("REPRO_KERNEL_BACKEND", "ref")

# Serving mesh the kernel wrappers shard_map over — paged decode attention
# by head, the fused GEMM column-parallel (None = single-device). Scoped by
# the serving engine around every trace — a module global like _BACKEND,
# read at trace time.
_SERVING_MESH = [None]


def set_backend(name: str):
    global _BACKEND
    assert name in ("ref", "pallas", "pallas_interpret")
    if name == "pallas":
        _require_tpu()
    _BACKEND = name


def get_backend() -> str:
    return _BACKEND


def set_serving_mesh(mesh):
    """Install (or clear, with None) the mesh the kernel entry points
    shard_map over. Head-sharded decode is only taken when the KV-head dim
    divides the 'model' axis; otherwise the call falls through to the
    unsharded dataflow and GSPMD handles placement. The 2-D fused GEMM
    always runs column-parallel under shard_map on a mesh, and raises
    where its output rows do not split into whole lane tiles."""
    _SERVING_MESH[0] = mesh


def get_serving_mesh():
    return _SERVING_MESH[0]


def _require_tpu():
    platform = jax.default_backend()
    if platform != "tpu":
        raise RuntimeError(
            f"kernel backend 'pallas' compiles for the TPU, but JAX's "
            f"platform is {platform!r}; select 'pallas_interpret' to run "
            "the kernels under the Pallas interpreter")


def interpret_mode() -> bool:
    """True when pallas kernels run under the interpreter — only when the
    'pallas_interpret' backend asks for it. 'pallas' (also when set via
    REPRO_KERNEL_BACKEND) is checked here to be on a TPU."""
    if _BACKEND == "pallas":
        _require_tpu()
    return _BACKEND == "pallas_interpret"


def _block_sizes(kind: str, w, m: int, n: int, k: int, batch: int = 1,
                 transpose_w: bool = False):
    from .autotune import best_block_sizes

    return best_block_sizes(
        kind, batch=batch, m=m, n=n, k=k, w_fmt=w.w_fmt, a_fmt=w.a_fmt,
        group_size=w.group_size, m2=w.shifts is not None,
        lorc_rank=0 if w.lorc_a is None else int(w.lorc_a.shape[-1]),
        transpose_w=transpose_w,
    )


def act_quant(x, fmt_name: str = "fp8_e4m3"):
    """Token-wise FP8 quantization -> (values_on_grid, scale)."""
    if _BACKEND.startswith("pallas"):
        from .act_quant import act_quant_pallas

        return act_quant_pallas(x, fmt_name, interpret=interpret_mode())
    return _ref.act_quant_ref(x, fmt_name)


def w4a8_matmul(x, w):
    """x: (..., in); w: PackedLinear (2D codes after any scan slicing).

    Pallas backend: ONE fused kernel — in-kernel FP8 act-quant, packed-FP4
    decode, f32 accumulation, LoRC epilogue — a single HBM write."""
    assert w.codes.ndim == 2, "stacked PackedLinear must go through w4a8_matmul_batched"
    if _BACKEND.startswith("pallas"):
        from .w4a8_fused import w4a8_fused_matmul_pallas

        lead = x.shape[:-1]
        k = x.shape[-1]
        x2 = x.reshape(-1, k)
        fields = (w.codes, w.scale, w.s_max, w.shifts, w.lorc_a, w.lorc_b)
        mesh = _SERVING_MESH[0]
        msize = 1 if mesh is None else mesh.shape.get("model", 1)
        n_loc = w.out_features // msize
        if msize > 1 and (w.out_features % msize or n_loc % 128):
            raise ValueError(
                f"the fused W4A8 GEMM runs column-parallel on a serving mesh "
                f"(a Mosaic call cannot be partitioned automatically): its "
                f"{w.out_features} output rows do not split into 'model'="
                f"{msize} shards of whole 128-lane tiles")
        bm, bn = _block_sizes("fused", w, x2.shape[0], n_loc, k)

        def gemm(xs, *f):
            return w4a8_fused_matmul_pallas(
                xs, *f, w_fmt=w.w_fmt, a_fmt=w.a_fmt,
                group_size=w.group_size, bm=bm, bn=bn,
                interpret=interpret_mode())

        if msize > 1:
            from jax.sharding import PartitionSpec as P

            # each 'model' shard owns N/m weight rows; LoRC's B factor
            # (rank x K) and the activations replicate
            row = P("model", None)
            specs = tuple(None if f is None else
                          (P() if name == "lorc_b" else row)
                          for name, f in zip(
                              ("codes", "scale", "s_max", "shifts",
                               "lorc_a", "lorc_b"), fields))
            gemm = jax.shard_map(
                gemm, mesh=mesh, in_specs=(P(),) + specs,
                out_specs=P(None, "model"), check_vma=False)
        y = gemm(x2, *fields)
        return y.reshape(*lead, -1).astype(x.dtype)
    return _ref.w4a8_matmul_ref(
        x, w.codes, w.scale, w.lorc_a, w.lorc_b,
        w_fmt=w.w_fmt, a_fmt=w.a_fmt, group_size=w.group_size,
        s_max=w.s_max, shifts=w.shifts,
    )


def w4a8_matmul_batched(x, w, transpose_w: bool = False,
                        quantize_acts: bool = True):
    """Stacked-weight GEMM straight off the packed codes (no densify).

    x: (E, M, D); w: batched PackedLinear (codes (E, out, in/2)).
    normal: D == in_features -> (E, M, out); transposed: D == out (contract
    the weight's out rows — MLA absorbed q path) -> (E, M, in).
    ``quantize_acts=False`` skips the FP8 activation quantization (latent
    absorbed paths feed already-attenuated activations). Returns f32.
    """
    assert w.codes.ndim == 3, "2-D PackedLinear goes through w4a8_matmul"
    a_fmt = w.a_fmt if quantize_acts else None
    if _BACKEND.startswith("pallas"):
        from .w4a8_fused import w4a8_fused_batched_pallas

        e, m, _ = x.shape
        bm, bn = _block_sizes("fused_batched", w, m, w.codes.shape[1],
                              x.shape[-1], batch=e, transpose_w=transpose_w)
        return w4a8_fused_batched_pallas(
            x, w.codes, w.scale, w.s_max, w.shifts, w.lorc_a, w.lorc_b,
            w_fmt=w.w_fmt, a_fmt=a_fmt, group_size=w.group_size,
            bm=bm, bn=bn, transpose_w=transpose_w, interpret=interpret_mode(),
        )
    return _ref.w4a8_batched_matmul_ref(
        x, w.codes, w.scale, w.lorc_a, w.lorc_b,
        w_fmt=w.w_fmt, a_fmt=a_fmt, group_size=w.group_size,
        transpose_w=transpose_w, s_max=w.s_max, shifts=w.shifts,
    )


def _layer_formats(pool_layer, key: str):
    """Derive the (active, frozen) PageFormats from a pool slice's leaves —
    dtype picks quantized vs bf16, the zero-size ``_fp4`` marker picks
    packed FP4 over FP8, and a ``<key>_fz`` leaf announces the dedicated
    packed frozen region (mirrors runtime.kv_cache.pool_format /
    frozen_format without importing the runtime layer)."""
    from .common import page_format

    leaf = pool_layer[key]
    if leaf.dtype != jnp.uint8:
        name = None
    else:
        name = "fp4_e2m1" if "_fp4" in pool_layer else "fp8_e4m3"
    frozen = ("fp4_e2m1" if key + "_fz" in pool_layer else None)
    return page_format(name), page_format(frozen) if frozen else None


def _fz_operands(pool_layer, names):
    """The frozen-region operand dict for the kernel/oracle call: the
    ``*_fz`` leaves when present, else all-None (the wrappers skip the
    frozen operand block entirely)."""
    out = {}
    for name in names:
        for suffix in ("_fz", "_fz_smax", "_fz_shift"):
            out[name + suffix] = pool_layer.get(name + suffix)
    return out


def _pool_shard_spec(name: str, leaf, msize: int):
    """PartitionSpec for one per-layer pool-slice leaf under head-sharded
    decode: 4-D ``(pages, page, KV, hd)`` code stores shard their head dim,
    per-(page, head) ``*_shift`` scales co-shard with them, and everything
    else (per-page smax, MLA latents, zero-size format markers) replicates.
    """
    from jax.sharding import PartitionSpec as P

    if leaf.ndim == 4 and leaf.size and leaf.shape[2] % msize == 0:
        return P(None, None, "model", None)
    if leaf.ndim == 2 and name.endswith("_shift") and \
            leaf.shape[1] % msize == 0:
        return P(None, "model")
    return P()


def paged_decode_attn(q, pool_layer, page_table, kv_lens, window: int = 0):
    """Paged decode attention over one layer's quantized KV pool slice.

    q: (B, H, hd) single-token queries; pool_layer: one layer of a
    runtime.kv_cache GQA pool ({'k', 'v'} + fp8 scale leaves, plus the
    packed ``*_fz`` frozen-region leaves in a mixed-precision pool);
    page_table: (B, PP) int32 — entries >= P+1 are frozen logical ids;
    kv_lens: (B,) int32 valid token counts; ``window``: sliding-window size
    (0 = full history). Returns (B, H, dv) f32.

    With a serving mesh installed (``set_serving_mesh``) and the KV-head dim
    divisible by the 'model' axis, the whole dataflow runs under
    ``shard_map`` with pages/scales head-sharded: each shard attends its
    own KV-head group against its slice of every page — queries arrive
    head-sharded, no collectives, outputs stay head-sharded. Non-divisible
    head counts fall through to the unsharded call (GSPMD places it).
    """
    mesh = _SERVING_MESH[0]
    if mesh is not None:
        msize = mesh.shape.get("model", 1)
        kvh, h = pool_layer["k"].shape[2], q.shape[1]
        if msize > 1 and kvh % msize == 0 and h % msize == 0:
            from jax.sharding import PartitionSpec as P

            specs = {n: _pool_shard_spec(n, l, msize)
                     for n, l in pool_layer.items()}
            hspec = P(None, "model", None)
            fn = jax.shard_map(
                lambda qv, pl, pt, kl: _paged_decode_attn_impl(
                    qv, pl, pt, kl, window=window),
                mesh=mesh, in_specs=(hspec, specs, P(), P()),
                out_specs=hspec, check_vma=False)
            return fn(q, pool_layer, page_table, kv_lens)
    return _paged_decode_attn_impl(q, pool_layer, page_table, kv_lens,
                                   window=window)


def _paged_decode_attn_impl(q, pool_layer, page_table, kv_lens,
                            window: int = 0):
    """Single-shard paged decode attention (backend dispatch unchanged —
    this is exactly the pre-mesh dataflow; under shard_map every shape
    below is the per-shard local shape)."""
    kp, vp = pool_layer["k"], pool_layer["v"]
    fmt, frozen = _layer_formats(pool_layer, "k")
    if fmt.quantized:
        ksm, ksh = pool_layer["k_smax"], pool_layer["k_shift"]
        vsm, vsh = pool_layer["v_smax"], pool_layer["v_shift"]
    else:  # dummies keep the kernel operand list static across formats
        ksm = vsm = jnp.zeros((1,), jnp.float32)
        ksh = vsh = jnp.zeros((1, 1), jnp.int32)
    fz = _fz_operands(pool_layer, ("k", "v"))
    if _BACKEND.startswith("pallas"):
        from .decode_attn import paged_decode_attn_pallas

        return paged_decode_attn_pallas(
            q, kp, vp, ksm, ksh, vsm, vsh, page_table, kv_lens,
            fmt=fmt, frozen=frozen, window=window,
            interpret=interpret_mode(), **fz,
        )
    return _ref.paged_decode_attn_ref(
        q, kp, vp, ksm, ksh, vsm, vsh, page_table, kv_lens, fmt=fmt,
        window=window, frozen=frozen, **fz,
    )


def paged_mla_decode_attn(q_lat, q_rope, pool_layer, page_table, kv_lens,
                          scale: float):
    """MLA absorbed decode over one layer's latent page pool slice.

    q_lat: (B, H, r) queries absorbed into the latent space; q_rope:
    (B, H, dr); pool_layer: one layer of a runtime.kv_cache MLA pool
    ({'ckv', 'krope'} + fp8 scale leaves); page_table: (B, PP) int32;
    kv_lens: (B,) int32; ``scale``: softmax scale (1/sqrt(nope + rope
    dims)). Returns the latent context (B, H, r) f32 — KV is one head,
    k = concat(ckv, krope), v = the ckv view.

    Pallas backend: the latent flash-decoding kernel gathers pages through
    the scalar-prefetched page table and dequantizes FP8 in VMEM. Ref: the
    gathered-page jnp oracle.

    With a serving mesh installed, the absorbed query heads shard along
    'model' while the latent pool (no head axis) replicates — each shard
    runs its head group against the full latent pages, so the contraction
    is local and the (B, H, r) context comes back head-sharded.
    """
    mesh = _SERVING_MESH[0]
    if mesh is not None:
        msize = mesh.shape.get("model", 1)
        if msize > 1 and q_lat.shape[1] % msize == 0:
            from jax.sharding import PartitionSpec as P

            specs = {n: P() for n in pool_layer}  # latents: no head axis
            hspec = P(None, "model", None)
            fn = jax.shard_map(
                lambda ql, qr, pl, pt, kl: _paged_mla_decode_attn_impl(
                    ql, qr, pl, pt, kl, scale=scale),
                mesh=mesh, in_specs=(hspec, hspec, specs, P(), P()),
                out_specs=hspec, check_vma=False)
            return fn(q_lat, q_rope, pool_layer, page_table, kv_lens)
    return _paged_mla_decode_attn_impl(q_lat, q_rope, pool_layer, page_table,
                                       kv_lens, scale=scale)


def _paged_mla_decode_attn_impl(q_lat, q_rope, pool_layer, page_table,
                                kv_lens, scale: float):
    """Single-shard MLA absorbed decode (the pre-mesh dataflow; under
    shard_map the head dim below is the per-shard local head count)."""
    cp, rp = pool_layer["ckv"], pool_layer["krope"]
    fmt, frozen = _layer_formats(pool_layer, "ckv")
    if fmt.quantized:
        csm, csh = pool_layer["ckv_smax"], pool_layer["ckv_shift"]
        rsm, rsh = pool_layer["krope_smax"], pool_layer["krope_shift"]
    else:  # dummies keep the kernel operand list static across formats
        csm = rsm = jnp.zeros((1,), jnp.float32)
        csh = rsh = jnp.zeros((1, 1), jnp.int32)
    fz = _fz_operands(pool_layer, ("ckv", "krope"))
    if _BACKEND.startswith("pallas"):
        from .autotune import best_block_sizes
        from .decode_attn import paged_mla_decode_attn_pallas

        b, h, r = q_lat.shape
        page = cp.shape[1]
        # same autotune kind as GQA decode: bm is the query-head block,
        # bn the page size; the latent contraction dim is r + dr
        bq, _ = best_block_sizes(
            "decode_attn", batch=b, m=h, n=page, k=r + q_rope.shape[-1],
            w_fmt=fmt.name or "bf16", a_fmt=None, group_size=page, m2=True,
            lorc_rank=0,
        )
        return paged_mla_decode_attn_pallas(
            q_lat, q_rope, cp, rp, csm, csh, rsm, rsh, page_table, kv_lens,
            scale, fmt=fmt, frozen=frozen, bq=bq,
            interpret=interpret_mode(), **fz,
        )
    return _ref.paged_mla_decode_attn_ref(
        q_lat, q_rope, cp, rp, csm, csh, rsm, rsh, page_table, kv_lens,
        scale, fmt=fmt, frozen=frozen, **fz,
    )


def dequant_packed(w):
    """PackedLinear -> dense f32 weights. Ref-backend fallback for einsum
    call-sites; the pallas backend routes those through w4a8_matmul_batched
    instead (asserted by tests/test_w4a8_fused.py)."""
    out = _ref.dequant_packed_ref(w.codes, w.scale, w.w_fmt, w.group_size)
    if w.lorc_a is not None:
        out = out + jnp.einsum(
            "...or,...ri->...oi", w.lorc_a.astype(jnp.float32), w.lorc_b.astype(jnp.float32)
        )
    return out
