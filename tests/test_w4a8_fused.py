"""Fused single-pass W4A8 pipeline validation.

Three layers of assertions, mirroring tests/test_kernels.py:
  * kernel parity: the fused kernel (in-kernel FP8 act-quant + LoRC
    epilogue) must match the split path (act_quant_pallas +
    w4a8_matmul_pallas + jnp LoRC matmuls) and the jnp oracles, swept over
    shapes (incl. M/N not divisible by the block sizes), both FP4 formats,
    M2 pow-2 scales, and LoRC rank in {0, 4, 16};
  * batched variant parity (both orientations) vs the batched oracle;
  * integration: MoE and MLA forward passes with packed weights never
    densify via dequant_packed under the pallas backend, and agree with the
    ref backend.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.policy import QuantPolicy
from repro.core.ptq import _pack_batched, pack_linear, quantize_tree
from repro.kernels import ops, ref
from repro.kernels.act_quant import act_quant_pallas
from repro.kernels.common import unpack_nibbles
from repro.kernels.w4a8_fused import (clamp_block, w4a8_fused_batched_pallas,
                                      w4a8_fused_matmul_pallas)
from repro.kernels.w4a8_matmul import w4a8_matmul_pallas
from repro.models.config import ArchConfig, MLASpec, MoESpec


@pytest.fixture(autouse=True)
def _ref_backend_after():
    yield
    ops.set_backend("ref")


def _pack(rng, n, k, group, w_fmt="fp4_e2m1", scale_mode="none", lorc_rank=0):
    w = jnp.asarray(rng.normal(size=(n, k)).astype(np.float32) * 0.05)
    policy = QuantPolicy(w_fmt=w_fmt, a_fmt="fp8_e4m3", group_size=group,
                         scale_mode=scale_mode, lorc_rank=lorc_rank)
    return pack_linear(w, policy)


def _split_path(x, w):
    """The pre-fusion three-pass pipeline, verbatim."""
    qv, sc = act_quant_pallas(x, w.a_fmt, interpret=True)
    xq = (qv * sc).astype(jnp.bfloat16)
    y = w4a8_matmul_pallas(xq, w.codes, w.scale, s_max=w.s_max, shifts=w.shifts,
                           w_fmt=w.w_fmt, group_size=w.group_size, interpret=True)
    if w.lorc_a is not None:
        y = y + (xq @ w.lorc_b.T.astype(jnp.bfloat16)).astype(jnp.bfloat16) @ \
            w.lorc_a.T.astype(jnp.bfloat16)
    return y


def _fused(x, w, bm=128, bn=128):
    return w4a8_fused_matmul_pallas(
        x, w.codes, w.scale, w.s_max, w.shifts, w.lorc_a, w.lorc_b,
        w_fmt=w.w_fmt, a_fmt=w.a_fmt, group_size=w.group_size,
        bm=bm, bn=bn, interpret=True)


# ---------------------------------------------------------------------------
# shared nibble unpack (copy-free bitwise construction)
# ---------------------------------------------------------------------------
def test_unpack_nibbles_matches_core():
    from repro.core.formats import unpack_nibbles as core_unpack

    rng = np.random.default_rng(0)
    packed = jnp.asarray(rng.integers(0, 256, size=(5, 16), dtype=np.uint8))
    np.testing.assert_array_equal(np.asarray(unpack_nibbles(packed)),
                                  np.asarray(core_unpack(packed)))
    # low nibble first
    np.testing.assert_array_equal(
        np.asarray(unpack_nibbles(jnp.asarray([[0xBA]], jnp.uint8))),
        np.asarray([[0x0A, 0x0B]], np.uint8))


# ---------------------------------------------------------------------------
# fused vs split parity sweep
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("mnk", [(8, 128, 256), (16, 256, 512), (128, 384, 256),
                                 (5, 96, 256), (3, 100, 512), (64, 128, 768)])
@pytest.mark.parametrize("scale_mode", ["none", "m2"])
def test_fused_matches_split_path(mnk, scale_mode):
    m, n, k = mnk
    rng = np.random.default_rng(m * n + k)
    w = _pack(rng, n, k, min(256, k), scale_mode=scale_mode)
    x = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32)).astype(jnp.bfloat16)
    y_fused = _fused(x, w)
    y_split = _split_path(x, w)
    np.testing.assert_allclose(np.asarray(y_fused), np.asarray(y_split),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("w_fmt", ["fp4_e2m1", "fp4_e3m0"])
@pytest.mark.parametrize("lorc_rank", [0, 4, 16])
def test_fused_formats_and_lorc_vs_oracle(w_fmt, lorc_rank):
    m, n, k, group = 16, 256, 512, 128
    rng = np.random.default_rng(lorc_rank + 29)
    w = _pack(rng, n, k, group, w_fmt=w_fmt, lorc_rank=lorc_rank)
    x = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32)).astype(jnp.bfloat16)
    y_fused = _fused(x, w)
    y_ref = ref.w4a8_matmul_ref(x.astype(jnp.float32), w.codes, w.scale,
                                w.lorc_a, w.lorc_b, w_fmt=w_fmt,
                                a_fmt="fp8_e4m3", group_size=group)
    np.testing.assert_allclose(np.asarray(y_fused), np.asarray(y_ref, np.float32),
                               rtol=2e-2, atol=2e-2)
    y_split = _split_path(x, w)
    np.testing.assert_allclose(np.asarray(y_fused), np.asarray(y_split),
                               rtol=2e-2, atol=2e-2)


def test_fused_m2_lorc_odd_blocks():
    """Everything at once: M2 shifts + rank-16 LoRC + block sizes that do not
    divide M or N (the kernel clamps to divisors)."""
    m, n, k, group = 12, 160, 512, 256
    rng = np.random.default_rng(7)
    w = _pack(rng, n, k, group, scale_mode="m2", lorc_rank=16)
    x = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32)).astype(jnp.bfloat16)
    y_ref = ref.w4a8_matmul_ref(x.astype(jnp.float32), w.codes, w.scale,
                                w.lorc_a, w.lorc_b, a_fmt="fp8_e4m3",
                                group_size=group)
    for bm, bn in [(128, 128), (8, 32), (3, 160)]:
        y = _fused(x, w, bm=bm, bn=bn)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref, np.float32),
                                   rtol=2e-2, atol=2e-2)


_E2M1 = np.array([0, 0.5, 1, 1.5, 2, 3, 4, 6], np.float64)


def _f32_dequant(w):
    """The paper's W4A8 weight, decoded in float64 straight from the packed
    fields: FP4-E2M1 code (low nibble = even column) x s_max x 2^-shift
    per group — no bf16 operand, no kernel-side factoring."""
    codes = np.asarray(w.codes)
    nib = np.stack([codes & 0x0F, codes >> 4], -1).reshape(codes.shape[0], -1)
    q = np.where(nib & 8, -1.0, 1.0) * _E2M1[nib & 7]
    scale = (np.asarray(w.s_max, np.float64).reshape(-1, 1)
             * 2.0 ** -np.asarray(w.shifts, np.float64))
    np.testing.assert_array_equal(scale, np.asarray(w.scale, np.float64))
    return q * np.repeat(scale, w.group_size, axis=1)


@pytest.mark.parametrize("mnk", [(8, 256, 512), (12, 384, 2048)])
@pytest.mark.parametrize("backend", ["ref", "pallas_interpret"])
def test_m2_gemm_matches_f32_dequant(mnk, backend):
    """An independent witness for the M2 factoring: the served GEMM (ref
    oracle and kernel alike) against float64 activations-on-the-FP8-grid
    x the float64 dequant of (codes, s_max, shifts), LoRC added in float64.
    What may differ is bf16 rounding of the activation operand, the LoRC
    intermediate and the bf16 output (2^-9 relative each), so both sit
    within 2^-8 of the output's largest magnitude (a weight operand
    rounded to bf16 as ``q * s_max * 2^-shift`` lands at ~4.5e-3)."""
    m, n, k = mnk
    rng = np.random.default_rng(k + n)
    w = _pack(rng, n, k, 256, scale_mode="m2", lorc_rank=8)
    x = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32)).astype(jnp.bfloat16)
    qx, sx = ref.act_quant_ref(x.astype(jnp.float32), "fp8_e4m3")
    xq = np.asarray(qx, np.float64) * np.asarray(sx, np.float64)
    want = xq @ _f32_dequant(w).T + (
        (xq @ np.asarray(w.lorc_b, np.float64).T)
        @ np.asarray(w.lorc_a, np.float64).T)
    ops.set_backend(backend)
    got = np.asarray(jax.jit(ops.w4a8_matmul)(x, w), np.float64)
    assert np.abs(got - want).max() <= 2.0 ** -8 * np.abs(want).max()


def test_clamp_block():
    """Blocks obey the TPU (8, 128) rule for every M the engine emits: bm
    is a sublane multiple (M pads up to whole blocks), bn a lane-multiple
    divisor of N or all of N (sublane multiple when N's rows contract)."""
    assert clamp_block(384, 384, 128, 128) == (128, 128)
    assert clamp_block(12, 2048, 128, 128) == (16, 128)
    assert clamp_block(136, 8192, 128, 256) == (128, 256)
    assert clamp_block(5, 100, 3, 128) == (8, 100)
    assert clamp_block(8, 640, 8, 512) == (8, 128)
    assert clamp_block(24, 160, 128, 32, transpose=True) == (24, 32)


# ---------------------------------------------------------------------------
# batched variant: expert stacks + transposed orientation
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("scale_mode,lorc_rank", [("none", 0), ("m2", 8)])
@pytest.mark.parametrize("transpose", [False, True])
def test_batched_fused_matches_oracle(scale_mode, lorc_rank, transpose):
    e, n, k, m, group = 4, 128, 256, 24, 128
    rng = np.random.default_rng(e * n + lorc_rank)
    w = jnp.asarray(rng.normal(size=(e, n, k)).astype(np.float32) * 0.05)
    policy = QuantPolicy(w_fmt="fp4_e2m1", a_fmt="fp8_e4m3", group_size=group,
                         scale_mode=scale_mode, lorc_rank=lorc_rank)
    pw = _pack_batched(w, policy)
    d = n if transpose else k
    x = jnp.asarray(rng.normal(size=(e, m, d)).astype(np.float32)).astype(jnp.bfloat16)
    for a_fmt in (None, "fp8_e4m3"):
        y = w4a8_fused_batched_pallas(
            x, pw.codes, pw.scale, pw.s_max, pw.shifts, pw.lorc_a, pw.lorc_b,
            w_fmt="fp4_e2m1", a_fmt=a_fmt, group_size=group,
            transpose_w=transpose, interpret=True)
        y_ref = ref.w4a8_batched_matmul_ref(
            x, pw.codes, pw.scale, pw.lorc_a, pw.lorc_b, w_fmt="fp4_e2m1",
            a_fmt=a_fmt, group_size=group, transpose_w=transpose)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=2e-2, atol=2e-2)


# ---------------------------------------------------------------------------
# integration: MoE / MLA forward without dequant_packed on pallas backend
# ---------------------------------------------------------------------------
class _NoDequant:
    """Context that makes ops.dequant_packed explode if the hot path calls it."""

    def __enter__(self):
        self._orig = ops.dequant_packed

        def boom(w):  # pragma: no cover - only fires on regression
            raise AssertionError("dequant_packed called on the pallas hot path")

        ops.dequant_packed = boom
        return self

    def __exit__(self, *exc):
        ops.dequant_packed = self._orig
        return False


def _moe_cfg():
    return ArchConfig(name="moe-test", family="moe", n_layers=1, d_model=128,
                      n_heads=4, n_kv_heads=4, d_ff=256, vocab_size=256,
                      mlp_gated=True, moe=MoESpec(n_experts=4, top_k=2, d_ff=128))


def test_moe_packed_pallas_no_dequant_matches_ref():
    from repro.models.moe import moe_layer, moe_params
    from repro.models.params import init_tree

    cfg = _moe_cfg()
    defs = moe_params(cfg)
    p = init_tree(defs, jax.random.PRNGKey(0))
    policy = QuantPolicy(w_fmt="fp4_e2m1", a_fmt="fp8_e4m3", group_size=64,
                         scale_mode="m2", lorc_rank=4)
    pq = quantize_tree(p, defs, policy)
    assert pq["wu"].codes.ndim == 3  # expert stack stayed packed
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model), jnp.bfloat16)

    ops.set_backend("ref")
    y_ref, _ = moe_layer(pq, x, cfg, group_size=32)
    ops.set_backend("pallas_interpret")
    with _NoDequant():
        y_pl, _ = moe_layer(pq, x, cfg, group_size=32)
    np.testing.assert_allclose(np.asarray(y_ref, np.float32),
                               np.asarray(y_pl, np.float32), rtol=5e-2, atol=5e-2)


def _mla_cfg():
    return ArchConfig(name="mla-test", family="dense", n_layers=1, d_model=128,
                      n_heads=4, n_kv_heads=4, d_ff=256, vocab_size=256,
                      attn_kind="mla",
                      mla=MLASpec(q_lora_rank=0, kv_lora_rank=64, qk_nope_dim=32,
                                  qk_rope_dim=16, v_head_dim=32))


def test_mla_decode_packed_pallas_no_dequant_matches_ref():
    from repro.models.mla import init_mla_cache, mla_attention, mla_params
    from repro.models.params import init_tree

    cfg = _mla_cfg()
    defs = mla_params(cfg)
    p = init_tree(defs, jax.random.PRNGKey(0))
    policy = QuantPolicy(w_fmt="fp4_e2m1", a_fmt="fp8_e4m3", group_size=64,
                         scale_mode="none", lorc_rank=4)
    pq = quantize_tree(p, defs, policy)
    assert isinstance(pq["wk_b"], type(pq["wv_b"]))  # both packed
    assert pq["wk_b"].codes is not None

    cache = init_mla_cache(cfg, 2, 32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 1, cfg.d_model), jnp.bfloat16)
    pos = jnp.full((2, 1), 5, jnp.int32)

    ops.set_backend("ref")
    y_ref, _ = mla_attention(pq, x, cfg, pos, kv_cache=cache, cache_index=5)
    ops.set_backend("pallas_interpret")
    with _NoDequant():
        y_pl, _ = mla_attention(pq, x, cfg, pos, kv_cache=cache, cache_index=5)
    np.testing.assert_allclose(np.asarray(y_ref, np.float32),
                               np.asarray(y_pl, np.float32), rtol=5e-2, atol=5e-2)


def test_mla_prefill_packed_pallas_no_dequant():
    """Materialized (prefill) form routes wk_b/wv_b through linear() ->
    fused 2-D kernel; nothing densifies either."""
    from repro.models.mla import mla_attention, mla_params
    from repro.models.params import init_tree

    cfg = _mla_cfg()
    defs = mla_params(cfg)
    p = init_tree(defs, jax.random.PRNGKey(0))
    policy = QuantPolicy(w_fmt="fp4_e2m1", a_fmt="fp8_e4m3", group_size=64)
    pq = quantize_tree(p, defs, policy)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 8, cfg.d_model), jnp.bfloat16)
    pos = jnp.broadcast_to(jnp.arange(8), (2, 8))

    ops.set_backend("ref")
    y_ref, _ = mla_attention(pq, x, cfg, pos)
    ops.set_backend("pallas_interpret")
    with _NoDequant():
        y_pl, _ = mla_attention(pq, x, cfg, pos)
    np.testing.assert_allclose(np.asarray(y_ref, np.float32),
                               np.asarray(y_pl, np.float32), rtol=5e-2, atol=5e-2)


def test_packed_head_view_roundtrip():
    from repro.models.layers import packed_head_view

    rng = np.random.default_rng(11)
    w = _pack(rng, 128, 64, 64, lorc_rank=4)  # e.g. (H*out, in) = (4*32, 64)
    v = packed_head_view(w, 4)
    assert v.codes.shape == (4, 32, 32)
    assert v.scale.shape == (4, 32, 1)
    assert v.lorc_a.shape == (4, 32, 4) and v.lorc_b.shape == (4, 4, 64)
    np.testing.assert_array_equal(np.asarray(v.codes.reshape(128, 32)),
                                  np.asarray(w.codes))


# ---------------------------------------------------------------------------
# autotuner
# ---------------------------------------------------------------------------
def test_autotune_sweep_and_cache(tmp_path, monkeypatch):
    from repro.kernels import autotune

    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "tune.json"))
    monkeypatch.setattr(autotune, "_MEM", None)

    rng = np.random.default_rng(5)
    w = _pack(rng, 128, 256, 128)
    x = jnp.asarray(rng.normal(size=(16, 256)).astype(np.float32)).astype(jnp.bfloat16)

    sig = dict(batch=1, m=16, n=128, k=256, w_fmt="fp4_e2m1", a_fmt="fp8_e4m3",
               group_size=128, m2=False, lorc_rank=0)
    key = autotune.cache_key("fused", **sig)

    def build(bm, bn):
        return lambda: _fused(x, w, bm=bm, bn=bn)

    best = autotune.autotune_gemm(build, key, candidates=((8, 128), (16, 128)))
    assert best in ((8, 128), (16, 128))
    # persisted: a fresh in-process cache reloads the winner from disk
    monkeypatch.setattr(autotune, "_MEM", None)
    assert autotune.best_block_sizes("fused", **sig) == best
    # a different signature misses and falls back to the legal heuristic
    bm, bn = autotune.best_block_sizes("fused", **{**sig, "m": 999})
    assert bm >= 1 and bn >= 1


def test_ops_batched_backend_switch():
    """ops.w4a8_matmul_batched agrees between ref and pallas backends."""
    e, n, k, m, group = 3, 128, 256, 8, 128
    rng = np.random.default_rng(23)
    w = jnp.asarray(rng.normal(size=(e, n, k)).astype(np.float32) * 0.05)
    policy = QuantPolicy(w_fmt="fp4_e2m1", a_fmt="fp8_e4m3", group_size=group,
                         scale_mode="m2", lorc_rank=4)
    pw = _pack_batched(w, policy)
    x = jnp.asarray(rng.normal(size=(e, m, k)).astype(np.float32)).astype(jnp.bfloat16)

    ops.set_backend("ref")
    y_ref = ops.w4a8_matmul_batched(x, pw)
    ops.set_backend("pallas_interpret")
    y_pl = ops.w4a8_matmul_batched(x, pw)
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_pl),
                               rtol=5e-2, atol=5e-2)
