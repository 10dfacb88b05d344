"""Pallas kernel validation: interpret-mode execution swept over shapes,
dtypes and scale modes, assert_allclose against the pure-jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.formats import FORMATS, fp_encode, pack_nibbles, quantize_to_grid, value_grid
from repro.core.policy import QuantPolicy
from repro.core.ptq import pack_linear
from repro.kernels import ops, ref
from repro.kernels.act_quant import act_quant_pallas
from repro.kernels.w4a8_matmul import decode_e2m1, decode_e3m0, w4a8_matmul_pallas


# ---------------------------------------------------------------------------
# decode closed forms vs core.formats
# ---------------------------------------------------------------------------
def test_decode_e2m1_matches_fp_decode():
    codes = jnp.arange(16, dtype=jnp.uint8)
    from repro.core.formats import fp_decode

    np.testing.assert_array_equal(
        np.asarray(decode_e2m1(codes)), np.asarray(fp_decode(codes, FORMATS["fp4_e2m1"]))
    )


def test_decode_e3m0_matches_fp_decode():
    codes = jnp.arange(16, dtype=jnp.uint8)
    from repro.core.formats import fp_decode

    np.testing.assert_array_equal(
        np.asarray(decode_e3m0(codes)), np.asarray(fp_decode(codes, FORMATS["fp4_e3m0"]))
    )


# ---------------------------------------------------------------------------
# act_quant kernel sweep
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("shape", [(8, 128), (16, 256), (3, 384), (32, 1024), (5, 96)])
@pytest.mark.parametrize("fmt", ["fp8_e4m3", "fp8_e5m2"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_act_quant_kernel_matches_ref(shape, fmt, dtype):
    rng = np.random.default_rng(hash((shape, fmt, str(dtype))) % 2**31)
    x = jnp.asarray(rng.normal(size=shape).astype(np.float32) * 13.0).astype(dtype)
    qk, sk = act_quant_pallas(x, fmt, interpret=True)
    qr, sr = ref.act_quant_ref(x, fmt)
    np.testing.assert_allclose(np.asarray(sk), np.asarray(sr), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(qk), np.asarray(qr))


def test_act_quant_kernel_outlier_row():
    x = jnp.asarray(np.r_[np.full(127, 0.01), [100.0]].astype(np.float32))[None]
    qk, sk = act_quant_pallas(x, "fp8_e4m3", interpret=True)
    qr, sr = ref.act_quant_ref(x, "fp8_e4m3")
    np.testing.assert_array_equal(np.asarray(qk), np.asarray(qr))
    # the outlier maps to the max grid value
    assert float(qk[0, -1]) == FORMATS["fp8_e4m3"].max_value


# ---------------------------------------------------------------------------
# w4a8 matmul kernel sweep
# ---------------------------------------------------------------------------
def _pack_weight(rng, n, k, group, w_fmt="fp4_e2m1", scale_mode="none"):
    w = jnp.asarray(rng.normal(size=(n, k)).astype(np.float32) * 0.05)
    policy = QuantPolicy(w_fmt=w_fmt, a_fmt="fp8_e4m3", group_size=group,
                        scale_mode=scale_mode)
    return w, pack_linear(w, policy)


@pytest.mark.parametrize("mnk", [(8, 128, 256), (16, 256, 512), (128, 384, 256),
                                 (4, 512, 1024), (64, 128, 768)])
@pytest.mark.parametrize("group", [128, 256])
def test_w4a8_kernel_matches_ref(mnk, group):
    m, n, k = mnk
    if k % group:
        pytest.skip("group must divide k")
    rng = np.random.default_rng(m * n + k)
    _, pl_w = _pack_weight(rng, n, k, group)
    x = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32)).astype(jnp.bfloat16)

    # activations quantized identically on both sides, so the only diff is
    # blocked vs monolithic f32 accumulation order
    qv, sc = ref.act_quant_ref(x, "fp8_e4m3")
    xq = (qv * sc).astype(jnp.bfloat16)

    y_kernel = w4a8_matmul_pallas(xq, pl_w.codes, pl_w.scale, group_size=group,
                                  interpret=True)
    w_deq = ref.dequant_packed_ref(pl_w.codes, pl_w.scale, "fp4_e2m1", group)
    y_ref = jax.lax.dot_general(xq.astype(jnp.float32), w_deq.astype(jnp.float32),
                                (((1,), (1,)), ((), ())))
    np.testing.assert_allclose(np.asarray(y_kernel), np.asarray(y_ref),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("w_fmt", ["fp4_e2m1", "fp4_e3m0"])
def test_w4a8_kernel_formats(w_fmt):
    rng = np.random.default_rng(7)
    n, k, m, group = 128, 512, 16, 256
    _, pl_w = _pack_weight(rng, n, k, group, w_fmt=w_fmt)
    xq = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32)).astype(jnp.bfloat16)
    y_kernel = w4a8_matmul_pallas(xq, pl_w.codes, pl_w.scale, w_fmt=w_fmt,
                                  group_size=group, interpret=True)
    w_deq = ref.dequant_packed_ref(pl_w.codes, pl_w.scale, w_fmt, group)
    y_ref = jax.lax.dot_general(xq.astype(jnp.float32), w_deq.astype(jnp.float32),
                                (((1,), (1,)), ((), ())))
    np.testing.assert_allclose(np.asarray(y_kernel), np.asarray(y_ref),
                               rtol=2e-2, atol=2e-2)


def test_w4a8_kernel_m2_shift_path():
    """The M2 exponent-shift path must equal the plain-scale path bit-for-bit
    (scales are exactly s_max * 2^-k)."""
    rng = np.random.default_rng(11)
    n, k, m, group = 128, 1024, 8, 256
    w = jnp.asarray(rng.normal(size=(n, k)).astype(np.float32) * 0.05)
    policy = QuantPolicy(w_fmt="fp4_e2m1", a_fmt="fp8_e4m3", group_size=group,
                        scale_mode="m2")
    pl_w = pack_linear(w, policy)
    assert pl_w.shifts is not None and pl_w.s_max is not None
    xq = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32)).astype(jnp.bfloat16)

    y_scale = w4a8_matmul_pallas(xq, pl_w.codes, pl_w.scale, group_size=group,
                                 interpret=True)
    y_shift = w4a8_matmul_pallas(xq, pl_w.codes, pl_w.scale, s_max=pl_w.s_max,
                                 shifts=pl_w.shifts, group_size=group,
                                 interpret=True)
    # shift path applies 2^-k exactly (pow2 scaling is lossless in bf16) and
    # s_max once in f32; the scale path rounds s_max*2^-k*w to bf16 — the
    # shift path is the MORE precise one (the paper's efficiency cast loses
    # nothing). Tolerance = bf16 quantum.
    np.testing.assert_allclose(np.asarray(y_shift), np.asarray(y_scale),
                               rtol=1e-2, atol=1e-2)


def test_ops_backend_switch_end_to_end():
    """linear() with a PackedLinear must agree between ref and pallas
    backends (same quantization, different execution)."""
    from repro.models.layers import PackedLinear, linear

    rng = np.random.default_rng(13)
    n, k, m, group = 256, 512, 8, 256
    w = jnp.asarray(rng.normal(size=(n, k)).astype(np.float32) * 0.05)
    policy = QuantPolicy(w_fmt="fp4_e2m1", a_fmt="fp8_e4m3", group_size=group,
                        scale_mode="m2", lorc_rank=4)
    fac_w = pack_linear(w, policy)
    x = jnp.asarray(rng.normal(size=(2, m // 2, k)).astype(np.float32)).astype(jnp.bfloat16)

    ops.set_backend("ref")
    y_ref = linear(fac_w, x)
    ops.set_backend("pallas_interpret")
    try:
        y_pl = linear(fac_w, x)
    finally:
        ops.set_backend("ref")
    np.testing.assert_allclose(
        np.asarray(y_ref, dtype=np.float32), np.asarray(y_pl, dtype=np.float32),
        rtol=5e-2, atol=5e-2,
    )


def test_pallas_backend_refuses_non_tpu(monkeypatch):
    """'pallas' means compiled: off a TPU it raises instead of silently
    running the interpreter — at selection, and at dispatch when the
    backend arrived through REPRO_KERNEL_BACKEND."""
    assert jax.default_backend() != "tpu"
    prev = ops.get_backend()
    with pytest.raises(RuntimeError, match="pallas_interpret"):
        ops.set_backend("pallas")
    assert ops.get_backend() == prev
    monkeypatch.setattr(ops, "_BACKEND", "pallas")
    with pytest.raises(RuntimeError, match="pallas_interpret"):
        ops.act_quant(jnp.ones((8, 128), jnp.float32))
    monkeypatch.setattr(ops, "_BACKEND", "pallas_interpret")
    assert ops.interpret_mode()


def test_packed_codes_roundtrip_grid():
    """Every packed code decodes to a grid value (property over random w)."""
    from repro.core.formats import fp_decode, unpack_nibbles

    rng = np.random.default_rng(17)
    w = jnp.asarray(rng.normal(size=(32, 256)).astype(np.float32))
    policy = QuantPolicy(w_fmt="fp4_e2m1", a_fmt="fp8_e4m3", group_size=64)
    pl_w = pack_linear(w, policy)
    vals = np.unique(np.asarray(fp_decode(unpack_nibbles(pl_w.codes), FORMATS["fp4_e2m1"])))
    grid = set(value_grid("fp4_e2m1").tolist())
    assert set(vals.tolist()) <= grid


# ---------------------------------------------------------------------------
# flash attention kernel sweep
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("shape", [(1, 128, 2, 64), (2, 256, 4, 32), (1, 384, 1, 128)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_ref(shape, causal):
    from repro.kernels.flash_attn import flash_attention_pallas, flash_attention_ref

    b, s, h, hd = shape
    rng = np.random.default_rng(b * s + h)
    q = jnp.asarray(rng.normal(size=(b, s, h, hd)).astype(np.float32)).astype(jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(b, s, h, hd)).astype(np.float32)).astype(jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(b, s, h, hd)).astype(np.float32)).astype(jnp.bfloat16)
    out = flash_attention_pallas(q, k, v, causal=causal, block_q=128, block_k=128,
                                 interpret=True)
    ref_out = flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref_out, np.float32),
        rtol=3e-2, atol=3e-2,
    )


def test_flash_attention_distinct_v_dim():
    """MLA-style: v head dim differs from qk head dim."""
    from repro.kernels.flash_attn import flash_attention_pallas, flash_attention_ref

    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.normal(size=(1, 256, 2, 64)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(1, 256, 2, 64)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(1, 256, 2, 32)).astype(np.float32))
    out = flash_attention_pallas(q, k, v, interpret=True)
    ref_out = flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out), rtol=1e-4, atol=1e-4)
