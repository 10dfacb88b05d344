"""Compile the served kernels for a TPU v5e that is described, not attached.

The Pallas interpreter accepts what the TPU compiler (Mosaic) refuses:
dynamic slices of loaded values, blocks that break the (8, 128) tiling
rule, lane reshapes. These tests compile the fused W4A8 GEMM (2-D and
batched, both orientations) and both paged decode kernels at OLMo-1B's
published widths for a described ``v5e:2x2`` topology, and check that the
kernel reached the program as a Mosaic custom call. Nothing runs: the
shapes are abstract and the numbers are covered by the interpreter tests.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, so each test-runner worker must collect
the same tests and only the worker given this file may load it.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.olmo_1b import CONFIG as OLMO
from repro.core.policy import QuantPolicy
from repro.core.ptq import _pack_batched, pack_linear
from repro.kernels.decode_attn import (paged_decode_attn_pallas,
                                       paged_mla_decode_attn_pallas)
from repro.kernels.w4a8_fused import (w4a8_fused_batched_pallas,
                                      w4a8_fused_matmul_pallas)

POLICY = QuantPolicy(w_fmt="fp4_e2m1", a_fmt="fp8_e4m3", scale_mode="m2",
                     lorc_rank=8)
PACKED = ("codes", "scale", "s_max", "shifts", "lorc_a", "lorc_b")
D, FF = OLMO.d_model, OLMO.d_ff
HD = OLMO.d_model // OLMO.n_heads
# the served pool: 8 slots x 2048 tokens in pages of 32, every layer stacked
PAGE, PP, SLOTS = 32, 64, 8
P = SLOTS * PP
L = OLMO.n_layers


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _assert_reads_stack_in_place(compiled):
    """The kernel reads its layer out of the stacked pool, and what it
    needs laid out per layer is made from the layer's slice: no
    instruction but a parameter or a bitcast of one holds a whole stacked
    leaf with a trailing unit dim (the (KV, 1) shift blocks), and nothing
    rewrites a whole stacked code store (active or frozen) into another
    layout — a ``copy``, fusion or custom call of it. An
    asynchronous ``copy-start``/``copy-done`` moves an argument into VMEM in
    its own layout (XLA's prefetch of a program argument) and is allowed."""
    rows = f"{P + 1}|{P // 4 + 1}"
    for line in compiled.as_text().splitlines():
        if re.search(rf"= \w+\[{L},{P + 1}(,\d+)*,1\]", line):
            assert re.search(r" (parameter|bitcast)\(", line), line
        if re.search(rf"= (u8|bf16)\[{L},({rows})(,\d+){{2,}}\]", line):
            op = re.search(r"= \S+ ([\w-]+)\(", line).group(1)
            assert op in ("parameter", "bitcast", "copy-start",
                          "copy-done"), line


def _packed(sharding, shape):
    """Abstract packed-weight fields (E, N, K) or (N, K) on ``sharding``."""
    if len(shape) == 2:
        w = jax.eval_shape(lambda: pack_linear(
            jnp.zeros(shape, jnp.float32), POLICY))
    else:
        w = jax.eval_shape(lambda: _pack_batched(
            jnp.zeros(shape, jnp.float32), POLICY))
    return w, [jax.ShapeDtypeStruct(getattr(w, f).shape, getattr(w, f).dtype,
                                    sharding=sharding) for f in PACKED]


@pytest.mark.parametrize("m", [8, 12, 256])
@pytest.mark.parametrize("k,n", [(D, FF), (FF, D)])
def test_fused_gemm_compiles(one_chip, m, k, n):
    """The 2-D fused GEMM at the MLP up (2048 -> 8192) and down
    (8192 -> 2048) widths: decode-sized, odd and prefill-sized M."""
    w, fields = _packed(one_chip, (n, k))
    x = jax.ShapeDtypeStruct((m, k), jnp.bfloat16, sharding=one_chip)
    _compile(lambda x, *f: w4a8_fused_matmul_pallas(
        x, *f, w_fmt=w.w_fmt, a_fmt=w.a_fmt, group_size=w.group_size),
        x, *fields)


@pytest.mark.parametrize("transpose", [False, True])
def test_batched_gemm_compiles(one_chip, transpose):
    """Stacked weights, both orientations: per-head (16, 128, 2048)
    projections at OLMo-1B's head width."""
    e, n, k = OLMO.n_heads, HD, D
    w, fields = _packed(one_chip, (e, n, k))
    x = jax.ShapeDtypeStruct((e, 8, n if transpose else k), jnp.bfloat16,
                             sharding=one_chip)
    _compile(lambda x, *f: w4a8_fused_batched_pallas(
        x, *f, w_fmt=w.w_fmt, a_fmt=w.a_fmt, group_size=w.group_size,
        transpose_w=transpose), x, *fields)


@pytest.mark.parametrize("fmt,frozen", [("fp8_e4m3", None), (None, None),
                                        ("fp8_e4m3", "fp4_e2m1")])
def test_gqa_paged_decode_compiles(one_chip, fmt, frozen):
    """GQA paged decode over the served pool: FP8 pages, bf16 pages, and
    FP8 pages with a packed FP4 frozen region."""
    kv = OLMO.n_kv_heads
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    code = jnp.uint8 if fmt else jnp.bfloat16
    args = [s((SLOTS, OLMO.n_heads, HD), jnp.float32),
            s((L, P + 1, PAGE, kv, HD), code),
            s((L, P + 1, PAGE, kv, HD), code),
            s((L, P + 1), jnp.float32), s((L, P + 1, kv), jnp.int32),
            s((L, P + 1), jnp.float32), s((L, P + 1, kv), jnp.int32),
            s((SLOTS, PP), jnp.int32), s((SLOTS,), jnp.int32),
            s((), jnp.int32)]
    names = ()
    if frozen:
        f = P // 4
        args += [s((L, f + 1, PAGE, kv, HD // 2), jnp.uint8),
                 s((L, f + 1, PAGE, kv, HD // 2), jnp.uint8),
                 s((L, f + 1), jnp.float32), s((L, f + 1, kv), jnp.int32),
                 s((L, f + 1), jnp.float32), s((L, f + 1, kv), jnp.int32)]
        names = ("k_fz", "v_fz", "k_fz_smax", "k_fz_shift", "v_fz_smax",
                 "v_fz_shift")
    compiled = _compile(lambda *a: paged_decode_attn_pallas(
        *a[:10], fmt=fmt, frozen=frozen, **dict(zip(names, a[10:]))), *args)
    _assert_reads_stack_in_place(compiled)


@pytest.mark.parametrize("frozen", [None, "fp4_e2m1"])
def test_mla_paged_decode_compiles(one_chip, frozen):
    """MLA latent decode (DeepSeek-style rank 512 + rope 64) over FP8
    pages, with and without a packed FP4 frozen region."""
    h, r, dr = 16, 512, 64
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    args = [s((SLOTS, h, r), jnp.float32), s((SLOTS, h, dr), jnp.float32),
            s((L, P + 1, PAGE, r), jnp.uint8),
            s((L, P + 1, PAGE, dr), jnp.uint8),
            s((L, P + 1), jnp.float32), s((L, P + 1, 1), jnp.int32),
            s((L, P + 1), jnp.float32), s((L, P + 1, 1), jnp.int32),
            s((SLOTS, PP), jnp.int32), s((SLOTS,), jnp.int32),
            s((), jnp.int32)]
    names = ()
    if frozen:
        f = P // 4
        args += [s((L, f + 1, PAGE, r // 2), jnp.uint8),
                 s((L, f + 1, PAGE, dr // 2), jnp.uint8),
                 s((L, f + 1), jnp.float32), s((L, f + 1, 1), jnp.int32),
                 s((L, f + 1), jnp.float32), s((L, f + 1, 1), jnp.int32)]
        names = ("ckv_fz", "krope_fz", "ckv_fz_smax", "ckv_fz_shift",
                 "krope_fz_smax", "krope_fz_shift")
    compiled = _compile(lambda *a: paged_mla_decode_attn_pallas(
        *a[:11], 1.0 / (128 + dr) ** 0.5, fmt="fp8_e4m3", frozen=frozen,
        bq=16, **dict(zip(names, a[11:]))), *args)
    _assert_reads_stack_in_place(compiled)


def test_fused_gemm_compiles_on_mesh(topo, one_chip, monkeypatch):
    """On a (data=1, model=4) serving mesh the 2-D GEMM compiles as a
    column-parallel shard_map — the TPU compiler cannot partition a kernel
    call itself. ``one_chip`` keeps the persistent cache off."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from repro.kernels import ops

    mesh = Mesh(np.array(topo.devices[:4]).reshape(1, 4), ("data", "model"))
    w, _ = _packed(one_chip, (FF, D))
    rows, repl = (NamedSharding(mesh, PartitionSpec(a))
                  for a in ("model", None))
    w = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=rows if a.shape[0] == FF else repl), w)
    x = jax.ShapeDtypeStruct((8, D), jnp.bfloat16, sharding=repl)
    monkeypatch.setattr(ops, "_require_tpu", lambda: None)
    monkeypatch.setattr(ops, "_BACKEND", "pallas")
    monkeypatch.setattr(ops, "_SERVING_MESH", [mesh])
    _compile(ops.w4a8_matmul, x, w)
