"""The checkpoint made on the device against quantize_tree, and the
reference's dequantization against the program's own."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny  # noqa: F401  (puts the benchmark on the path)
from chipbench import harness, reference, spec, weights


def _smoke_arch(name):
    """The configuration at its preset's SMOKE size."""
    from repro.configs import olmo_1b, opt_125m

    cfg = spec._json(spec.config_file(name))
    smoke = {"olmo-1b": olmo_1b.SMOKE, "opt-6.7b": opt_125m.SMOKE}[name]
    keys = ("n_layers", "d_model", "n_heads", "n_kv_heads", "d_ff",
            "vocab_size")
    cfg["arch"] = dict(cfg["arch"], **{k: getattr(smoke, k) for k in keys})
    if "max_position" in cfg["arch"]:
        cfg["arch"]["max_position"] = 128
    return cfg


@pytest.mark.parametrize("name", ["olmo-1b", "opt-6.7b"])
def test_made_tree_matches_quantize_tree(name):
    from repro import models
    from repro.core.policy import QuantPolicy
    from repro.core.ptq import quantize_tree

    cfg = _smoke_arch(name)
    arch = harness.arch_config(cfg)
    q = cfg["quant"]
    built = weights.build(arch, 2 ** 31 + 5, q)
    policy = QuantPolicy(w_fmt=q["w_fmt"], a_fmt=q["a_fmt"],
                         scale_mode=q["scale_mode"], lorc_rank=q["lorc_rank"],
                         group_size=q["group_size"])
    ref = quantize_tree(models.init_params(arch, jax.random.PRNGKey(0)),
                        models.build_def(arch), policy)
    assert jax.tree.structure(built) == jax.tree.structure(ref)
    for a, b in zip(jax.tree.leaves(built), jax.tree.leaves(ref)):
        assert a.shape == b.shape and a.dtype == b.dtype


def test_made_tree_is_deterministic_per_seed():
    cfg = _smoke_arch("olmo-1b")
    arch = harness.arch_config(cfg)
    a = weights.build(arch, 7, cfg["quant"])
    b = weights.build(arch, 7, cfg["quant"])
    c = weights.build(arch, 8, cfg["quant"])
    la, lb, lc = (jax.tree.leaves(t) for t in (a, b, c))
    assert all(np.array_equal(x, y) for x, y in zip(la, lb))
    assert not all(np.array_equal(x, y) for x, y in zip(la, lc))


def test_reference_dequant_matches_the_program():
    """The reference decodes codes, M2 scales and LoRC on its own; the
    program's oracle (``kernels/ref.py``) reads the same fields."""
    from repro.kernels.ref import dequant_packed_ref

    cfg = _smoke_arch("olmo-1b")
    arch = harness.arch_config(cfg)
    tree = weights.build(arch, 3, cfg["quant"])
    w = tree["segments"][0]["ffn"]["mlp"]["up"]
    one = jax.tree.map(lambda a: a[0], w)
    mine = reference.dequant({f: getattr(one, f) for f in
                              ("codes", "s_max", "shifts", "lorc_a",
                               "lorc_b")})
    # the program's M2 semantics: the exact bf16 q * 2**-shift, times the
    # row's s_max in f32 (kernels/ref.py w4a8_matmul_ref)
    theirs = dequant_packed_ref(
        one.codes, jnp.ldexp(jnp.float32(1), -one.shifts.astype(jnp.int32)),
        one.w_fmt, one.group_size).astype(jnp.float32) * one.s_max
    theirs = theirs + one.lorc_a.astype(jnp.float32) @ one.lorc_b.astype(
        jnp.float32)
    np.testing.assert_allclose(np.asarray(mine), np.asarray(theirs),
                               rtol=1e-6, atol=1e-6)
    # M2: the stored scale is s_max * 2**-shift exactly
    np.testing.assert_array_equal(
        np.asarray(one.scale),
        np.ldexp(np.asarray(one.s_max), -np.asarray(one.shifts, np.int32)))
