"""The harness's warm-up reaches every program the traffic can."""
import numpy as np

import bench_tiny
from chipbench import harness


def test_warm_lengths_cover_every_bucket():
    for lo, hi in ((32, 1536), (768, 1920), (8, 60)):
        lens = harness.warm_lengths(lo, hi, 256, 64)
        got = set().union(*(harness.chunk_shapes(n, 256, 64) for n in lens))
        want = set().union(*(harness.chunk_shapes(n, 256, 64)
                             for n in range(lo, hi + 1)))
        assert got == want


def test_warm_up_compiles_what_the_engine_buckets():
    """The engine's own record of the prefill shapes it ran
    (``Server.prefill_traces``) after the warm-up equals every bucket the
    traffic's prompt lengths reach."""
    import jax

    cell = bench_tiny.tiny_cell()
    cfg = harness.arch_config(cell.config)
    from chipbench import weights

    params = weights.build(cfg, 1, cell.config["quant"])
    server = harness.make_server(params, cfg, cell.config, "ref")
    harness.warm_up(server, cell.config, cell.traffic, 1)
    s = cell.config["serve"]
    p = cell.traffic["prompt"]
    want = set().union(*(harness.chunk_shapes(n, s["prefill_token_budget"],
                                              s["page_size"])
                         for n in range(p["min"], p["max"] + 1)))
    assert server.prefill_traces == want
    # and a window at the cell's load compiles nothing new
    count = harness.CompileCount()
    _, win = harness.drive(server, cell.traffic, cell.config, 5, 1.0, 6.0,
                           None, count)
    assert win.compiles == 0
    assert np.isfinite(win.t_close) and jax.device_count() >= 1


def test_precompile_that_no_longer_fits_the_step_is_fatal(monkeypatch):
    """``precompile`` mirrors the engine step's private arguments; where the
    step no longer takes them, the warm-up raises instead of going on."""
    import jax
    import pytest
    from repro.runtime import serve

    cell = bench_tiny.tiny_cell()
    cfg = harness.arch_config(cell.config)
    from chipbench import weights

    params = weights.build(cfg, 1, cell.config["quant"])
    server = harness.make_server(params, cfg, cell.config, "ref")
    monkeypatch.setattr(serve, "_decode_step_jit",
                        jax.jit(lambda params, pools: pools))
    with pytest.raises(TypeError):
        harness.warm_up(server, cell.config, cell.traffic, 1)
