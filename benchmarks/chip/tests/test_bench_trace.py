"""The reduction from a profiler trace to the device metrics."""
import json
import os

import pytest

import bench_tiny  # noqa: F401  (puts the benchmark on the path)
from chipbench import trace as tr

FIXTURE = os.path.join(os.path.dirname(__file__), "data", "v5e_window.json")


def _toy():
    # window 0..100 ns; ops [10,30) gemm, [20,40) other, [60,70) decode
    dev = [("fusion.1", 10, 20, "gemm"), ("fusion.2", 20, 20, ""),
           ("custom-call.3", 60, 10, "decode_attn")]
    host = [("bench.window", 0, 100), ("bench.step", 5, 40),
            ("bench.drain", 45, 10), ("bench.submit", 72, 5)]
    return tr.Trace(device=dev, host=host, window=(0, 100))


def test_busy_is_the_union_of_op_intervals():
    t = _toy()
    assert tr.busy_intervals(t) == [(10, 40), (60, 70)]
    assert tr.busy_s(t) == pytest.approx(40e-9)
    assert tr.window_s(t) == pytest.approx(100e-9)


def test_short_names_and_control_flow():
    assert tr._short("%fusion.12 = bf16[8,128]{1,0} fusion(...)") == "fusion.12"
    assert tr.CONTROL.match("while.7") and tr.CONTROL.match("conditional")
    assert not tr.CONTROL.match("while_body_fusion.3")


def test_kernels_found_by_their_labels():
    t = _toy()
    assert tr.kernel_s(t, "gemm") == pytest.approx(20e-9)
    assert tr.kernel_s(t, "decode_attn") == pytest.approx(10e-9)


def test_idle_gaps_go_to_the_covering_host_span():
    # gaps: [0,10) mid 5 -> step; [40,60) mid 50 -> drain;
    # [70,100) mid 85 -> no span
    got = dict(tr.idle_by_host(_toy()))
    assert got == pytest.approx({"bench.step": 10e-9, "bench.drain": 20e-9,
                                 "none": 30e-9})


def test_top_ops_sum_by_name():
    assert tr.top_ops(_toy(), 2) == [("fusion.1", 20e-9), ("fusion.2", 20e-9)]


def test_recorded_v5e_window():
    """A trimmed window of a traced chip run of olmo-1b.chat-poisson: the
    numbers the reduction gave on the chip, recomputed from the record."""
    with open(FIXTURE) as f:
        rec = json.load(f)
    t = tr.Trace.from_json(rec["trace"])
    want = rec["expect"]
    assert tr.busy_s(t) == pytest.approx(want["busy_s"], rel=1e-9)
    assert tr.window_s(t) == pytest.approx(want["window_s"], rel=1e-9)
    assert tr.kernel_s(t, "gemm") == pytest.approx(want["gemm_s"], rel=1e-9)
    assert tr.kernel_s(t, "decode_attn") == pytest.approx(
        want["decode_attn_s"], rel=1e-9)
    assert tr.kernel_s(t, "gemm") > 0 and tr.kernel_s(t, "decode_attn") > 0
    assert 0 < tr.busy_s(t) < tr.window_s(t)
    assert [list(x) for x in tr.idle_by_host(t)] == [
        [k, pytest.approx(v, rel=1e-9)] for k, v in want["idle_by_host"]]
