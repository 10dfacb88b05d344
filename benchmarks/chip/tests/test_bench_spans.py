"""The engine's spans, named scopes, timestamps and counter, reduced
(``chipbench/spans.py``), and ``breakdown.py`` run at test size."""
import json
import os
import types

import pytest

import bench_tiny
from chipbench import spans as sp
from chipbench import trace as tr

DATA = os.path.join(os.path.dirname(__file__), "data")
OLD_FIXTURE = os.path.join(DATA, "v5e_window.json")
SPANS_FIXTURE = os.path.join(DATA, "v5e_window_spans.json")
# One TPU v5 lite trace of a three-layer scanned toy program under the
# engine's scope names (embed; layers around the scan; attn, with kv_write
# inside it, and mlp in the body; head), called inside a serve.step span;
# source locations and the host metadata plane (HLO protos) taken out.
SCOPES_XPLANE = os.path.join(DATA, "v5e_scopes.xplane.pb")


def _toy():
    # window 0..200 ns. Two engine steps: step 1 launches (launch, sync
    # children), step 2 finds nothing to run. Device ops:
    # [10,30) attn, [30,50) scan slice (layers), [50,60) sample,
    # [120,130) an op under no scope.
    dev = [("fusion.1", 10, 20, "gemm"), ("fusion.2", 30, 20, ""),
           ("fusion.3", 50, 10, ""), ("copy.4", 120, 10, "")]
    scopes = ["attn", "layers", "sample", "none"]
    host = [("bench.window", 0, 200), ("bench.step", 0, 100),
            ("bench.drain", 100, 20), ("bench.step", 140, 30)]
    spans = host + [
        ("serve.step", 2, 96), ("serve.schedule", 2, 3),
        ("serve.prepare", 5, 3), ("serve.launch", 8, 2),
        ("serve.sync", 10, 70), ("serve.commit", 80, 18),
        ("serve.step", 141, 28), ("serve.schedule", 141, 28)]
    spans.sort(key=lambda e: (e[1], -e[2]))
    return sp.ServeTrace(device=dev, host=host, window=(0, 200),
                         spans=spans, scopes=scopes)


def test_innermost_scope_of_an_op_name():
    pre = "jit(_decode_step)/jit(main)/"
    assert sp.innermost(
        pre + "layers/while/body/closed_call/attn/kv_write/scatter:") \
        == "kv_write"
    assert sp.innermost(pre + "layers/while/body/dynamic_slice:") == "layers"
    assert sp.innermost(pre + "sample/jit(_where)/select_n:") == "sample"
    assert sp.innermost(pre + "jit(paged_decode_attn_pallas)/x:") == "none"
    assert sp.innermost("") == "none"


def test_idle_goes_to_the_innermost_covering_span():
    # gaps: [0,10) mid 5 -> serve.prepare; [60,120) mid 90 -> serve.commit;
    # [130,200) mid 165 -> serve.schedule of step 2
    got = dict(sp.idle_by_span(_toy()))
    assert got == pytest.approx({"serve.prepare": 10e-9,
                                 "serve.commit": 60e-9,
                                 "serve.schedule": 70e-9})


def test_unscoped_scan_ops_count_under_layers():
    got = dict(sp.scope_s(_toy()))
    assert got == pytest.approx({"attn": 20e-9, "layers": 20e-9,
                                 "sample": 10e-9, "none": 10e-9})


def test_host_step_time_leaves_out_the_wait_and_idle_steps():
    t = _toy()
    assert sp.launched_steps(t) == [(2, 96, 70)]
    assert sp.step_host_ms(t) == pytest.approx((96 - 70) / 1e6)
    assert sp.scope_ms_per_step(t, "layers") == pytest.approx(20e-6)
    assert sp.scope_ms_per_step(t, "sample") == pytest.approx(10e-6)
    assert sp.scope_ms_per_step(t, "mlp") is None
    assert sp.mean_span_ms(t, "bench.step") == pytest.approx(65e-6)


def test_a_trace_without_engine_spans_reads_nothing():
    t = _toy()
    t.spans = [s for s in t.spans if s[0].startswith("bench.")]
    t.scopes = ["none"] * len(t.device)
    assert sp.step_host_ms(t) is None
    assert sp.scope_ms_per_step(t, "layers") is None
    assert sp.mean_span_ms(t, "bench.step") == pytest.approx(65e-6)


def _req(submit, chunk):
    return types.SimpleNamespace(t_submit=submit, t_first_chunk=chunk)


def test_prefill_wait_counts_a_request_with_no_chunk_until_the_end():
    reqs = [_req(0.0, 0.1 * i) for i in range(1, 10)] + [_req(1.0, None)]
    # waits 100..900 ms and 4000 ms (no chunk until t_end = 5 s)
    got = sp.prefill_wait_p90_ms(reqs, 5.0)
    assert got == pytest.approx(900 + 0.1 * (4000 - 900))
    # an engine that keeps no first-chunk time reads nothing
    old = [types.SimpleNamespace(t_submit=0.0)]
    assert sp.prefill_wait_p90_ms(old, 5.0) is None


def test_prefill_lane_busy_is_the_share_of_steps_with_a_chunk():
    s0 = {"steps": 100, "prefill_steps": 40}
    s1 = {"steps": 300, "prefill_steps": 190}
    assert sp.prefill_lane_busy(s0, s1) == pytest.approx(75.0)
    assert sp.prefill_lane_busy({"steps": 1}, {"steps": 5}) is None


def test_scopes_and_spans_read_from_a_recorded_v5e_trace():
    """The op_name of each op comes from the trace's event metadata: the
    scan's own slices of its carried buffer fall under ``layers``, the
    in-place write under ``kv_write``, the convert before the scan under
    no scope."""
    t = sp.load(SCOPES_XPLANE)
    assert len(t.scopes) == len(t.device) == 22
    by_op = {}
    for (name, _, _, _), scope in zip(t.device, t.scopes):
        assert by_op.setdefault(name, scope) == scope
    assert by_op == {
        "broadcast_multiply_fusion": "embed", "custom-call": "layers",
        "convert.2": "none", "copy.12": "layers",
        "broadcast_add_fusion.2": "attn", "slice_bitcast_fusion.2": "layers",
        "constant_dynamic-slice_fusion.2": "layers",
        "dynamic-update-slice.9": "kv_write",
        "constant_dynamic-update-slice_fusion.2": "layers",
        "reduce_sum.7": "head"}
    assert [s[0] for s in t.spans] == ["serve.step"]
    assert tr.busy_s(t) == pytest.approx(tr.busy_s(tr.load(SCOPES_XPLANE)))


def test_recorded_v5e_window_with_the_engine_spans():
    """The first three engine steps of a traced chip run of
    olmo-1b.chat-poisson (``breakdown.py --fixture``): the numbers the
    reductions gave on the chip, recomputed from the record."""
    with open(SPANS_FIXTURE) as f:
        rec = json.load(f)
    t = sp.ServeTrace.from_json(rec["trace"])
    want = rec["expect"]
    assert len(sp.launched_steps(t)) == 3
    for name, got in (("step_host_ms", sp.step_host_ms(t)),
                      ("scan_io_ms", sp.scope_ms_per_step(t, "layers")),
                      ("sampler_ms", sp.scope_ms_per_step(t, "sample"))):
        assert got == pytest.approx(want[name], rel=1e-9), name
    for name, got in (("idle_by_span", sp.idle_by_span(t)),
                      ("device_scopes", sp.scope_s(t))):
        assert [list(x) for x in got] == [
            [k, pytest.approx(v, rel=1e-9)] for k, v in want[name]], name
    # every idle gap inside a step falls in one of its phases, and the
    # page pool's round trip through the scan (the four fusions the chip
    # runs of this cell name in their breakdown) is the scan's own traffic
    assert {k for k, _ in want["idle_by_span"]} <= {
        "serve.schedule", "serve.prepare", "serve.launch", "serve.sync",
        "serve.commit"}
    pool = {"dynamic-slice_bitcast_fusion.9", "dynamic-slice_bitcast_fusion.10",
            "bitcast_dynamic-update-slice_fusion.4",
            "bitcast_dynamic-update-slice_fusion.5"}
    got = {name: s for (name, _, _, _), s in zip(t.device, t.scopes)
           if name in pool}
    assert got == dict.fromkeys(pool, "layers")
    busy = tr.busy_s(t)
    assert dict(sp.scope_s(t))["none"] < 0.1 * busy


def test_the_older_fixture_loads_with_no_spans_and_the_same_numbers():
    with open(OLD_FIXTURE) as f:
        rec = json.load(f)
    t = sp.ServeTrace.from_json(rec["trace"])
    assert t.spans == [] and t.scopes == []
    want = rec["expect"]
    assert tr.busy_s(t) == pytest.approx(want["busy_s"], rel=1e-9)
    assert tr.window_s(t) == pytest.approx(want["window_s"], rel=1e-9)
    assert tr.kernel_s(t, "gemm") == pytest.approx(want["gemm_s"], rel=1e-9)
    assert [list(x) for x in tr.idle_by_host(t)] == [
        [k, pytest.approx(v, rel=1e-9)] for k, v in want["idle_by_host"]]
    assert sp.scope_s(t) == [] and sp.step_host_ms(t) is None


def test_breakdown_at_test_size_reads_the_engine(monkeypatch):
    """``breakdown.run`` on the CPU at test size: the host spans, the
    timestamps and the counter are read; the device numbers, which need
    the chip's trace, are left out. (The CPU has no peaks entry: the
    existing readers get the chip's, and their numbers are not read.)"""
    import breakdown
    from chipbench import harness, peaks

    monkeypatch.setattr(harness, "peaks_of",
                        lambda kind: peaks.peaks("TPU v5 lite"))
    cell = bench_tiny.tiny_cell()
    got = breakdown.run(cell, 11, 2.0, backend="ref")
    m = {k: v["value"] for k, v in got["metrics"].items()}
    assert 0 < m["prefill_lane_busy"] <= 100
    assert m["prefill_wait_p90_ms"] > 0
    assert 0 < m["step_host_ms"] <= m["bench_step_ms"]
    assert "scan_io_ms" not in m and "sampler_ms" not in m
    assert got["breakdown"]["device_scopes"] == []
