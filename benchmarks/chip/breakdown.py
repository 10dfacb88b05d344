#!/usr/bin/env python3
"""Break one traced window of a cell down by the serving engine's own
spans, named scopes, timestamps and counter.

    python3 benchmarks/chip/breakdown.py --workload olmo-1b.chat-poisson \\
        --seed 7 --seconds 30 [--fixture out.json]

Runs what ``bench.py --trace 1`` runs (``harness.run``) and keeps what the
harness does not keep yet: the profiler trace, read with
``chipbench/spans.py``, and the window's requests and counters. The last
line of standard output is bench.py's result with these numbers added
under ``metrics``:

* ``prefill_wait_p90_ms``: p90, over the requests due in the window, of
  submit -> first prefill chunk launched (``Request.t_first_chunk``); one
  with no chunk when the loop stops counts at its wait until then;
* ``prefill_lane_busy``: percent of the window's steps whose program
  carried a prefill chunk (``stats["prefill_steps"]`` over ``steps``);
* ``step_host_ms``: mean ``serve.step`` span that launched a program, minus
  its ``serve.sync`` child, in the traced window;
* ``scan_io_ms``, ``sampler_ms``: device ms per traced step under
  ``layers`` and in none of the block's scopes, and under ``sample``;
* ``bench_step_ms``: mean ``bench.step`` span in the traced window (the
  harness's own span around ``Server.step``, so an engine without spans
  reads it too);

and under ``breakdown``: ``idle_by_span`` (idle device seconds by the
innermost ``bench.*``/``serve.*`` span), ``device_scopes`` (device
seconds by innermost scope) and ``device_ops_scope`` (the ops that took
the most device time, each with its scope). A number the engine gives nothing to read
for is left out. ``--fixture`` writes the trace of the window's first
``--fixture-steps`` launched steps, with what the reductions give on it,
for ``tests/test_bench_spans.py``.

Programs are compiled with their op metadata in the persistent cache's
key, so the scopes read are this tree's own (``chipbench/spans.py``).
Exits nonzero, printing no result, where JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CACHE_DIR = os.path.join(HERE, ".jax_cache")


def trimmed(t, steps: int):
    """``t`` cut to the span of its first ``steps`` launched engine
    steps."""
    from chipbench import spans

    launched = spans.launched_steps(t)[:steps]
    lo, hi = launched[0][0], launched[-1][0] + launched[-1][1]
    keep = [i for i, (_, s, d, _) in enumerate(t.device)
            if s < hi and s + d > lo]
    over = lambda evs: [e for e in evs if e[1] < hi and e[1] + e[2] > lo]
    return spans.ServeTrace(
        device=[t.device[i] for i in keep], host=over(t.host),
        window=(lo, hi), spans=over(t.spans),
        scopes=[t.scopes[i] for i in keep])


def ops_by_scope(t, n: int = 12) -> list:
    """(op, device seconds, innermost scope) of the ``n`` ops that took
    the most device time."""
    tot = {}
    for (name, _, d, _), scope in zip(t.device, t.scopes):
        tot[(name, scope)] = tot.get((name, scope), 0) + d
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [(name, v / 1e9, scope) for (name, scope), v in top]


def readings(t) -> dict:
    from chipbench import spans

    return {"step_host_ms": spans.step_host_ms(t),
            "scan_io_ms": spans.scope_ms_per_step(t, "layers"),
            "sampler_ms": spans.scope_ms_per_step(t, "sample"),
            "idle_by_span": spans.idle_by_span(t),
            "device_scopes": spans.scope_s(t)}


def run(cell, seed: int, seconds: float, backend: str = "pallas",
        fixture=None, fixture_steps: int = 3) -> dict:
    """``harness.run`` of ``cell`` with the trace on, and the numbers
    above added to its result."""
    from chipbench import harness, spans

    kept = {}
    drive, load = harness.drive, harness.tr.load

    def keep_drive(*a, **kw):
        kept["tracked"], kept["win"] = out = drive(*a, **kw)
        return out

    def keep_trace(path):
        base = load(path)
        kept["trace"] = spans.load(path, base)
        return base

    harness.drive, harness.tr.load = keep_drive, keep_trace
    try:
        result, _ = harness.run(cell, seed, seconds, True, backend=backend)
    finally:
        harness.drive, harness.tr.load = drive, load
    t, win = kept["trace"], kept["win"]
    counted = [x.req for x in kept["tracked"] if x.counted]
    got = readings(t)
    found = {
        "prefill_wait_p90_ms": spans.prefill_wait_p90_ms(counted, win.t_end),
        "prefill_lane_busy": spans.prefill_lane_busy(win.stats_open,
                                                     win.stats_close),
        "step_host_ms": got["step_host_ms"],
        "scan_io_ms": got["scan_io_ms"],
        "sampler_ms": got["sampler_ms"],
        "bench_step_ms": spans.mean_span_ms(t, "bench.step"),
    }
    unit = lambda name: "%" if name == "prefill_lane_busy" else "ms"
    result["metrics"].update({k: {"value": v, "unit": unit(k)}
                              for k, v in found.items() if v is not None})
    result["breakdown"].update(idle_by_span=got["idle_by_span"],
                               device_scopes=got["device_scopes"],
                               device_ops_scope=ops_by_scope(t))
    if fixture:
        part = trimmed(t, fixture_steps)
        rec = {"about": f"{cell.name} seed {seed}, first {fixture_steps} "
                        f"engine steps of the traced window, one "
                        f"{result['device']['kind']}",
               "expect": readings(part), "trace": part.to_json()}
        with open(fixture, "w") as f:
            json.dump(rec, f)
    return result


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fixture")
    ap.add_argument("--fixture-steps", type=int, default=3)
    args = ap.parse_args()

    sys.path[:0] = [os.path.join(ROOT, "src"), HERE]
    import jax

    from chipbench import harness, spec

    harness.use_compile_cache(CACHE_DIR)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    cell = spec.cell(args.workload, spec.load_benchmark(ROOT))
    if jax.devices()[0].platform != "tpu":
        print("breakdown: needs a TPU", file=sys.stderr)
        return 2
    result = run(cell, args.seed, args.seconds, fixture=args.fixture,
                 fixture_steps=args.fixture_steps)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
