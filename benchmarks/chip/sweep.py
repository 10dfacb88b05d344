#!/usr/bin/env python3
"""Find a chat cell's knee: the highest offered rate its engine sustains.

    python3 benchmarks/chip/sweep.py --workload olmo-1b.chat-poisson \\
        --seed 5 --seconds 30 --preroll 20 --rates 1 1.5 2 2.5 3

One process builds the cell's checkpoint and compiles its programs once,
then offers the cell's traffic at each rate in turn to a fresh ``Server``:
``--preroll`` seconds of arrivals, so that the window opens on a loaded
engine, then ``--seconds`` of window. Per rate it prints one JSON line:
requests due in the window; the requests waiting for a slot, and the
backlog (requests sent that have no first token yet, waiting for a slot or
in prefill), at the window's open and close; time to first token (p50,
p90) of the requests due in each half of the window, a request with no
first token at the close counted as missing; the gap between tokens (p95);
and whether the rate is sustained.

A rate is sustained where no request waits for a slot at the close and
every request due in the window's first half has its first token by the
close. A queue that grows fails one or the other within the window: slots
that stay full leave arrivals waiting for one, and prefill that falls
behind leaves the first half's requests waiting. The requests due near the
close are not held to it: at any rate they wait one time to first token.
The knee is the highest sustained rate tried, with every rate below it
sustained too. The cells' rates are fixed from it (``knee_rps`` in the
configuration file); the benchmark never searches for a rate itself.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CACHE_DIR = os.path.join(HERE, ".jax_cache")


def _pcts(values):
    if not values:
        return None, None
    v = sorted(values)
    at = lambda q: v[min(len(v) - 1, int(math.ceil(q * len(v))) - 1)]
    return at(0.5), at(0.9)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--preroll", type=float, default=20.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args()
    sys.path[:0] = [os.path.join(ROOT, "src"), HERE]
    import jax

    from chipbench import harness, spec, weights

    harness.use_compile_cache(CACHE_DIR)
    cell = spec.cell(args.workload, spec.load_benchmark(ROOT))
    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 2
    cfg = harness.arch_config(cell.config)
    params = weights.build(cfg, args.seed, cell.config["quant"])
    traffic = dict(cell.traffic, tail_s=0.0, preroll_s=args.preroll)
    warmed = False
    for rate in sorted(args.rates):
        server = harness.make_server(params, cfg, cell.config, "pallas")
        if not warmed:
            harness.warm_up(server, cell.config, cell.traffic, args.seed)
            warmed = True
        tracked, win = harness.drive(server, traffic, cell.config, args.seed,
                                     args.seconds, rate, None)
        due = [t for t in tracked if t.counted]
        mid = (win.t_open + win.t_close) / 2
        halves = ([t for t in due if t.due < mid], [t for t in due if t.due >= mid])
        ttft = [[(t.req.token_times[0] - t.due) * 1e3
                 if t.req.token_times and t.req.token_times[0] < win.t_close
                 else math.inf for t in h] for h in halves]
        vals = harness.e2e_values(tracked, win)
        sustained = win.queue_close == 0 and all(map(math.isfinite, ttft[0]))
        line = {
            "rate_rps": rate, "due": len(due),
            "queue_at_open": win.queue_open, "queue_at_close": win.queue_close,
            "backlog_at_open": win.backlog_open,
            "backlog_at_close": win.backlog_close,
            "ttft_p50_p90_ms_first_half": _pcts(ttft[0]),
            "ttft_p50_p90_ms_second_half": _pcts(ttft[1]),
            "itl_p95_ms": vals.get("itl_p95_ms"), "tok_s": vals["tok_s"],
            "steps": vals["_steps"], "sustained": sustained,
        }
        print(json.dumps(line), flush=True)
        del server, tracked
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
