#!/usr/bin/env python3
"""Measure one cell of ``BENCHMARK.json`` on the accelerator.

    python3 benchmarks/chip/bench.py --workload olmo-1b.chat-poisson \\
        --seed 7 --seconds 30 --trace 0

Builds the cell's W4A8 checkpoint on the device from ``--seed``, serves the
cell's traffic through ``repro.runtime.serve.Server`` with the compiled
Pallas kernels for ``--seconds`` of measured window, and checks what was
served against the plain reference. The last line of standard output is
one JSON object (``correct``, ``attempted``, ``failed``, ``metrics``,
``device``; with ``--trace 1`` also ``breakdown``, and the per-layer metrics
in place of the end-to-end ones). The numbers compared for ``correct`` are
printed last on standard error, each beside its limit, and again under
``check`` in that line. ``--control`` puts the control (the reference one
precision step lower, read on the same prompts and served tokens) in the
program's place: its readings are the numbers compared, so a sound check
reads ``correct`` false.

Exits nonzero, printing no result, where JAX finds no TPU or fewer chips
than the cell asks for. JAX's persistent compilation cache is kept where
``JAX_COMPILATION_CACHE_DIR`` points, or else at ``benchmarks/chip/.jax_cache``
inside the checkout.
"""
from __future__ import annotations

import os
import time


def _process_age():
    """Seconds since this process started (the kernel's start time), so set-up
    counts the interpreter's own start too."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        start = ticks / os.sysconf("SC_CLK_TCK")
        return lambda: time.clock_gettime(time.CLOCK_BOOTTIME) - start
    except (OSError, ValueError, IndexError):
        t0 = time.perf_counter()
        return lambda: time.perf_counter() - t0


SETUP_CLOCK = _process_age()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CACHE_DIR = os.path.join(HERE, ".jax_cache")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args()

    sys.path[:0] = [os.path.join(ROOT, "src"), HERE]
    import jax

    from chipbench import harness, spec

    print(f"compile cache: {harness.use_compile_cache(CACHE_DIR)}",
          file=sys.stderr)

    cell = spec.cell(args.workload, spec.load_benchmark(ROOT))
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"bench: needs {cell.chips} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return 2
    result, compared = harness.run(cell, args.seed, args.seconds,
                                   bool(args.trace), setup_clock=SETUP_CLOCK,
                                   control=args.control)
    for name, value, limit in compared:
        print(f"check {name}: {value} limit {limit}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
