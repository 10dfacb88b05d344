"""From a profiler trace to the numbers the per-layer metrics read.

``load(path)`` reads the ``.xplane.pb`` the JAX profiler wrote (with
``jax.profiler.ProfileData``, nothing else) into a small ``Trace``: the
device's operations (short name, start, duration, and which kernel they
belong to, found by the patterns in ``KERNELS`` in the op's name and the
HLO text the profiler attaches, where the kernel's jitted wrapper is named)
and the benchmark's own host spans (``bench.*`` annotations). Control-flow
ops (a layer loop's ``while``) are left out: their spans cover the ops
inside them. ``Trace`` is also what the
recorded fixture under ``tests/data`` holds, so the reduction below is
checked on a real v5e window.

The reduction:

* busy time is the union of the device operations' intervals; idle share is
  one minus busy over the traced window;
* a kernel's time is the sum of the durations of its events;
* each idle gap between device operations is attributed to the host span
  that covers its midpoint, and idle time is summed per span name
  ("none" where the host was in none of the benchmark's spans).
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

# patterns matched against "<op name> <hlo text>" of each device event
KERNELS = {
    "gemm": re.compile(r"w4a8_fused"),
    "decode_attn": re.compile(r"paged_decode_attn|decode_attn"),
}
# control flow whose events span the ops inside them
CONTROL = re.compile(r"^(while|conditional|call)(\.|$)")
HOST_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Trace:
    # (name, start_ns, dur_ns, kernel) of each op on the first device;
    # kernel is the KERNELS entry the op belongs to, "" for none
    device: List[Tuple[str, int, int, str]]
    # (name, start_ns, dur_ns) of the benchmark's host spans
    host: List[Tuple[str, int, int]]
    window: Tuple[int, int]  # start, end in ns on the same clock

    def to_json(self) -> Dict:
        return {"device": self.device, "host": self.host,
                "window": list(self.window)}

    @classmethod
    def from_json(cls, d: Dict) -> "Trace":
        return cls(device=[tuple(e) for e in d["device"]],
                   host=[tuple(e) for e in d["host"]],
                   window=tuple(d["window"]))


def _short(name: str) -> str:
    """An op's name without its HLO text: "%fusion.12 = bf16[...] ..." ->
    "fusion.12"."""
    return name.split(" = ", 1)[0].lstrip("%")


def _kernel_of(ev) -> str:
    """The ``KERNELS`` entry an event belongs to ("" for none), from its
    name and the HLO text and metadata the profiler attaches."""
    text = ev.name + " " + " ".join(
        v for _, v in getattr(ev, "stats", ()) if isinstance(v, str))
    for kernel, pat in KERNELS.items():
        if pat.search(text):
            return kernel
    return ""


def find_xplane(logdir: str) -> str:
    hits = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True)
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return max(hits, key=os.path.getmtime)


def load(path: str, window_ns: Optional[Tuple[int, int]] = None) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device, host = [], []
    for plane in pd.planes:
        if plane.name == "/device:TPU:0":
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    if CONTROL.match(_short(ev.name)):
                        continue  # a loop's span covers its body's ops
                    device.append((_short(ev.name), int(ev.start_ns),
                                   int(ev.duration_ns), _kernel_of(ev)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        host.append((ev.name, int(ev.start_ns),
                                     int(ev.duration_ns)))
    device.sort(key=lambda e: e[1])
    host.sort(key=lambda e: e[1])
    if window_ns is None:
        # the harness wraps the traced window in one "bench.window" span
        win = [(s, s + d) for name, s, d in host if name == WINDOW_SPAN]
        window_ns = win[0] if win else (device[0][1],
                                        device[-1][1] + device[-1][2])
    return Trace(device=device, host=host, window=tuple(window_ns))


def busy_intervals(trace: Trace) -> List[Tuple[int, int]]:
    """The union of device op intervals, clipped to the window."""
    lo, hi = trace.window
    out: List[List[int]] = []
    for _, s, d, _ in trace.device:
        a, b = max(s, lo), min(s + d, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [tuple(x) for x in out]


def busy_s(trace: Trace) -> float:
    return sum(b - a for a, b in busy_intervals(trace)) / 1e9


def window_s(trace: Trace) -> float:
    return (trace.window[1] - trace.window[0]) / 1e9


def kernel_s(trace: Trace, kernel: str) -> float:
    return sum(d for _, _, d, k in trace.device if k == kernel) / 1e9


def top_ops(trace: Trace, n: int = 10) -> List[Tuple[str, float]]:
    tot: Dict[str, int] = {}
    for name, _, d, _ in trace.device:
        tot[name] = tot.get(name, 0) + d
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [(k, v / 1e9) for k, v in top]


def idle_by_host(trace: Trace, n: int = 10) -> List[Tuple[str, float]]:
    """Idle device time summed by the host span covering each gap's
    midpoint (innermost span; "none" where no span covers it)."""
    lo, hi = trace.window
    busy = busy_intervals(trace)
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    # the harness's spans do not nest, apart from the window around them
    spans = [h for h in trace.host if h[0] != WINDOW_SPAN]
    starts = [s for _, s, _ in spans]
    tot: Dict[str, int] = {}
    for a, b in gaps:
        mid = (a + b) // 2
        i = bisect.bisect_right(starts, mid) - 1
        key = "none"
        if i >= 0 and mid <= spans[i][1] + spans[i][2]:
            key = spans[i][0]
        tot[key] = tot.get(key, 0) + (b - a)
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [(k, v / 1e9) for k, v in top]
