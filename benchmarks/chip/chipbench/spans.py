"""The serving engine's own spans, named scopes, timestamps and counter,
reduced to per-layer numbers.

The engine (``repro.runtime.serve``) opens host spans on the profiler's
clock: ``serve.step`` around each ``Server.step`` call, and inside it
``serve.schedule``, ``serve.prepare``, ``serve.launch``, ``serve.sync``,
``serve.commit`` (and ``serve.audit``). Its device program carries named
scopes: ``embed``, ``layers`` around the layer scan, ``attn``,
``kv_write`` and ``mlp`` inside the scanned block, ``head`` and
``sample``. Each request records ``t_submit``, ``t_admit``,
``t_first_chunk`` and ``token_times``; ``Server.stats["prefill_steps"]``
counts the steps that carried a prefill chunk.

``load(path)`` reads a profiler trace into a ``ServeTrace``: the
``trace.Trace`` that ``trace.load`` gives, plus ``spans`` (every
``bench.*`` and ``serve.*`` host span) and ``scopes`` (each device op's
innermost scope, a list parallel to ``device``). An op's scope comes from
the ``tf_op`` stat of its event metadata, where the profiler keeps the
op's ``op_name`` (``jit(_decode_step)/layers/while/body/closed_call/attn/
kv_write/scatter:``). ``jax.profiler.ProfileData`` does not expose event
metadata, so the ``.xplane.pb`` is also read here as plain protobuf
fields; an op is matched to its metadata by its program (the enclosing
``XLA Modules`` event names it) and its HLO text.

An executable keeps the ``op_name`` of the tree that compiled it, and
JAX's persistent cache leaves that metadata out of its key: a program
loaded from a cache that another tree filled carries that tree's scopes.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from typing import Dict, List, Optional, Tuple

import numpy as np

from . import trace as tr

SERVE_PREFIX = "serve."
SCOPES = ("embed", "layers", "attn", "kv_write", "mlp", "head", "sample")
DEVICE_PLANE = "/device:TPU:0"
PROGRAM_ID = re.compile(r"\((\d+)\)$")


@dataclasses.dataclass
class ServeTrace(tr.Trace):
    # (name, start_ns, dur_ns) of the bench.* and serve.* host spans
    spans: List[Tuple[str, int, int]] = dataclasses.field(
        default_factory=list)
    # innermost named scope of each op in ``device`` ("none": no scope)
    scopes: List[str] = dataclasses.field(default_factory=list)

    def to_json(self) -> Dict:
        return dict(super().to_json(), spans=self.spans, scopes=self.scopes)

    @classmethod
    def from_json(cls, d: Dict) -> "ServeTrace":
        base = tr.Trace.from_json(d)
        return cls(device=base.device, host=base.host, window=base.window,
                   spans=[tuple(e) for e in d.get("spans", ())],
                   scopes=list(d.get("scopes", ())))


def innermost(op_name: str) -> str:
    """The innermost of ``SCOPES`` among an op_name's components."""
    for part in reversed(op_name.split("/")):
        if part in SCOPES:
            return part
    return "none"


# -- reading the .xplane.pb's event metadata ---------------------------------
def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of a protobuf message: an int
    for varint and fixed-width fields, a memoryview for the others."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            width = 8 if kind == 1 else 4
            v, i = int.from_bytes(buf[i:i + width], "little"), i + width
        else:
            raise ValueError(f"protobuf wire type {kind} at byte {i}")
        yield key >> 3, v


def _map_values(entry):
    """The value (field 2) of a protobuf map entry."""
    return next((v for f, v in _fields(entry) if f == 2), b"")


def op_names(path: str) -> Dict[Tuple[int, str], str]:
    """(program id, HLO text) -> op_name of every op the device plane's
    event metadata describes. XSpace.planes = 1; XPlane.name = 2,
    .event_metadata = 4, .stat_metadata = 5 (maps: key 1, value 2);
    XEventMetadata.name = 2, .stats = 5; XStatMetadata.id = 1, .name = 2;
    XStat.metadata_id = 1, .uint64_value = 3, .str_value = 5,
    .ref_value = 7 (an id in stat_metadata, whose name is the string)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    for fnum, plane in _fields(space):
        if fnum != 1:
            continue
        parts: Dict[int, list] = {2: [], 4: [], 5: []}
        for f, v in _fields(plane):
            if f in parts:
                parts[f].append(v)
        if not parts[2] or bytes(parts[2][0]).decode() != DEVICE_PLANE:
            continue
        stat_names = {}
        for entry in parts[5]:
            md = dict(_fields(_map_values(entry)))
            stat_names[md.get(1, 0)] = bytes(md.get(2, b"")).decode()
        out = {}
        for entry in parts[4]:
            name, op_name, program = "", None, None
            for f, v in _fields(_map_values(entry)):
                if f == 2:
                    name = bytes(v).decode()
                elif f == 5:
                    st = dict(_fields(v))
                    what = stat_names.get(st.get(1))
                    if what == "tf_op":
                        op_name = (bytes(st[5]).decode() if 5 in st
                                   else stat_names.get(st.get(7), ""))
                    elif what == "program_id":
                        program = st.get(3)
            if op_name is not None:
                out[(program, name)] = op_name
        return out
    return {}


def load(path: str, base: Optional[tr.Trace] = None) -> ServeTrace:
    """``base`` (by default ``trace.load(path)``) with the host spans of
    the benchmark and the engine and each device op's innermost named
    scope."""
    from jax.profiler import ProfileData

    base = base if base is not None else tr.load(path)
    names = op_names(path)
    spans, ops, modules = [], [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == DEVICE_PLANE:
            for line in plane.lines:
                if line.name == "XLA Modules":
                    for ev in line.events:
                        m = PROGRAM_ID.search(ev.name)
                        modules.append((int(ev.start_ns),
                                        int(ev.start_ns + ev.duration_ns),
                                        int(m.group(1)) if m else None))
                elif line.name == "XLA Ops":
                    # the same events, in the same order, as trace.load
                    ops.extend((int(ev.start_ns), ev.name)
                               for ev in line.events
                               if not tr.CONTROL.match(tr._short(ev.name)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((ev.name, int(ev.start_ns), int(ev.duration_ns))
                             for ev in line.events
                             if ev.name.startswith((tr.HOST_PREFIX,
                                                    SERVE_PREFIX)))
    ops.sort(key=lambda e: e[0])
    modules.sort()
    starts = [m[0] for m in modules]
    scopes = []
    for start, name in ops:
        i = bisect.bisect_right(starts, start) - 1
        program = modules[i][2] if i >= 0 and start < modules[i][1] else None
        scopes.append(innermost(names.get((program, name), "")))
    if len(scopes) != len(base.device):
        raise ValueError(f"{path}: {len(scopes)} device ops against the "
                         f"{len(base.device)} of its base trace")
    spans.sort(key=lambda e: (e[1], -e[2]))
    return ServeTrace(device=base.device, host=base.host, window=base.window,
                      spans=spans, scopes=scopes)


# -- reductions ---------------------------------------------------------------
def _covering(spans, starts, t: int) -> str:
    """The innermost span covering ``t`` (spans sorted by start, the
    outer first where two start together): the last-started one whose end
    is not before ``t``."""
    i = bisect.bisect_right(starts, t) - 1
    while i >= 0:
        name, s, d = spans[i]
        if t <= s + d:
            return name
        i -= 1
    return "none"


def idle_by_span(trace: ServeTrace, n: int = 12) -> List[Tuple[str, float]]:
    """Idle device seconds by the innermost ``bench.*`` or ``serve.*``
    span covering each gap's midpoint ("none" where no span does)."""
    lo, hi = trace.window
    gaps, t = [], lo
    for a, b in tr.busy_intervals(trace):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    spans = [s for s in trace.spans if s[0] != tr.WINDOW_SPAN]
    starts = [s for _, s, _ in spans]
    tot: Dict[str, int] = {}
    for a, b in gaps:
        key = _covering(spans, starts, (a + b) // 2)
        tot[key] = tot.get(key, 0) + (b - a)
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [(k, v / 1e9) for k, v in top]


def scope_s(trace: ServeTrace) -> List[Tuple[str, float]]:
    """Device seconds by innermost scope, largest first. ``layers`` holds
    the scan's own traffic (ops in the scan and in none of the block's
    scopes); ``none`` the ops under no scope."""
    tot: Dict[str, int] = {}
    for (_, _, d, _), scope in zip(trace.device, trace.scopes):
        tot[scope] = tot.get(scope, 0) + d
    return [(k, v / 1e9) for k, v in sorted(tot.items(), key=lambda kv: -kv[1])]


def launched_steps(trace: ServeTrace) -> List[Tuple[int, int, int]]:
    """(start_ns, dur_ns, sync_ns) of every ``serve.step`` span inside the
    window that launched a program (has a ``serve.launch`` child);
    ``sync_ns`` is its ``serve.sync`` child's duration."""
    lo, hi = trace.window
    out = []
    cur = None
    for name, s, d in trace.spans:
        if name == "serve.step":
            cur = [s, d, 0, False] if lo <= s and s + d <= hi else None
            if cur is not None:
                out.append(cur)
        elif cur is not None and cur[0] <= s <= cur[0] + cur[1]:
            if name == "serve.launch":
                cur[3] = True
            elif name == "serve.sync":
                cur[2] = d
    return [(s, d, sync) for s, d, sync, launched in out if launched]


def mean_span_ms(trace: ServeTrace, name: str) -> Optional[float]:
    """Mean milliseconds of the spans called ``name`` inside the window."""
    lo, hi = trace.window
    ds = [d for n, s, d in trace.spans if n == name and lo <= s
          and s + d <= hi]
    return float(np.mean(ds)) / 1e6 if ds else None


def step_host_ms(trace: ServeTrace) -> Optional[float]:
    """Mean host milliseconds of a launched engine step outside its wait
    on the device: ``serve.step`` minus its ``serve.sync`` child."""
    steps = launched_steps(trace)
    if not steps:
        return None
    return float(np.mean([d - sync for _, d, sync in steps])) / 1e6


def scope_ms_per_step(trace: ServeTrace, scope: str) -> Optional[float]:
    """Device milliseconds under ``scope`` per launched engine step."""
    steps = launched_steps(trace)
    spent = dict(scope_s(trace)).get(scope)
    if not steps or not spent:
        return None
    return 1e3 * spent / len(steps)


def prefill_wait_p90_ms(requests, t_end: float) -> Optional[float]:
    """90th percentile, over ``requests``, of submit -> first prefill
    chunk; a request with no chunk yet counts at its wait until
    ``t_end``. None where the engine keeps no first-chunk time."""
    if not requests or not hasattr(requests[0], "t_first_chunk"):
        return None
    waits = [((r.t_first_chunk if r.t_first_chunk is not None else t_end)
              - r.t_submit) * 1e3 for r in requests]
    return float(np.percentile(np.asarray(waits, np.float64), 90))


def prefill_lane_busy(stats_open: Dict, stats_close: Dict) -> Optional[float]:
    """Percent of the window's engine steps that carried a prefill chunk.
    None where the engine does not count them."""
    if "prefill_steps" not in stats_open:
        return None
    steps = stats_close["steps"] - stats_open["steps"]
    if steps <= 0:
        return None
    return 100.0 * (stats_close["prefill_steps"]
                    - stats_open["prefill_steps"]) / steps
