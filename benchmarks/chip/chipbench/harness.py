"""One run of one cell: set-up, the measured window, the check.

Set-up builds the W4A8 checkpoint on the device from the seed
(``weights.py``), a ``Server`` with the configuration's engine sizes and
``kernel_backend="pallas"``, and compiles every program the cell's traffic
can reach: one request for each set of (chunk, page-table) buckets the
prompt lengths can produce, each decoding two tokens, drained. Arrivals then
start ``preroll_s`` before the window opens, so it opens on a loaded engine.

The benchmark drives the server with its own loop around ``Server.submit``
and ``Server.step``, with a host span around each (``bench.submit``,
``bench.step``), around the bookkeeping after each step (``bench.drain``)
and around waits for the next arrival (``bench.wait``). Open-loop requests
are timed from when they were due; the requests due inside the window are
the ones counted, and after the window closes the load goes on until each
of them has its first token (or ``tail_s`` has passed: it then counts as
failed, at the whole time it waited).
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import os
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Optional

import numpy as np

from . import check, costs, trace as tr, traffic as tg
from .peaks import peaks as peaks_of

TRACE_MAX_S = 6.0


def use_compile_cache(default_dir: str) -> str:
    """Turn on JAX's persistent compilation cache where
    ``JAX_COMPILATION_CACHE_DIR`` points, or else at ``default_dir``, a
    fixed path (the path is part of the cache's key). The variable is set
    to the directory chosen, for the program's own cache set-up. Every
    program is cached however fast it compiled, and nothing is evicted: an
    evicting cache keeps an access-time file per entry, and one missing
    file makes every later write fail. Returns the directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or default_dir
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    return path


def arch_config(config: Dict):
    from repro.models.config import ArchConfig

    return ArchConfig(name=config["name"], **config["arch"])


def chunk_shapes(n: int, budget: int, page: int) -> set:
    """The (padded chunk, table width) buckets the mixed engine step takes
    for an ``n``-token prompt: chunks of ``budget`` tokens from position 0,
    each padded to a power of two, each with the page table trimmed to the
    power of two of the pages it reaches (``Server._chunk_plan``)."""
    out, pos = set(), 0
    while pos < n:
        take = min(budget, n - pos)
        padded = min(costs.next_pow2(take), budget)
        w = costs.next_pow2(pos // page + -(-padded // page))
        out.add((padded, w))
        pos += take
    return out


def warm_lengths(lo: int, hi: int, budget: int, page: int) -> List[int]:
    """Prompt lengths in [lo, hi] whose chunks reach every bucket a prompt
    in that range can reach, picked greedily (most new buckets first)."""
    shapes = {n: chunk_shapes(n, budget, page) for n in range(lo, hi + 1)}
    need = set().union(*shapes.values())
    out = []
    while need:
        n = max(shapes, key=lambda m: (len(shapes[m] & need), m))
        out.append(n)
        need -= shapes[n]
    return sorted(out)


def span(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


@dataclasses.dataclass
class Tracked:
    spec: tg.Spec
    req: object  # repro.runtime.serve.Request
    due: float  # perf_counter time it was due
    counted: bool


@dataclasses.dataclass
class Window:
    t_open: float = 0.0
    t_close: float = 0.0
    t_trace: float = 0.0  # when the trace started (== t_close if none)
    stats_open: Optional[Dict] = None
    stats_close: Optional[Dict] = None
    steps: List = dataclasses.field(default_factory=list)  # (t0, t1, Step)
    lateness: List[float] = dataclasses.field(default_factory=list)
    compiles: int = 0
    queue_open: int = 0  # requests waiting for a slot at the open, close
    queue_close: int = 0
    backlog_open: int = 0  # requests sent with no first token yet
    backlog_close: int = 0
    t_end: float = 0.0  # when the loop stopped waiting for first tokens


def make_server(params, cfg, config: Dict, backend: str):
    from repro.runtime.serve import (CachePolicy, SchedulerConfig, Server,
                                     ServerConfig)

    s, q = config["serve"], config["quant"]
    return Server(params, cfg, ServerConfig(
        slots=s["slots"], max_seq=s["max_seq"], page_size=s["page_size"],
        pool_pages=s["pool_pages"], a_fmt=q["a_fmt"],
        kernel_backend=backend,
        cache=CachePolicy(active_fmt=q["kv_fmt"]),
        scheduler=SchedulerConfig(
            prefill_token_budget=s["prefill_token_budget"])))


def precompile(server, shapes, workers: int = 4) -> None:
    """Compile the engine step for every (chunk, table) bucket in
    ``shapes`` and the decode-only step into the process's and the
    persistent cache, so that the warm-up's own compiles become cache hits.
    The programs are lowered one after another (lowering is Python, and
    the names it gives ops depend on its order, so a fixed order keeps the
    persistent cache's keys the same from run to run) and compiled in
    ``workers`` threads. It mirrors the arguments ``Server._step_mixed``
    and ``Server._step_decode`` pass to ``serve._decode_step_jit``, which
    the server has no public entry for; where that no longer holds, it
    raises, and the run ends with no result, rather than measure a set-up
    that has quietly changed."""
    import concurrent.futures as cf

    import jax
    import jax.numpy as jnp

    from repro.runtime import kv_cache as kvc
    from repro.runtime import serve

    n = server.slots
    sds = jax.ShapeDtypeStruct
    like = lambda a: sds(a.shape, a.dtype)
    params = jax.tree.map(like, server.params)
    pools = jax.tree.map(like, server.pools)
    i32 = jnp.int32
    samp = lambda m: (sds((m,), jnp.float32), sds((m,), i32),
                      sds((m,), jnp.float32), sds((m,), jnp.uint32),
                      sds((m,), i32))
    dec = kvc.PagedState(page_table=sds(server.page_table.shape, i32),
                         lengths=sds((n,), i32))
    calls = [(sds((n, 1), i32), dec, sds((n,), jnp.bool_), samp(n))]
    for padded, w in sorted(shapes):
        pre = kvc.PagedState(page_table=sds((1, w), i32),
                             lengths=sds((1,), i32),
                             chunk_len=sds((1,), i32))
        calls.append((sds((1, n + padded), i32),
                      dec._replace(prefill=pre),
                      sds((n + 1,), jnp.bool_), samp(n + 1)))
    lowered = [serve._decode_step_jit.lower(
        params, pools, *args, cfg=server.cfg, a_fmt=server.a_fmt,
        backend=server._backend()) for args in calls]
    with cf.ThreadPoolExecutor(workers) as ex:
        list(ex.map(lambda lw: lw.compile(), lowered))


def warm_up(server, config: Dict, traffic: Dict, seed: int) -> int:
    """Compile every program the traffic can reach; returns the number of
    warm-up requests."""
    from repro.runtime.serve import Request

    s = config["serve"]
    hi = min(traffic["prompt"]["max"], s["max_seq"] - 2)
    lens = warm_lengths(traffic["prompt"]["min"], hi,
                        server.prefill_token_budget, s["page_size"])
    precompile(server, set().union(*(chunk_shapes(
        n, server.prefill_token_budget, s["page_size"]) for n in lens)))
    rng = np.random.default_rng([seed, 3])
    vocab = config["arch"]["vocab_size"]
    for i, n in enumerate(lens):
        server.submit(Request(rid=10 ** 9 + i, max_new=2,
                              prompt=rng.integers(1, vocab, size=n).tolist()))
    server.run_until_drained()
    return len(lens)


def _request(spec: tg.Spec, rid: int, seed: int):
    from repro.runtime.serve import Request, SamplingParams

    samp = SamplingParams(temperature=spec.temperature, top_p=spec.top_p,
                          top_k=spec.top_k,
                          seed=(seed * 1000003 + spec.idx) % (2 ** 31))
    return Request(rid=rid, prompt=list(spec.prompt), max_new=spec.max_new,
                   sampling=samp)


def _decoding(server) -> Dict[int, int]:
    """slot -> context length of every row that decodes this step (the
    engine's own rule: the row is at prompt + emitted - 1 tokens)."""
    out = {}
    for s, r in enumerate(server.active):
        if r is not None and int(server.lengths[s]) >= (
                len(r.prompt) + max(len(r.out) - 1, 0)):
            out[s] = int(server.lengths[s])
    return out


def _step(server) -> Optional[costs.Step]:
    """Run one engine step; returns what it carried (None: no work)."""
    dec = _decoding(server)
    before = {s: (id(r), int(server.lengths[s]))
              for s, r in enumerate(server.active) if r is not None}
    pre = server.stats["prefill_tokens"]
    if not server.step():
        return None
    take = server.stats["prefill_tokens"] - pre
    pos = 0
    if take:
        for s, r in enumerate(server.active):
            if r is None or s in dec:
                continue
            rid, l0 = before.get(s, (None, 0))
            l0 = l0 if rid == id(r) else 0
            if int(server.lengths[s]) - l0 == take:
                pos = l0
                break
    padded = min(costs.next_pow2(take), server.prefill_token_budget) \
        if take else 0
    return costs.Step(slots=server.slots, decode_lens=tuple(dec.values()),
                      chunk_pos=pos, chunk_take=take, chunk_padded=padded)


class CompileCount:
    """Counts programs as JAX compiles them or loads them from the
    persistent cache (``n``), and the cache's hits and misses."""

    def __init__(self):
        import jax

        self.n = self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_time(self, name, secs, **_kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def _on_event(self, name, **_kw):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def drive(server, traffic: Dict, config: Dict, seed: int, seconds: float,
          rate: Optional[float], trace_dir: Optional[str],
          compiles: Optional[CompileCount] = None):
    """Offer the traffic to ``server`` and measure one window.

    Returns (tracked requests, Window)."""
    import jax

    preroll = float(traffic.get("preroll_s", 5.0))
    tail = float(traffic.get("tail_s", 60.0))
    vocab, max_seq = config["arch"]["vocab_size"], config["serve"]["max_seq"]
    closed = traffic["loop"] == "closed"
    if closed:
        clients = traffic["clients"]
        clients = server.slots if clients == "slots" else int(clients)
        pool = tg.draw_specs(traffic, clients * 64, seed, vocab, max_seq)
        pending = []
    else:
        pending = tg.open_schedule(traffic, rate, preroll + seconds + tail,
                                   seed, vocab, max_seq)
        pool = []
    win = Window()
    tracked: List[Tracked] = []
    by_rid: Dict[int, Tracked] = {}
    t0 = time.perf_counter()
    win.t_open, win.t_close = t0 + preroll, t0 + preroll + seconds
    trace_s = min(TRACE_MAX_S, seconds / 2) if trace_dir else 0.0
    win.t_trace = win.t_close - trace_s
    nxt = 0  # next pending open-loop request
    done_at = len(server.finished)
    window_ann = None
    counted_left = 0
    phase = "preroll"

    def backlog():
        return sum(1 for t in tracked
                   if not t.req.token_times and not t.req.done)

    def submit(spec, due, now):
        t = Tracked(spec=spec, req=_request(spec, len(tracked), seed),
                    due=due, counted=win.t_open <= due < win.t_close)
        server.submit(t.req)
        tracked.append(t)
        by_rid[t.req.rid] = t
        if phase != "preroll" or t.counted:
            win.lateness.append(now - due)
        return t

    if closed:
        with span("bench.submit"):
            for c in range(clients):
                submit(pool[c], t0, t0)
        nxt = clients
    while True:
        now = time.perf_counter()
        if phase == "preroll" and now >= win.t_open:
            phase, win.t_open = "open", now
            win.stats_open = dict(server.stats)
            win.queue_open = len(server.queue)
            win.backlog_open = backlog()
            if compiles is not None:
                win.compiles = -compiles.n
        if phase == "open" and trace_dir and now >= win.t_trace:
            jax.profiler.start_trace(trace_dir)
            window_ann = span(tr.WINDOW_SPAN)
            window_ann.__enter__()
            phase, win.t_trace = "traced", time.perf_counter()
        if phase in ("open", "traced") and now >= win.t_close:
            if window_ann is not None:
                window_ann.__exit__(None, None, None)
                jax.profiler.stop_trace()
            if phase == "open":
                win.t_trace = now
            phase, win.t_close = "closed", now
            win.stats_close = dict(server.stats)
            win.queue_close = len(server.queue)
            win.backlog_close = backlog()
            if compiles is not None:
                win.compiles += compiles.n
            counted_left = sum(1 for t in tracked
                               if t.counted and not t.req.token_times
                               and not t.req.done)
        if phase == "closed":
            if closed or counted_left == 0 or now > win.t_close + tail:
                win.t_end = now
                break
        if not closed:
            with span("bench.submit"):
                while nxt < len(pending) and t0 + pending[nxt].due <= now:
                    submit(pending[nxt], t0 + pending[nxt].due, now)
                    nxt += 1
        ta = time.perf_counter()
        with span("bench.step"):
            st = _step(server)
        t1 = time.perf_counter()
        if st is None:
            with span("bench.wait"):
                wake = win.t_close if phase != "closed" else now + 1e-3
                if not closed and nxt < len(pending):
                    wake = min(wake, t0 + pending[nxt].due)
                if phase == "preroll":
                    wake = min(wake, win.t_open)
                time.sleep(max(0.0, min(wake - t1, 0.01)))
            continue
        if phase in ("open", "traced"):
            win.steps.append((ta, t1, st))
        with span("bench.drain"):
            fin = server.finished
            while done_at < len(fin):
                r = fin[done_at]
                done_at += 1
                t = by_rid.get(r.rid)
                if t is None:
                    continue
                if closed and phase != "closed":
                    submit(pool[nxt % len(pool)], t1, t1)
                    nxt += 1
            if phase == "closed":
                counted_left = sum(1 for t in tracked
                                   if t.counted and not t.req.token_times
                                   and not t.req.done)
    return tracked, win


def _pct(values, q: float) -> float:
    """The q-th percentile (0-100), interpolated between order statistics
    (numpy's default, as ``benchmarks/run.py`` took it)."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def e2e_values(tracked: List[Tracked], win: Window,
               closed: bool = False) -> Dict[str, float]:
    """Every end-to-end number a cell can report, from one window. A
    closed loop reports no time to first token: its requests are sent as
    the last ones finish, and only those the server failed count as
    failed."""
    lo, hi = win.t_open, win.t_close
    counted = [t for t in tracked if t.counted]
    ttft, missing = [], 0
    for t in counted:
        r = t.req
        if r.token_times and r.status != "failed":
            ttft.append((r.token_times[0] - t.due) * 1e3)
        elif not closed:
            # failed, or no first token when the loop stopped waiting:
            # missing, counted at the whole wait (a lower bound)
            ttft.append((win.t_end - t.due) * 1e3)
            missing += 1
    if closed:
        ttft = []
        missing = sum(1 for t in counted if t.req.status == "failed")
    gaps = []
    gen = 0
    for t in tracked:
        ts = t.req.token_times
        gen += sum(1 for x in ts if lo <= x < hi)
        gaps.extend((b - a) * 1e3 for a, b in zip(ts, ts[1:]) if lo <= b < hi)
    s0, s1 = win.stats_open, win.stats_close
    prefilled = s1["prefill_tokens"] - s0["prefill_tokens"]
    out = {"tok_s": (prefilled + gen) / (hi - lo)}
    if ttft:
        out["ttft_p90_ms"] = _pct(ttft, 90)
    if gaps:
        out["itl_p95_ms"] = _pct(gaps, 95)
    out["_counted"] = len(counted)
    out["_gaps"] = len(gaps)
    out["_failed"] = missing
    out["_steps"] = s1["steps"] - s0["steps"]
    return out


def layer_context(config: Dict, win: Window, trace: Optional[tr.Trace],
                  device_kind: str) -> Dict:
    host = [st for a, b, st in win.steps if b <= win.t_trace]
    traced = [st for a, b, st in win.steps if a >= win.t_trace]
    return {"arch": config["arch"], "quant": config["quant"],
            "serve": config["serve"], "peaks": peaks_of(device_kind),
            "host_window_s": win.t_trace - win.t_open, "host_steps": host,
            "trace": trace, "trace_steps": traced}


def served_greedy(tracked: List[Tracked]) -> List[check.Served]:
    return [check.Served(prompt=t.spec.prompt, out=tuple(t.req.out))
            for t in tracked
            if t.spec.greedy and t.req.done and t.req.status == "ok"]


@contextlib.contextmanager
def trace_dir(enabled: bool):
    if not enabled:
        yield None
        return
    d = tempfile.mkdtemp(prefix="chipbench-trace-")
    try:
        yield d
    finally:
        shutil.rmtree(d, ignore_errors=True)


def run(cell, seed: int, seconds: float, trace: bool, backend: str = "pallas",
        setup_clock=None, control: bool = False, fault=None, log=None):
    """One run of ``cell`` (a ``spec.Cell``). Returns the result object the
    command prints, plus the list of (name, value, limit) compared: the
    served tokens' readings, or with ``control`` the control's, which then
    decide ``correct`` in their place."""
    import jax

    log = log or (lambda m: print(m, file=sys.stderr, flush=True))
    setup_clock = setup_clock or (lambda: 0.0)
    config, traffic = cell.config, cell.traffic
    dev = jax.devices()[0]
    compiles = CompileCount()
    cfg = arch_config(config)
    from . import weights

    t_start = setup_clock()
    params = weights.build(cfg, seed, config["quant"])
    t_weights = setup_clock()
    server = make_server(params, cfg, config, backend)
    if fault is not None:
        fault(server)
    n_warm = warm_up(server, config, traffic, seed)
    rate = None if traffic["loop"] == "closed" else tg.rate_of(traffic, config)
    log(f"set-up: process start {t_start:.2f} s, weights "
        f"{t_weights - t_start:.2f} s, server and {n_warm} warm-up requests "
        f"{setup_clock() - t_weights:.2f} s; {compiles.n} programs, "
        f"persistent cache {compiles.hits} hits {compiles.misses} misses; "
        f"offered rate {rate} req/s")
    with trace_dir(trace) as tdir:
        tracked, win = drive(server, traffic, config, seed, seconds, rate,
                             tdir, compiles)
        setup_s = win.t_open - time.perf_counter() + setup_clock()
        tr_data = tr.load(tr.find_xplane(tdir)) if tdir else None
    vals = e2e_values(tracked, win, closed=traffic["loop"] == "closed")
    late = np.asarray(win.lateness) * 1e3
    log(f"generator lateness ms: p50 {np.percentile(late, 50):.3f} "
        f"p99 {np.percentile(late, 99):.3f} max {late.max():.3f} "
        f"over {late.size} requests")
    log(f"window: {win.t_close - win.t_open:.3f} s, {vals['_steps']} steps, "
        f"{vals['_counted']} requests due, {vals['_failed']} failed, "
        f"{vals['_gaps']} token gaps, {win.compiles} compiles inside")
    stats = dict(server.stats)
    mem = dev.memory_stats() or {}
    peak = int(mem.get("peak_bytes_in_use", 0))
    served = served_greedy(tracked)
    slots = server.slots
    del server
    gc.collect()

    metrics = {}
    if trace:
        from .spec import load_reader

        ctx = layer_context(config, win, tr_data, dev.device_kind)
        for m in cell.per_layer:
            v = load_reader(m.name)(ctx)
            if v is not None:
                metrics[m.name] = {"value": float(v), "unit": m.unit}
    else:
        for m in cell.end_to_end:
            v = setup_s if m.name == "setup_s" else vals.get(m.name)
            if v is not None:
                metrics[m.name] = {"value": float(v), "unit": m.unit}

    chk = config["check"]
    sample = check.pick(served, seed, chk["rows"], chk["tokens"])
    got = check.gaps(params, config["arch"], sample, chk["rows"],
                     config["serve"]["max_seq"], control=control)
    # with ``control`` the control stands in the program's place: its
    # readings are the numbers compared, and they decide ``correct``
    pre = "control." if control else ""
    compared = [(pre + name, got.get(pre + name), lim)
                for name, lim in chk["limits"].items()]
    correct = got["compared"] > 0 and all(
        v is not None and v <= lim for _, v, lim in compared)
    log(f"check: {len(sample)} requests, {got['compared']} served tokens "
        f"compared; readings {got}; engine served "
        f"{stats['decoded_tokens']} decode tokens on {slots} slots")
    result = {
        "correct": bool(correct),
        "attempted": vals["_counted"] if vals["_counted"] else len(tracked),
        "failed": vals["_failed"],
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": jax.device_count(), "memory_peak_bytes": peak},
    }
    if tr_data is not None:
        result["device"]["busy_s"] = tr.busy_s(tr_data)
        result["device"]["window_s"] = tr.window_s(tr_data)
        result["breakdown"] = {"device_ops": tr.top_ops(tr_data),
                               "idle_gaps": tr.idle_by_host(tr_data)}
    result["check"] = {name: {"value": v, "limit": lim}
                       for name, v, lim in compared}
    return result, compared
