"""The serving engine's own spans, timestamps, counter and named scopes.

Covers: every engine step — decode-only and mixed, on the mixed and the
alternating engine — emits ``serve.step`` with ``serve.schedule``,
``prepare``, ``launch``, ``sync`` and ``commit`` in order (``serve.audit``
only when the audit runs); per-request ``t_submit <= t_admit <=
t_first_chunk <= token_times[0]`` on both engines, the first values
surviving preemption, resume and spill eviction, and carried into
``RequestResult``; ``stats["prefill_steps"]`` counting exactly the steps
that carried a chunk; and the compiled engine step carrying the
``embed``, ``layers``, ``attn``, ``kv_write``, ``mlp``, ``head`` and
``sample`` scopes in its op metadata.
"""
import contextlib
import re

import jax
import numpy as np
import pytest

from conftest import tiny_lm_cfg

from repro import models
from repro.runtime import serve
from repro.runtime.serve import (Request, SchedulerConfig, Server,
                                 ServerConfig)

PHASES = ["serve.schedule", "serve.prepare", "serve.launch", "serve.sync",
          "serve.commit"]


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_lm_cfg()
    return cfg, models.init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture
def spans(monkeypatch):
    """(depth, name) of every span the engine opens, in order."""
    log, depth = [], [0]

    @contextlib.contextmanager
    def span(name):
        log.append((depth[0], name))
        depth[0] += 1
        try:
            yield
        finally:
            depth[0] -= 1

    monkeypatch.setattr(serve, "_span", span)
    return log


def _server(tiny, engine="mixed", slots=2, spill_budget_bytes=None, **kw):
    cfg, params = tiny
    return Server(params, cfg, ServerConfig(
        slots=slots, max_seq=48, page_size=4, a_fmt=None,
        scheduler=SchedulerConfig(engine=engine, prefill_token_budget=8,
                                  spill_budget_bytes=spill_budget_bytes),
        **kw))


def _one_step(srv, log):
    del log[:]
    assert srv.step()
    return list(log)


@pytest.mark.parametrize("engine", ["mixed", "alternating"])
def test_each_step_emits_its_phases_in_order(tiny, spans, engine):
    srv = _server(tiny, engine)
    srv.submit(Request(rid=0, prompt=list(range(1, 12)), max_new=4))
    want = [(0, "serve.step")] + [(1, p) for p in PHASES]
    kinds = set()
    while srv.active[0] is not None or srv.queue:
        chunks = srv.stats["prefill_tokens"]
        got = _one_step(srv, spans)
        kinds.add(srv.stats["prefill_tokens"] > chunks)
        assert got == want
    # steps that prefilled (in the mixed engine's program, or in the
    # alternating engine's schedule) and steps that only decoded
    assert kinds == {True, False}


def test_audit_runs_under_its_own_span(tiny, spans):
    srv = _server(tiny, audit_every=1)
    srv.submit(Request(rid=0, prompt=[3, 4, 5], max_new=2))
    got = _one_step(srv, spans)
    assert got == ([(0, "serve.step")] + [(1, p) for p in PHASES]
                   + [(1, "serve.audit")])


def test_a_step_with_nothing_to_run_schedules_only(tiny, spans):
    srv = _server(tiny)
    assert not srv.step()
    assert spans == [(0, "serve.step"), (1, "serve.schedule")]


def _drive(srv, reqs):
    """Step to the end, recording each request's first non-None admit and
    first-chunk times as they appear."""
    for r in reqs:
        srv.submit(r)
    first = {}
    for _ in range(2000):
        if not srv.step() and not srv.queue and not srv.preempted:
            break
        for r in reqs:
            got = first.setdefault(r.rid, [None, None])
            for i, v in enumerate((r.t_admit, r.t_first_chunk)):
                if got[i] is None:
                    got[i] = v
    return first


@pytest.mark.parametrize("engine", ["mixed", "alternating"])
@pytest.mark.parametrize("spill_budget", [None, 0],
                         ids=["resume", "evict"])
def test_timestamps_order_and_survive_preemption(tiny, engine,
                                                 spill_budget):
    rng = np.random.default_rng(17)
    reqs = [Request(rid=i, max_new=12,
                    prompt=rng.integers(1, 64, size=int(n)).tolist())
            for i, n in enumerate(rng.integers(3, 18, size=6))]
    srv = _server(tiny, engine, slots=3, pool_pages=12,
                  spill_budget_bytes=spill_budget)
    first = _drive(srv, reqs)
    assert srv.stats["preemptions"] >= 1, "scenario must steal"
    if spill_budget == 0:
        assert srv.stats["spill_evictions"] >= 1, "scenario must evict"
    for r in reqs:
        assert r.done and r.status == "ok"
        assert r.t_submit <= r.t_admit <= r.t_first_chunk \
            <= r.token_times[0]
        assert [r.t_admit, r.t_first_chunk] == first[r.rid]
        res = r.result()
        assert (res.submitted_at, res.admitted_at, res.first_chunk_at) == (
            r.t_submit, r.t_admit, r.t_first_chunk)
    assert any(r.preemptions for r in reqs)


def test_a_request_never_admitted_has_no_admit_time(tiny):
    res = Request(rid=0, prompt=[1, 2]).result()
    assert res.admitted_at is None and res.first_chunk_at is None


@pytest.mark.parametrize("engine", ["mixed", "alternating"])
def test_prefill_steps_counts_the_steps_that_carried_a_chunk(tiny, engine):
    srv = _server(tiny, engine, slots=3)
    rng = np.random.default_rng(5)
    for i, n in enumerate((5, 19, 30, 2)):
        srv.submit(Request(rid=i, max_new=6,
                           prompt=rng.integers(1, 64, size=n).tolist()))
    carried = 0
    while True:
        before = (srv.stats["steps"], srv.stats["prefill_tokens"])
        if not srv.step():
            break
        # a chunk that rode the step's own program moved prefill_tokens
        # and was launched with it; the alternating engine's admission
        # prefill runs before the step's program, in separate programs
        if engine == "mixed":
            carried += srv.stats["prefill_tokens"] > before[1]
    assert srv.stats["prefill_tokens"] > 0
    assert srv.stats["prefill_steps"] == carried
    if engine == "mixed":
        assert 0 < carried < srv.stats["steps"]
    else:
        assert carried == 0


SCOPES = ("embed", "layers", "attn", "kv_write", "mlp", "head", "sample")


def test_compiled_step_carries_the_named_scopes(tiny):
    """The mixed step program, lowered and compiled at test size with the
    arguments the engine passes: every scope is a component of some op's
    ``op_name`` metadata, which is what a profiler trace attributes device
    time by."""
    srv = _server(tiny)
    seen = {}
    real = srv._decode

    def spy(*a, **k):
        seen["call"] = (a, k)
        return real(*a, **k)

    srv._decode = spy
    srv.submit(Request(rid=0, prompt=list(range(1, 7)), max_new=2))
    srv.step()
    a, k = seen["call"]
    hlo = serve._decode_step_jit.lower(
        *a, cfg=srv.cfg, a_fmt=srv.a_fmt, **k).compile().as_text()
    parts = set()
    for op_name in re.findall(r'op_name="([^"]*)"', hlo):
        parts.update(op_name.split("/"))
    assert set(SCOPES) <= parts
    # the page append sits inside the attention scope, inside the scan
    assert re.search(r'op_name="[^"]*/layers/[^"]*/attn/kv_write/', hlo)
