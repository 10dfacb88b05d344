"""Token-budget continuous-batching scheduler: page-steal preemption,
streaming paged prefill, pool-accounting invariants.

Covers: preempted-then-resumed requests generate token-identical greedy
output vs an uncontended solo run (bf16 + fp8 pages — spills restore page
payloads bit-exactly); a seeded fuzz of admit/steal/resume sequences
asserting the pool never leaks or double-owns a page; streaming chunked
prefill parity against the monolithic-prefill + one-shot-splice path (GQA
and MLA); watermark admission hysteresis; the run_until_drained starvation
guard; and token-budget vs reserve-on-admit utilization under a long-tail
max_new workload."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import tiny_lm_cfg

from repro import models
from repro.runtime import kv_cache as kvc
from repro.runtime.serve import (Request, SchedulerConfig, Server,
                                 ServerConfig)


def _assert_pool_invariants(srv):
    """Refcounted pool accounting invariants, checked by the *production*
    auditor (``Server.audit()`` — promoted from this file's PR 5 fuzz
    helper): every page is exactly one of mapped / parked / free and the
    three sets partition the pool, the page table mirrors ownership, a
    slot's pages split into a leading shared-frozen run followed by
    exclusively-owned private pages, and slabs are exclusively owned.
    Running it here means every scheduler fuzz also exercises the auditor
    itself (a clean audit returns a summary instead of raising)."""
    summary = srv.audit()
    assert summary["violations"] == 0


def _drain_checked(srv, max_steps=500):
    """Step to drain, asserting pool invariants after every engine step."""
    done_before = len(srv.finished)
    for _ in range(max_steps):
        went = srv.step()
        _assert_pool_invariants(srv)
        if not went and not srv.queue and not srv.preempted:
            break
    else:
        raise AssertionError("drain did not converge")
    return srv.finished[done_before:]


class TestPreemptResume:
    @pytest.mark.parametrize("kv_fmt", [None, "fp8_e4m3"])
    def test_resume_token_identical(self, trained_tiny, kv_fmt):
        """A preempted-then-resumed request produces token-identical greedy
        output vs an uncontended run: the steal spills the page payload
        bit-exactly and the restored pages are logically identical."""
        cfg, params = trained_tiny
        rng = np.random.default_rng(11)
        prompts = [rng.integers(1, cfg.vocab_size, size=5).tolist()
                   for _ in range(2)]
        # pool of 6 x 4-token pages; both requests charge 2 prompt pages + 1
        # headroom, then both grow past 12 tokens -> the later-admitted
        # request (rid 1) is the steal victim and must resume afterwards
        srv = Server(params, cfg,
                     ServerConfig(slots=2, max_seq=32, kv_fmt=kv_fmt,
                                  page_size=4, pool_pages=6, a_fmt=None))
        reqs = [Request(rid=i, prompt=p, max_new=10)
                for i, p in enumerate(prompts)]
        for r in reqs:
            srv.submit(r)
        _drain_checked(srv)
        assert reqs[1].preemptions >= 1, "scenario must actually preempt"
        assert srv.stats["resumes"] >= 1
        for r in reqs:
            solo = Server(params, cfg,
                          ServerConfig(slots=1, max_seq=32, kv_fmt=kv_fmt,
                                       page_size=4, a_fmt=None))
            ref = Request(rid=99, prompt=list(r.prompt), max_new=10)
            solo.submit(ref)
            solo.run_until_drained()
            assert r.out == ref.out, (r.rid, r.out, ref.out)

    def test_priority_protects_high(self, trained_tiny):
        """Steal victims are picked lowest-priority-first, not by slot
        order: the high-priority request is never preempted."""
        cfg, params = trained_tiny
        rng = np.random.default_rng(3)
        srv = Server(params, cfg,
                     ServerConfig(slots=2, max_seq=32, kv_fmt="fp8_e4m3",
                                  page_size=4, pool_pages=6, a_fmt=None))
        lo = Request(rid=0, prompt=rng.integers(1, 64, 5).tolist(),
                     max_new=10, priority=0)
        hi = Request(rid=1, prompt=rng.integers(1, 64, 5).tolist(),
                     max_new=10, priority=1)
        srv.submit(lo)
        srv.submit(hi)  # admitted later -> default tie-break victim, but
        _drain_checked(srv)  # priority=1 shields it
        assert srv.stats["preemptions"] >= 1
        assert hi.preemptions == 0
        assert lo.preemptions >= 1


class TestFuzzAccounting:
    def test_admit_steal_resume_fuzz(self):
        """Seeded fuzz over staggered submissions on a tight pool: every
        step preserves pool-accounting invariants, every request finishes
        fully, and the drained pool is whole again."""
        cfg = tiny_lm_cfg()
        params = models.init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(7)
        srv = Server(params, cfg,
                     ServerConfig(slots=3, max_seq=32, kv_fmt="fp8_e4m3",
                                  page_size=4, pool_pages=9, a_fmt=None,
                                  scheduler=SchedulerConfig(headroom_pages=1, steal_cooldown=1)))
        # prompt lengths restricted to a few values: each distinct length is
        # a fresh prefill-chunk jit trace on CPU
        reqs = [Request(rid=i, prompt=rng.integers(1, 64, rng.choice([3, 5, 9])).tolist(),
                        max_new=int(rng.choice([2, 6, 14])),
                        priority=int(rng.choice([0, 1])))
                for i in range(12)]
        pending = list(reqs)
        for _ in range(4):  # staggered arrivals fuzz the admit sequence
            srv.submit(pending.pop(0))
        for step in range(600):
            went = srv.step()
            _assert_pool_invariants(srv)
            if pending and step % 3 == 0:
                srv.submit(pending.pop(0))
            if not went and not pending and not srv.queue and not srv.preempted:
                break
        assert len(srv.finished) == len(reqs)
        assert all(len(r.out) == r.max_new for r in reqs)
        assert not any(r.truncated for r in reqs)
        assert sorted(srv.free_pages + srv.reusable_pages) == \
            list(range(srv._n_pages))
        assert (srv.page_refs == 0).all()
        assert srv.stats["preemptions"] >= 1, "fuzz should exercise steals"
        assert srv.stats["preemptions"] == (srv.stats["resumes"]
                                            + srv.stats["resume_fallbacks"])


class TestStreamingPrefill:
    def test_gqa_stream_matches_splice(self):
        """Chunked in-graph prefill writes bit-identical pages to the
        monolithic prefill + one-shot splice, and the final-chunk logits
        match the full prefill's last-token logits."""
        cfg = tiny_lm_cfg()
        params = models.init_params(cfg, jax.random.PRNGKey(1))
        rng = np.random.default_rng(5)
        prompt = rng.integers(1, cfg.vocab_size, size=11).tolist()
        page, n = 4, 11
        for fmt in (None, "fp8_e4m3"):
            logits_ref, caches = models.prefill(
                params, cfg, {"tokens": jnp.asarray([prompt], jnp.int32)}, 12)
            pool_ref = kvc.init_gqa_pool(cfg.n_layers, 6, page, cfg.n_kv_heads,
                                         cfg.resolved_head_dim, fmt)
            pool_ref = kvc.splice_prefill(pool_ref, caches[0]["kv"],
                                          np.array([0, 1, 2]), n)
            pools = [{"kv": kvc.init_gqa_pool(cfg.n_layers, 6, page,
                                              cfg.n_kv_heads,
                                              cfg.resolved_head_dim, fmt)}]
            pos, ids = 0, [0, 1, 2]
            while pos < n:
                take = min(2 * page, n - pos)
                w = kvc.pages_needed(pos + take, page)
                table = np.zeros((1, w), np.int32)
                table[0] = ids[:w]
                st = kvc.PagedState(jnp.asarray(table),
                                    jnp.asarray([pos], jnp.int32))
                logits, pools = models.decode_step(
                    params, cfg, jnp.asarray([prompt[pos: pos + take]], jnp.int32),
                    pools, st)
                pos += take
            st = kvc.PagedState(jnp.asarray([[0, 1, 2]], jnp.int32),
                                jnp.asarray([n], jnp.int32))
            for name in ("k", "v"):
                a = kvc.gather_pages(pool_ref, 0, name, st)
                b = kvc.gather_pages(pools[0]["kv"], 0, name, st)
                np.testing.assert_allclose(np.asarray(b)[0, :n],
                                           np.asarray(a)[0, :n],
                                           rtol=5e-2, atol=5e-2)
            lr, ls = np.asarray(logits_ref[0]), np.asarray(logits[0])
            tol = 0.08 if fmt else 1e-3
            assert np.abs(lr - ls).max() / (np.abs(lr).max() + 1e-9) < tol

    def test_mla_stream_matches_splice(self):
        """The MLA absorbed chunk path: streamed latent pages match the
        materialized-prefill splice, and final-chunk logits agree."""
        from repro.configs import get_smoke

        cfg = get_smoke("minicpm3-4b")
        params = models.init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(2)
        prompt = rng.integers(1, cfg.vocab_size, size=13).tolist()
        page, n = 8, 13
        for fmt in (None, "fp8_e4m3"):
            logits_ref, caches = models.prefill(
                params, cfg, {"tokens": jnp.asarray([prompt], jnp.int32)}, 16)
            pool_ref = kvc.init_mla_pool(cfg.n_layers, 4, page,
                                         cfg.mla.kv_lora_rank,
                                         cfg.mla.qk_rope_dim, fmt)
            pool_ref = kvc.splice_prefill(pool_ref, caches[0]["kv"],
                                          np.array([0, 1]), n)
            pools = [{"kv": kvc.init_mla_pool(cfg.n_layers, 4, page,
                                              cfg.mla.kv_lora_rank,
                                              cfg.mla.qk_rope_dim, fmt)}]
            pos, ids = 0, [0, 1]
            while pos < n:
                take = min(page, n - pos)
                w = kvc.pages_needed(pos + take, page)
                table = np.zeros((1, w), np.int32)
                table[0] = ids[:w]
                st = kvc.PagedState(jnp.asarray(table),
                                    jnp.asarray([pos], jnp.int32))
                logits, pools = models.decode_step(
                    params, cfg, jnp.asarray([prompt[pos: pos + take]], jnp.int32),
                    pools, st)
                pos += take
            st = kvc.PagedState(jnp.asarray([[0, 1]], jnp.int32),
                                jnp.asarray([n], jnp.int32))
            for name in ("ckv", "krope"):
                a = kvc.gather_pages(pool_ref, 0, name, st)
                b = kvc.gather_pages(pools[0]["kv"], 0, name, st)
                np.testing.assert_allclose(np.asarray(b)[0, :n],
                                           np.asarray(a)[0, :n],
                                           rtol=8e-2, atol=8e-2)
            lr, ls = np.asarray(logits_ref[0]), np.asarray(logits[0])
            tol = 0.12 if fmt else 3e-2  # absorbed-vs-materialized reorder
            assert np.abs(lr - ls).max() / (np.abs(lr).max() + 1e-9) < tol


class TestSpillBudget:
    @pytest.mark.parametrize("kv_fmt", [None, "fp8_e4m3"])
    def test_eviction_requeues_and_finishes_token_identical(self, trained_tiny,
                                                            kv_fmt):
        """ROADMAP (b): with a zero spill budget every preemption evicts —
        the spilled bytes are dropped and the request re-queues for a full
        context re-prefill — yet every request still finishes with the same
        greedy tokens as an uncontended solo run (no host OOM path left)."""
        cfg, params = trained_tiny
        rng = np.random.default_rng(11)
        prompts = [rng.integers(1, cfg.vocab_size, size=5).tolist()
                   for _ in range(2)]
        srv = Server(params, cfg,
                     ServerConfig(slots=2, max_seq=32, kv_fmt=kv_fmt,
                                  page_size=4, pool_pages=6, a_fmt=None,
                                  scheduler=SchedulerConfig(spill_budget_bytes=0)))
        reqs = [Request(rid=i, prompt=list(p), max_new=10)
                for i, p in enumerate(prompts)]
        for r in reqs:
            srv.submit(r)
        _drain_checked(srv)
        assert srv.stats["preemptions"] >= 1
        assert srv.stats["spill_evictions"] >= 1
        assert srv._spill_bytes == 0 and not srv.preempted
        assert any(r.evictions >= 1 for r in reqs)
        for r in reqs:
            solo = Server(params, cfg,
                          ServerConfig(slots=1, max_seq=32, kv_fmt=kv_fmt,
                                       page_size=4, a_fmt=None))
            ref = Request(rid=99, prompt=list(r.prompt), max_new=10)
            solo.submit(ref)
            solo.run_until_drained()
            assert r.out == ref.out, (r.rid, r.out, ref.out)

    def test_budget_keeps_newest_spills_resident(self, trained_tiny):
        """A budget large enough for one spill keeps the newest resident
        (oldest-first eviction) instead of dropping everything."""
        cfg, params = trained_tiny
        rng = np.random.default_rng(3)
        srv = Server(params, cfg,
                     ServerConfig(slots=2, max_seq=32, kv_fmt="fp8_e4m3",
                                  page_size=4, pool_pages=6, a_fmt=None,
                                  scheduler=SchedulerConfig(spill_budget_bytes=1 << 30)))
        reqs = [Request(rid=i, prompt=rng.integers(1, 64, 5).tolist(),
                        max_new=10) for i in range(2)]
        for r in reqs:
            srv.submit(r)
        _drain_checked(srv)
        assert srv.stats["preemptions"] >= 1
        assert srv.stats["spill_evictions"] == 0  # generous budget: no evicts
        assert srv.stats["resumes"] == srv.stats["preemptions"]


class TestPrefillBucketing:
    def test_trace_count_logarithmic(self, trained_tiny):
        """ROADMAP (a): a high-entropy prompt-length workload must compile
        O(log max_seq) prefill programs, not one per distinct length. The
        engine records each distinct (padded_chunk, table_width) signature
        it feeds the jitted step — with a fixed config that set IS the
        trace-cache key set."""
        cfg, params = trained_tiny
        rng = np.random.default_rng(0)
        srv = Server(params, cfg,
                     ServerConfig(slots=2, max_seq=64, kv_fmt="fp8_e4m3",
                                  page_size=4, a_fmt=None,
                                  scheduler=SchedulerConfig(prefill_chunk_pages=4)))
        lengths = list(range(3, 28))  # 25 distinct prompt lengths
        rng.shuffle(lengths)
        for i, n in enumerate(lengths):
            srv.submit(Request(rid=i, prompt=rng.integers(1, 64, n).tolist(),
                               max_new=2))
        done = srv.run_until_drained()
        assert len(done) == len(lengths)
        assert srv._bucket_prefill
        # pow2 chunk lengths x pow2 table widths: far below the 25 distinct
        # (chunk_len, width) pairs the unbucketed engine would compile
        assert len(srv.prefill_traces) <= 8, sorted(srv.prefill_traces)
        for padded, w in srv.prefill_traces:
            assert padded & (padded - 1) == 0, (padded, w)
            assert w & (w - 1) == 0, (padded, w)

    def test_bucketed_prefill_token_identical(self, trained_tiny):
        """Pad+mask must not change numerics: bucketed streaming prefill
        reproduces the legacy contiguous-cache greedy output exactly on
        bf16 pages for lengths exercising every pad path."""
        from test_kv_cache import _greedy_legacy

        cfg, params = trained_tiny
        rng = np.random.default_rng(9)
        for n in (1, 3, 8, 13, 17, 30):
            prompt = rng.integers(1, cfg.vocab_size, size=n).tolist()
            srv = Server(params, cfg,
                         ServerConfig(slots=1, max_seq=64, kv_fmt=None,
                                      page_size=4, a_fmt=None,
                                      scheduler=SchedulerConfig(prefill_chunk_pages=2)))
            r = Request(rid=0, prompt=list(prompt), max_new=5)
            srv.submit(r)
            srv.run_until_drained()
            assert r.out == _greedy_legacy(params, cfg, prompt, 5), n


class TestStateSlabs:
    def test_slab_fuzz_steal_resume_bit_identity(self):
        """Seeded fuzz on a slab-starved xLSTM pool (3 slots, 2 slabs):
        priority arrivals force slab steals; every spill/resume restores
        the recurrent state bit-exactly, so each request's output equals
        its uncontended solo run — even at random init, where any
        numerical drift would flip tokens."""
        from repro.configs import get_smoke

        cfg = get_smoke("xlstm-125m")
        params = models.init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(13)
        srv = Server(params, cfg,
                     ServerConfig(slots=3, max_seq=32, a_fmt=None,
                                  pool_slabs=2, page_size=4,
                                  scheduler=SchedulerConfig(prefill_chunk_pages=1,
                                                            steal_cooldown=1)))
        # recurrent state cannot skip prefill chunks: no prefix cache
        assert srv._prefix is None
        reqs = [Request(rid=i,
                        prompt=rng.integers(1, cfg.vocab_size,
                                            rng.choice([3, 5, 9])).tolist(),
                        max_new=int(rng.choice([2, 5, 8])),
                        priority=int(i % 3))
                for i in range(9)]
        pending = list(reqs)
        for _ in range(2):
            srv.submit(pending.pop(0))
        for step in range(500):
            went = srv.step()
            # slab accounting invariants: owned + free partition the pool
            owned = [s for s in srv.slot_slab if s >= 0]
            assert len(owned) == len(set(owned))
            assert sorted(owned + srv.free_slabs) == list(range(srv._n_slabs))
            if pending and step % 2 == 0:
                srv.submit(pending.pop(0))
            if (not went and not pending and not srv.queue
                    and not srv.preempted):
                break
        assert len(srv.finished) == len(reqs)
        assert srv.stats["preemptions"] >= 1, "fuzz should exercise steals"
        assert sorted(srv.free_slabs) == list(range(srv._n_slabs))
        for r in reqs:
            solo = Server(params, cfg,
                          ServerConfig(slots=1, max_seq=32, a_fmt=None,
                                       page_size=4,
                                       scheduler=SchedulerConfig(prefill_chunk_pages=1)))
            ref = Request(rid=99, prompt=list(r.prompt), max_new=r.max_new)
            solo.submit(ref)
            solo.run_until_drained()
            assert r.out == ref.out, (r.rid, r.out, ref.out)

    def test_priority_slab_steal_under_zero_budget_loses_nothing(self):
        """Regression: a slab steal fires *mid-admission* (the arriving
        request outbids the runner), and with a zero spill budget the
        victim is immediately evicted into the queue. Budget enforcement
        must not run inside the preempt (it would mutate the queue under
        _admit_one's feet and pop the wrong request) — both requests must
        finish, token-identical to solo runs."""
        from repro.configs import get_smoke

        cfg = get_smoke("xlstm-125m")
        params = models.init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(1)
        srv = Server(params, cfg,
                     ServerConfig(slots=2, max_seq=32, a_fmt=None,
                                  pool_slabs=1, page_size=4,
                                  scheduler=SchedulerConfig(prefill_chunk_pages=1,
                                                            spill_budget_bytes=0,
                                                            steal_cooldown=0)))
        lo = Request(rid=0, prompt=rng.integers(1, 64, 5).tolist(),
                     max_new=8, priority=0)
        hi = Request(rid=1, prompt=rng.integers(1, 64, 5).tolist(),
                     max_new=4, priority=1)
        srv.submit(lo)
        srv.step()  # lo running on the only slab
        srv.submit(hi)  # outbids lo -> slab steal mid-admission + eviction
        srv.run_until_drained()
        assert lo.done and hi.done
        assert srv.stats["preemptions"] >= 1
        assert srv.stats["spill_evictions"] >= 1 and lo.evictions >= 1
        for r in (lo, hi):
            solo = Server(params, cfg,
                          ServerConfig(slots=1, max_seq=32, a_fmt=None,
                                       page_size=4,
                                       scheduler=SchedulerConfig(prefill_chunk_pages=1)))
            ref = Request(rid=99, prompt=list(r.prompt), max_new=r.max_new)
            solo.submit(ref)
            solo.run_until_drained()
            assert r.out == ref.out, (r.rid, r.out, ref.out)

    def test_reserve_scheduler_never_slab_steals(self):
        """Regression: reserve-on-admit's contract is that admitted work is
        never preempted — a slab-starved high-priority arrival must wait
        for retirement, not steal (the stolen victim could never resume:
        spill readmission is a token-budget mechanism)."""
        from repro.configs import get_smoke

        cfg = get_smoke("xlstm-125m")
        params = models.init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(5)
        srv = Server(params, cfg,
                     ServerConfig(slots=2, max_seq=32, a_fmt=None,
                                  pool_slabs=1, page_size=4,
                                  scheduler=SchedulerConfig(prefill_chunk_pages=1,
                                                            policy="reserve",
                                                            steal_cooldown=0)))
        lo = Request(rid=0, prompt=rng.integers(1, 64, 5).tolist(),
                     max_new=6, priority=0)
        hi = Request(rid=1, prompt=rng.integers(1, 64, 5).tolist(),
                     max_new=4, priority=1)
        srv.submit(lo)
        srv.step()
        srv.submit(hi)  # must wait for lo's slab, not steal it
        srv.run_until_drained()
        assert lo.done and hi.done
        assert srv.stats["preemptions"] == 0

    def test_xlstm_stream_matches_full_prefill(self):
        """Chunked streaming prefill carries the (c, n, m) + conv state
        across chunks exactly: the final-chunk logits argmax matches the
        one-shot legacy prefill (this is what the _mlstm_chunked carry fix
        makes true for T > chunk)."""
        from repro.configs import get_smoke

        cfg = get_smoke("xlstm-125m")
        params = models.init_params(cfg, jax.random.PRNGKey(1))
        rng = np.random.default_rng(2)
        prompt = rng.integers(1, cfg.vocab_size, size=13).tolist()
        logits_ref, _ = models.prefill(
            params, cfg, {"tokens": jnp.asarray([prompt], jnp.int32)}, 32)
        srv = Server(params, cfg,
                     ServerConfig(slots=1, max_seq=32, a_fmt=None, page_size=4,
                                  scheduler=SchedulerConfig(prefill_chunk_pages=1)))
        r = Request(rid=0, prompt=list(prompt), max_new=1)
        srv.submit(r)
        srv.run_until_drained()
        assert r.out[0] == int(jnp.argmax(logits_ref[0]))


class TestPrefixCacheServing:
    """Refcounted pages + the content-addressed shared-prefix cache: the
    acceptance scenario (shared system prompt -> zero prefill compute for
    the shared pages, token-identical output), refcount/parking lifecycle,
    and the resume fallback when cached pages were reclaimed."""

    @pytest.mark.parametrize("kv_fmt", [None, "fp8_e4m3"])
    def test_shared_prefix_token_identical_and_saves_prefill(
            self, trained_tiny, kv_fmt):
        """Acceptance: 8 requests sharing a 64-token system prompt produce
        greedy outputs token-identical to the cold-cache engine, while
        ``stats['prefill_tokens']`` drops by exactly the shared pages'
        token count (every request after the first maps all 8 pages)."""
        cfg, params = trained_tiny
        rng = np.random.default_rng(21)
        page = 8
        shared = rng.integers(1, cfg.vocab_size, size=64).tolist()
        prompts = [shared + rng.integers(1, cfg.vocab_size,
                                         size=int(t)).tolist()
                   for t in rng.integers(3, 7, size=8)]
        total = sum(len(p) for p in prompts)
        outs = {}
        for warm in (False, True):
            srv = Server(params, cfg,
                         ServerConfig(slots=4, max_seq=96, kv_fmt=kv_fmt,
                                      page_size=page, a_fmt=None,
                                      prefix_cache=warm))
            reqs = [Request(rid=i, prompt=list(p), max_new=6)
                    for i, p in enumerate(prompts)]
            for r in reqs:
                srv.submit(r)
            done = _drain_checked(srv)
            assert len(done) == len(reqs)
            outs[warm] = {r.rid: r.out for r in reqs}
            if warm:
                saved = 7 * 64  # everyone but the first hits all 8 pages
                assert srv.stats["prefix_hit_tokens"] == saved
                assert srv.stats["prefix_hit_pages"] == 7 * 8
                assert srv.stats["prefill_tokens"] == total - saved
                assert srv.prefix_hit_rate() > 0.7
            else:
                assert srv.stats["prefix_hit_tokens"] == 0
                assert srv.stats["prefill_tokens"] == total
        assert outs[False] == outs[True]

    def test_refcounts_and_parking_lifecycle(self, trained_tiny):
        """Two concurrent requests map the same physical prefix pages
        (refcount 2); retirement parks them at refcount 0 in the reusable
        LRU instead of the free list; a third request re-acquires them.

        Pinned to the alternating engine: the step-1 assertions require
        admission to prefill+register the first request before the second
        walks the prefix index; the mixed engine streams that prefill
        across steps (its refcount/parking coverage is the steal-happy
        identity fuzz in tests/test_mixed_engine.py)."""
        cfg, params = trained_tiny
        rng = np.random.default_rng(3)
        page = 8
        shared = rng.integers(1, cfg.vocab_size, size=2 * page).tolist()
        tail = rng.integers(1, cfg.vocab_size, size=3).tolist()
        mk = lambda rid: Request(rid=rid, prompt=shared + tail, max_new=3)
        srv = Server(params, cfg,
                     ServerConfig(slots=2, max_seq=64, kv_fmt="fp8_e4m3",
                                  page_size=page, a_fmt=None,
                                  scheduler=SchedulerConfig(
                                      engine="alternating")))
        a, b = mk(0), mk(1)
        srv.submit(a)
        srv.submit(b)
        srv.step()  # admits both: a prefills + registers, b maps the hits
        assert srv.slot_shared == [2, 2]
        assert srv.slot_pages[0][:2] == srv.slot_pages[1][:2]
        assert (srv.page_refs[srv.slot_pages[0][:2]] == 2).all()
        _assert_pool_invariants(srv)
        _drain_checked(srv)
        # retired: the prefix pages parked, not freed — still reusable
        assert len(srv.reusable_pages) == 2
        assert (srv.page_refs == 0).all()
        c = mk(2)
        srv.submit(c)
        _drain_checked(srv)
        assert srv.stats["prefix_hit_tokens"] == 2 * (2 * page)
        assert a.out == b.out == c.out

    def test_preempt_keeps_prefix_resident_and_resumes(self, trained_tiny):
        """Preemption spills only the private tail: the shared prefix
        pages stay in the index (parked if nobody else maps them) and are
        re-resolved on resume — the spill's host bytes exclude them."""
        cfg, params = trained_tiny
        rng = np.random.default_rng(5)
        prompt = rng.integers(1, cfg.vocab_size, size=9).tolist()
        srv = Server(params, cfg,
                     ServerConfig(slots=1, max_seq=32, kv_fmt="fp8_e4m3",
                                  page_size=4, a_fmt=None))
        r = Request(rid=0, prompt=list(prompt), max_new=8)
        srv.submit(r)
        srv.step()
        assert srv.slot_shared[0] == 2  # 8 of 9 prompt tokens registered
        srv._preempt(0)
        sp = srv.preempted[0]
        assert sp.shared_pages == 2
        assert len(srv.reusable_pages) == 2  # prefix parked, not spilled
        _assert_pool_invariants(srv)
        srv.run_until_drained()
        assert srv.stats["resumes"] == 1 and r.done
        solo = Server(params, cfg,
                      ServerConfig(slots=1, max_seq=32, kv_fmt="fp8_e4m3",
                                   page_size=4, a_fmt=None))
        ref = Request(rid=99, prompt=list(prompt), max_new=8)
        solo.submit(ref)
        solo.run_until_drained()
        assert r.out == ref.out

    def test_resume_falls_back_to_reprefill_after_reclaim(self, trained_tiny):
        """If a spill's shared prefix pages were reclaimed while it sat on
        host, resume cannot restore behind them: the engine falls back to
        an eviction-style tail re-prefill and still finishes
        token-identically."""
        cfg, params = trained_tiny
        rng = np.random.default_rng(7)
        prompt = rng.integers(1, cfg.vocab_size, size=9).tolist()
        srv = Server(params, cfg,
                     ServerConfig(slots=1, max_seq=32, kv_fmt="fp8_e4m3",
                                  page_size=4, a_fmt=None))
        r = Request(rid=0, prompt=list(prompt), max_new=8)
        srv.submit(r)
        srv.step()
        srv._preempt(0)
        # simulate pool pressure reclaiming the parked prefix while spilled
        while srv._prefix.n_reusable:
            srv.free_pages.append(srv._prefix.reclaim())
        _assert_pool_invariants(srv)
        srv.run_until_drained()
        assert srv.stats["resume_fallbacks"] == 1
        assert srv.stats["spill_evictions"] == 1 and r.evictions == 1
        assert r.done and len(r.out) == 8
        solo = Server(params, cfg,
                      ServerConfig(slots=1, max_seq=32, kv_fmt="fp8_e4m3",
                                   page_size=4, a_fmt=None))
        ref = Request(rid=99, prompt=list(prompt), max_new=8)
        solo.submit(ref)
        solo.run_until_drained()
        assert r.out == ref.out

    def test_admission_charges_parked_hits_against_free_pool(self,
                                                             trained_tiny):
        """Regression: a prefix hit sitting parked in the reusable LRU
        counts in ``_free_capacity()`` but is consumed by the very
        admission that maps it — the feasibility check must charge parked
        hits against the free pool, or ``_alloc`` runs the allocator dry
        mid-admission (assert crash) instead of deferring the request."""
        cfg, params = trained_tiny
        rng = np.random.default_rng(19)
        page = 4
        prompt_a = rng.integers(1, cfg.vocab_size, size=13).tolist()
        srv = Server(params, cfg,
                     ServerConfig(slots=2, max_seq=32, kv_fmt="fp8_e4m3",
                                  page_size=page, pool_pages=8, a_fmt=None))
        a = Request(rid=0, prompt=list(prompt_a), max_new=2)
        srv.submit(a)
        _drain_checked(srv)
        assert len(srv.reusable_pages) == 3  # A's full prompt pages parked
        # D fills the entire free list with private pages and keeps running
        d = Request(rid=1, prompt=rng.integers(1, 64, 13).tolist(),
                    max_new=18)
        srv.submit(d)
        srv.step()
        assert len(srv.free_pages) == 0 and len(srv.reusable_pages) == 3
        # E hits all 3 parked pages, but its private tail pages cannot be
        # allocated with free = 0: it must wait for D, not crash
        e = Request(rid=2, prompt=list(prompt_a), max_new=8)
        srv.submit(e)
        done = _drain_checked(srv)
        assert e in done and len(e.out) == 8 and d in done
        solo = Server(params, cfg,
                      ServerConfig(slots=1, max_seq=32, kv_fmt="fp8_e4m3",
                                   page_size=page, a_fmt=None))
        ref = Request(rid=99, prompt=list(prompt_a), max_new=8)
        solo.submit(ref)
        solo.run_until_drained()
        assert e.out == ref.out

    def test_mla_shared_prefix_token_identical(self, trained_tiny_mla):
        """The prefix cache is payload-agnostic: MLA latent pages (ckv +
        krope leaves under one page id) share across requests exactly like
        GQA K/V pages."""
        cfg, params = trained_tiny_mla
        rng = np.random.default_rng(8)
        page = 8
        shared = rng.integers(1, cfg.vocab_size, size=2 * page).tolist()
        prompts = [shared + rng.integers(1, cfg.vocab_size,
                                         size=t).tolist()
                   for t in (3, 5, 4)]
        outs = {}
        for warm in (False, True):
            srv = Server(params, cfg,
                         ServerConfig(slots=3, max_seq=64, kv_fmt="fp8_e4m3",
                                      page_size=page, a_fmt=None,
                                      prefix_cache=warm,
                                      scheduler=SchedulerConfig(prefill_chunk_pages=1)))
            reqs = [Request(rid=i, prompt=list(p), max_new=5)
                    for i, p in enumerate(prompts)]
            for r in reqs:
                srv.submit(r)
            done = _drain_checked(srv)
            assert len(done) == len(reqs)
            outs[warm] = {r.rid: r.out for r in reqs}
            if warm:
                assert srv.stats["prefix_hit_tokens"] == 2 * (2 * page)
        assert outs[False] == outs[True]

    @pytest.mark.parametrize("kv_fmt", [None, "fp8_e4m3"])
    def test_shared_prefix_fuzz_refcounted(self, trained_tiny, kv_fmt):
        """Satellite fuzz: staggered shared-prefix arrivals on a tight,
        steal-happy pool — every step preserves the refcount invariants
        (no leaked pages, no double-free, refcounts == table occupancy),
        every request finishes, and each output is token-identical to a
        cold-cache solo run."""
        cfg, params = trained_tiny
        rng = np.random.default_rng(17)
        page = 4
        shared = rng.integers(1, cfg.vocab_size, size=2 * page).tolist()
        srv = Server(params, cfg,
                     ServerConfig(slots=3, max_seq=32, kv_fmt=kv_fmt,
                                  page_size=page, pool_pages=8, a_fmt=None,
                                  scheduler=SchedulerConfig(prefill_chunk_pages=1,
                                                            headroom_pages=1,
                                                            steal_cooldown=1)))
        reqs = [Request(rid=i,
                        prompt=shared + rng.integers(
                            1, cfg.vocab_size, int(rng.choice([1, 3, 6]))
                        ).tolist(),
                        max_new=int(rng.choice([5, 9, 12])),
                        priority=int(rng.choice([0, 1])))
                for i in range(10)]
        pending = list(reqs)
        for _ in range(3):
            srv.submit(pending.pop(0))
        for step in range(600):
            went = srv.step()
            _assert_pool_invariants(srv)
            if pending and step % 3 == 0:
                srv.submit(pending.pop(0))
            if (not went and not pending and not srv.queue
                    and not srv.preempted):
                break
        assert len(srv.finished) == len(reqs)
        assert srv.stats["preemptions"] >= 1, "fuzz should exercise steals"
        assert srv.stats["prefix_hit_tokens"] > 0, "fuzz should share pages"
        assert sorted(srv.free_pages + srv.reusable_pages) == \
            list(range(srv._n_pages))
        assert (srv.page_refs == 0).all()
        for r in reqs:
            solo = Server(params, cfg,
                          ServerConfig(slots=1, max_seq=32, kv_fmt=kv_fmt,
                                       page_size=page, a_fmt=None,
                                       prefix_cache=False,
                                       scheduler=SchedulerConfig(prefill_chunk_pages=1)))
            ref = Request(rid=99, prompt=list(r.prompt), max_new=r.max_new)
            solo.submit(ref)
            solo.run_until_drained()
            assert r.out == ref.out, (r.rid, r.out, ref.out)


class TestWaitLineFairness:
    def test_evicted_spill_keeps_global_wait_order(self, trained_tiny):
        """Regression (satellite 1): budget eviction must not push the
        *oldest* waiter behind every younger spill. A is preempted before
        B; the budget evicts A (oldest-first) into the queue; readmission
        must still pick A first — one global (since, seq) wait line, not
        'preempted strictly before fresh'."""
        cfg, params = trained_tiny
        rng = np.random.default_rng(11)
        srv = Server(params, cfg,
                     ServerConfig(slots=2, max_seq=32, kv_fmt="fp8_e4m3",
                                  page_size=4, pool_pages=12, a_fmt=None))
        a = Request(rid=0, prompt=rng.integers(1, 64, 5).tolist(), max_new=10)
        b = Request(rid=1, prompt=rng.integers(1, 64, 5).tolist(), max_new=10)
        srv.submit(a)
        srv.submit(b)
        srv.step()  # both active
        srv._preempt(srv.active.index(a))  # A spilled first (older key)
        srv._step_no += 1  # a step passes without readmitting A ...
        srv._preempt(srv.active.index(b))  # ... then B is spilled too
        assert a.since < b.since
        # budget fits exactly one spill: the oldest (A) is evicted
        srv.spill_budget_bytes = max(sp.nbytes for sp in srv.preempted)
        srv._enforce_spill_budget()
        assert a.evictions == 1 and a in srv.queue
        assert [sp.req for sp in srv.preempted] == [b]
        # readmission picks A (evicted but oldest), not the younger spill
        assert srv._admit_one(0)
        assert srv.active[0] is a
        srv.run_until_drained()
        for r in (a, b):
            solo = Server(params, cfg,
                          ServerConfig(slots=1, max_seq=32, kv_fmt="fp8_e4m3",
                                       page_size=4, a_fmt=None))
            ref = Request(rid=99, prompt=list(r.prompt), max_new=10)
            solo.submit(ref)
            solo.run_until_drained()
            assert r.out == ref.out, (r.rid, r.out, ref.out)


class TestDeadlineVictim:
    def test_deadline_shields_tight_slo(self, trained_tiny):
        """ROADMAP (c): within a priority class the victim is the request
        with the *most* deadline slack. The older no-deadline request —
        which the old newest-first tie-break would have protected — yields
        to the newer request racing a tight deadline."""
        cfg, params = trained_tiny
        rng = np.random.default_rng(13)
        srv = Server(params, cfg,
                     ServerConfig(slots=2, max_seq=32, kv_fmt="fp8_e4m3",
                                  page_size=4, pool_pages=6, a_fmt=None,
                                  scheduler=SchedulerConfig(steal_cooldown=0)))
        loose = Request(rid=0, prompt=rng.integers(1, 64, 5).tolist(),
                        max_new=10)  # no deadline: infinite slack
        tight = Request(rid=1, prompt=rng.integers(1, 64, 5).tolist(),
                        max_new=10, deadline_step=14)
        srv.submit(loose)
        srv.submit(tight)
        _drain_checked(srv)
        assert srv.stats["preemptions"] >= 1
        assert tight.preemptions == 0, "tight-SLO request must be shielded"
        assert loose.preemptions >= 1

    def test_pick_victim_orders_by_slack_then_age(self, trained_tiny):
        cfg, params = trained_tiny
        rng = np.random.default_rng(2)
        srv = Server(params, cfg,
                     ServerConfig(slots=2, max_seq=32, kv_fmt="fp8_e4m3",
                                  page_size=4, a_fmt=None,
                                  scheduler=SchedulerConfig(steal_cooldown=0)))
        r0 = Request(rid=0, prompt=rng.integers(1, 64, 3).tolist(),
                     max_new=8, deadline_step=100)  # plenty of slack
        r1 = Request(rid=1, prompt=rng.integers(1, 64, 3).tolist(),
                     max_new=8, deadline_step=10)  # about to miss
        srv.submit(r0)
        srv.submit(r1)
        srv.step()
        victim = srv._pick_victim()
        assert srv.active[victim] is r0
        # priority stays the primary key: a lower-priority tight request
        # still yields before a higher-priority slack-rich one
        r0.priority, r1.priority = 1, 0
        assert srv.active[srv._pick_victim()] is r1
        # a deadline already missed stops shielding: the dead-SLO request
        # yields before a peer whose deadline is still meetable
        r0.priority = 0
        r0.deadline_step, r1.deadline_step = 100, 1  # r1's SLO is lost
        assert srv._slack(r1) == float("inf")
        assert srv.active[srv._pick_victim()] is r1


class TestTruncation:
    def test_max_seq_boundary_sets_truncated(self, trained_tiny):
        """Satellite: a request cut off at the max_seq - 1 context bound
        retires with fewer than max_new tokens and must say so."""
        cfg, params = trained_tiny
        rng = np.random.default_rng(4)
        srv = Server(params, cfg,
                     ServerConfig(slots=1, max_seq=16, kv_fmt=None,
                                  page_size=4, a_fmt=None))
        r = Request(rid=0, prompt=rng.integers(1, 64, 5).tolist(), max_new=50)
        srv.submit(r)
        srv.run_until_drained()
        assert r.done and r.truncated
        assert len(r.out) == (16 - 1) - 5 + 1  # context bound, not budget
        assert srv.stats["truncated"] == 1
        ok = Request(rid=1, prompt=rng.integers(1, 64, 3).tolist(), max_new=4)
        srv.submit(ok)
        srv.run_until_drained()
        assert ok.done and not ok.truncated and len(ok.out) == 4
        assert srv.stats["truncated"] == 1


class TestPrefillTableContract:
    def test_overhang_pages_nulled(self, trained_tiny):
        """Satellite: a bucketed chunk's zeroed pad writes overhang the
        last data page; ``append_prefill_chunk``'s contract is that those
        table positions point at the *null page* — never at allocated
        headroom (a correctness hazard once pages are shared read-only).

        Pinned to the alternating engine: the spy reads the serial chunk
        loop's ``state.page_table``; the mixed step nests the same
        _chunk_plan table under ``state.prefill`` (covered by
        tests/test_mixed_engine.py)."""
        cfg, params = trained_tiny
        rng = np.random.default_rng(6)
        srv = Server(params, cfg,
                     ServerConfig(slots=1, max_seq=64, kv_fmt="fp8_e4m3",
                                  page_size=4, a_fmt=None,
                                  scheduler=SchedulerConfig(
                                      prefill_chunk_pages=4,
                                      engine="alternating")))
        tables = []
        orig = srv._decode

        def spy(params, pools, toks, state, poison, samp, **kw):
            tables.append(np.asarray(state.page_table))
            return orig(params, pools, toks, state, poison, samp, **kw)

        srv._decode = spy
        r = Request(rid=0, prompt=rng.integers(1, 64, 9).tolist(), max_new=2)
        srv.submit(r)
        srv.run_until_drained()
        # chunk: take=9 padded to 16 -> table width 4, but only 3 pages
        # hold data; the pad-overhang fourth slot must be the null page
        # (the old table mapped the allocated headroom page there)
        pre = tables[0]
        assert pre.shape[1] == 4
        assert pre[0, 3] == srv._null_page
        assert (pre[0, :3] != srv._null_page).all()
        assert len(srv.slot_pages[0]) == 0 and r.done  # sanity: retired


class TestSchedulerPolicy:
    def test_low_watermark_defers_fresh_admission(self):
        """With active work running, fresh admission must leave
        ``low_watermark`` pages free (growth slack) — the second request
        waits even though its charge would physically fit."""
        cfg = tiny_lm_cfg()
        params = models.init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        srv = Server(params, cfg,
                     ServerConfig(slots=2, max_seq=32, kv_fmt="fp8_e4m3",
                                  page_size=4, pool_pages=4, a_fmt=None,
                                  scheduler=SchedulerConfig(headroom_pages=1, low_watermark=2)))
        a = Request(rid=0, prompt=rng.integers(1, 64, 3).tolist(), max_new=3)
        b = Request(rid=1, prompt=rng.integers(1, 64, 3).tolist(), max_new=3)
        srv.submit(a)
        srv.submit(b)
        srv.step()  # admits a (pool idle: watermark bypassed), defers b
        assert srv.active.count(None) == 1 and b in srv.queue
        _drain_checked(srv)
        assert a.done and b.done

    def test_overlong_prompt_fails_fast(self):
        """A prompt with no decode room left must be rejected at submit,
        not crash mid-prefill after pages were already allocated."""
        cfg = tiny_lm_cfg()
        params = models.init_params(cfg, jax.random.PRNGKey(0))
        srv = Server(params, cfg,
                     ServerConfig(slots=1, max_seq=32, kv_fmt="fp8_e4m3",
                                  page_size=4, a_fmt=None))
        with pytest.raises(ValueError, match="max_seq"):
            srv.submit(Request(rid=0, prompt=list(range(1, 41)), max_new=4))

    def test_starvation_guard_raises(self):
        """If the pool is fully stolen and nothing can ever be readmitted,
        run_until_drained raises a clear error instead of spinning (or
        silently dropping preempted-but-never-resumed requests)."""
        cfg = tiny_lm_cfg()
        params = models.init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        srv = Server(params, cfg,
                     ServerConfig(slots=1, max_seq=32, kv_fmt="fp8_e4m3",
                                  page_size=4, pool_pages=4, a_fmt=None))
        r = Request(rid=0, prompt=rng.integers(1, 64, 5).tolist(), max_new=8)
        srv.submit(r)
        srv.step()
        srv._preempt(0)  # steal the only runner's pages ...
        srv.free_pages.clear()  # ... and simulate the pool never recovering
        with pytest.raises(RuntimeError, match="starved"):
            srv.run_until_drained()

    def test_token_budget_beats_reserve_under_long_tail(self, trained_tiny):
        """The acceptance claim at test scale: under a long-tail max_new
        workload on a tight pool, token-budget admission achieves strictly
        higher slot utilization (and fewer engine steps for the same
        tokens) than reserve-on-admit, with identical greedy outputs."""
        cfg, params = trained_tiny
        rng = np.random.default_rng(17)
        prompts = [rng.integers(1, cfg.vocab_size, size=int(m)).tolist()
                   for m in rng.integers(3, 8, size=8)]
        outs, stats = {}, {}
        for sched in ("reserve", "token_budget"):
            srv = Server(params, cfg,
                         ServerConfig(slots=4, max_seq=48, kv_fmt="fp8_e4m3",
                                      page_size=4, pool_pages=12, a_fmt=None,
                                      scheduler=SchedulerConfig(policy=sched)))
            reqs = [Request(rid=i, prompt=list(p),
                            max_new=24 if i % 4 == 0 else 4)
                    for i, p in enumerate(prompts)]
            for r in reqs:
                srv.submit(r)
            done = srv.run_until_drained()
            assert len(done) == len(reqs)
            outs[sched] = {r.rid: r.out for r in reqs}
            stats[sched] = (srv.utilization(), srv.stats["steps"])
        assert outs["reserve"] == outs["token_budget"]
        (u_rv, steps_rv), (u_tb, steps_tb) = stats["reserve"], stats["token_budget"]
        assert u_tb > u_rv, (u_tb, u_rv)
        assert steps_tb <= steps_rv, (steps_tb, steps_rv)
