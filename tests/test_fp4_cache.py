"""Mixed-precision KV cache: packed FP4 frozen pages behind a CachePolicy.

Covers: the CachePolicy validation surface and the ``kv_fmt`` deprecation
shim (warn on legacy, TypeError on both, token-identity of the shim path),
freeze-point transcode roundtrips (FP8 page -> packed FP4 frozen row ->
dual-region gather), the FP4 tolerance tier of the decode kernels
(kernel == oracle bit-parity in interpret mode, both vs the exact
unquantized softmax across a (heads, head_dim, page, seq) sweep, GQA and
MLA), the no-write-path-targets-FP4 invariants (append assert, pool
constructor validation, ``assert_unfrozen`` frozen-base extension), and
the served end-to-end path: a warm shared-prefix workload under
``frozen_fmt='fp4_e2m1'`` stays within bounded greedy-token divergence of
the all-FP8 run while frozen residency lands at about half the
bytes-per-token, and a steal-happy policy-transition fuzz
(freeze -> transcode -> park -> reclaim -> steal) holds ``Server.audit()``
clean at every step with spill/resume of mixed-format tables
token-identical to uncontended runs."""
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.kernels.common import PAGE_FORMAT_NAMES, page_format
from repro.runtime import kv_cache as kvc
from repro.runtime.serve import (CachePolicy, Request, SchedulerConfig,
                                 Server, ServerConfig)

# FP4 E2M1 has 1 mantissa bit on a 8-point positive grid: per-page M2
# scales leave ~2-4x the FP8 quantization error through a softmax.
FP4_TOL = 0.35
FP8_TOL = 0.12


class TestCachePolicy:
    def test_defaults_are_homogeneous_bf16(self):
        p = CachePolicy()
        assert not p.mixed
        assert p.active.fmt is None and p.frozen.fmt is None

    def test_frozen_inherits_active(self):
        p = CachePolicy(active_fmt="fp8_e4m3")
        assert not p.mixed
        assert p.frozen.name == "fp8_e4m3" and p.cross.name == "fp8_e4m3"

    def test_mixed_pair(self):
        p = CachePolicy(active_fmt="fp8_e4m3", frozen_fmt="fp4_e2m1")
        assert p.mixed
        assert p.frozen.packed and p.frozen.bytes_per_code == 0.5

    def test_active_must_be_writable(self):
        with pytest.raises(ValueError, match="writable"):
            CachePolicy(active_fmt="fp4_e2m1")

    def test_only_supported_transcode_pair(self):
        with pytest.raises(ValueError, match="transcode"):
            CachePolicy(active_fmt=None, frozen_fmt="fp8_e4m3")

    def test_cross_fp4_needs_quantized_engine(self):
        with pytest.raises(ValueError, match="cross_fmt"):
            CachePolicy(cross_fmt="fp4_e2m1")

    def test_unknown_format_fails_fast_with_allowed_set(self):
        with pytest.raises(ValueError) as ei:
            CachePolicy(active_fmt="fp8_e4m3", frozen_fmt="fp3_e1m1")
        msg = str(ei.value)
        for name in PAGE_FORMAT_NAMES:
            assert name in msg, msg

    def test_frozen_pages_floor(self):
        with pytest.raises(ValueError, match="frozen_pages"):
            CachePolicy(active_fmt="fp8_e4m3", frozen_fmt="fp4_e2m1",
                        frozen_pages=0)


class TestKvFmtShim:
    def test_legacy_kv_fmt_warns_and_normalizes(self):
        with pytest.warns(DeprecationWarning, match="kv_fmt"):
            legacy = ServerConfig(kv_fmt="fp8_e4m3")
        assert legacy == ServerConfig(cache=CachePolicy(active_fmt="fp8_e4m3"))
        assert legacy.kv_fmt is None  # normalized into the policy

    def test_both_is_a_type_error(self):
        with pytest.raises(TypeError, match="not both"):
            ServerConfig(kv_fmt="fp8_e4m3",
                         cache=CachePolicy(active_fmt="fp8_e4m3"))

    def test_cache_alone_does_not_warn(self):
        with warnings.catch_warnings():
            warnings.simplefilter("error", DeprecationWarning)
            ServerConfig(cache=CachePolicy(active_fmt="fp8_e4m3"))

    def test_shim_token_identical_to_policy(self, trained_tiny):
        cfg, params = trained_tiny
        prompts = [[3, 7, 11, 2, 9], [5, 5, 1]]

        def serve(sc):
            srv = Server(params, cfg, sc)
            for i, p in enumerate(prompts):
                srv.submit(Request(rid=i, prompt=list(p), max_new=6))
            return [list(r.tokens) for r in srv.run_until_drained()]

        with pytest.warns(DeprecationWarning):
            legacy = serve(ServerConfig(slots=2, max_seq=64, page_size=8,
                                        kv_fmt="fp8_e4m3", a_fmt=None))
        modern = serve(ServerConfig(
            slots=2, max_seq=64, page_size=8, a_fmt=None,
            cache=CachePolicy(active_fmt="fp8_e4m3")))
        assert legacy == modern

    def test_legacy_flat_kwargs_route_through_policy(self, trained_tiny):
        cfg, params = trained_tiny
        with pytest.warns(DeprecationWarning):
            srv = Server(params, cfg, slots=2, max_seq=64,
                         kv_fmt="fp8_e4m3", a_fmt=None)
        assert srv.policy == CachePolicy(active_fmt="fp8_e4m3")
        assert srv.kv_fmt == "fp8_e4m3"  # read-side alias survives


def _mixed_gqa_layer(rng, kv, hd, page, pp, lens, freeze):
    """A 1-layer mixed pool: FP8 splice, then the first ``freeze`` pages of
    each row transcoded into the packed FP4 frozen region with the table
    rewritten to frozen logical ids (base = P+1)."""
    b = len(lens)
    n_pages = b * pp
    pool = kvc.init_gqa_pool(1, n_pages, page, kv, hd, "fp8_e4m3",
                             frozen_fmt="fp4_e2m1", n_frozen=n_pages)
    pt = np.zeros((b, pp), np.int32)
    kc = rng.normal(size=(b, 1, 1, pp * page, kv, hd)).astype(np.float32)
    vc = rng.normal(size=(b, 1, 1, pp * page, kv, hd)).astype(np.float32)
    base = n_pages + 1
    fidx = 0
    for r in range(b):
        npg = kvc.pages_needed(int(lens[r]), page)
        ids = np.arange(r * pp, r * pp + npg, dtype=np.int32)
        pt[r, :npg] = ids
        pool = kvc.splice_prefill(
            pool, {"k": jnp.asarray(kc[r]), "v": jnp.asarray(vc[r])}, ids,
            int(lens[r]))
        for i in range(min(freeze, npg)):
            pool = kvc.transcode_page(pool, int(ids[i]), fidx)
            pt[r, i] = base + fidx
            fidx += 1
    return pool, pt, kc[:, 0, 0], vc[:, 0, 0]


def _attn_exact(q, k, v, kv_len, g):
    h, hd = q.shape
    o = np.zeros((h, v.shape[-1]), np.float32)
    for hi in range(h):
        sc = q[hi] @ k[:kv_len, hi // g].T / np.sqrt(hd)
        p = np.exp(sc - sc.max())
        p /= p.sum()
        o[hi] = p @ v[:kv_len, hi // g]
    return o


class TestTranscode:
    def test_roundtrip_within_fp4_grid_error(self):
        rng = np.random.default_rng(0)
        lens = np.array([24, 9], np.int32)
        pool, pt, kc, _ = _mixed_gqa_layer(rng, 2, 16, 8, 3, lens, freeze=2)
        state = kvc.PagedState(jnp.asarray(pt), jnp.asarray(lens))
        got = np.asarray(kvc.gather_pages(pool, 0, "k", state))
        for r, n in enumerate(lens):
            ref = kc[r, :n]
            err = np.abs(got[r, :n] - ref).max() / np.abs(ref).max()
            assert err < FP4_TOL, (r, err)

    def test_frozen_store_is_half_width(self):
        pool = kvc.init_gqa_pool(2, 8, 8, 2, 16, "fp8_e4m3",
                                 frozen_fmt="fp4_e2m1", n_frozen=4)
        assert pool["k"].shape[-1] == 16
        assert pool["k_fz"].shape[-1] == 8
        assert pool["k_fz"].shape[1] == 5  # n_frozen + clamped-gather dummy

    def test_odd_head_dim_packs_with_pad_nibble(self):
        rng = np.random.default_rng(1)
        lens = np.array([10], np.int32)
        pool, pt, kc, _ = _mixed_gqa_layer(rng, 2, 9, 8, 2, lens, freeze=1)
        assert pool["k_fz"].shape[-1] == 5  # ceil(9 / 2)
        state = kvc.PagedState(jnp.asarray(pt), jnp.asarray(lens))
        got = np.asarray(kvc.gather_pages(pool, 0, "k", state))[0, :10]
        err = np.abs(got - kc[0, :10]).max() / np.abs(kc[0, :10]).max()
        assert err < FP4_TOL, err

    def test_mixed_pool_page_bytes_ratio(self):
        # the bench-gated density ratio: a frozen page must cost <= 0.55x
        # an active FP8 page across the stacked layers
        pool = kvc.init_gqa_pool(4, 32, 8, 2, 64, "fp8_e4m3",
                                 frozen_fmt="fp4_e2m1", n_frozen=16)
        ratio = kvc.page_bytes(pool, frozen=True) / kvc.page_bytes(pool)
        assert ratio <= 0.55, ratio
        # active-class accounting must not be polluted by the frozen store
        plain = kvc.init_gqa_pool(4, 32, 8, 2, 64, "fp8_e4m3")
        assert kvc.pool_bytes_per_token(pool) == \
            kvc.pool_bytes_per_token(plain)


class TestNoWritePathTargetsFP4:
    def test_append_asserts_on_packed_pages(self):
        pool = kvc.init_gqa_pool(1, 4, 8, 2, 16, "fp4_e2m1")
        state = kvc.PagedState(jnp.asarray([[0, 1]], jnp.int32),
                               jnp.asarray([3], jnp.int32))
        new = {"k": jnp.ones((1, 1, 2, 16)), "v": jnp.ones((1, 1, 2, 16))}
        with pytest.raises(AssertionError, match="packed FP4"):
            kvc.append_paged(pool, 0, new, state)

    def test_mixed_pool_requires_fp8_active(self):
        with pytest.raises(ValueError, match="fp4_e2m1"):
            kvc.init_gqa_pool(1, 4, 8, 2, 16, None,
                              frozen_fmt="fp4_e2m1", n_frozen=2)

    def test_assert_unfrozen_rejects_frozen_region_ids(self):
        c = kvc.PrefixCache(page_size=8)
        c.insert([1] * 8, [3])
        c.assert_unfrozen([0, 1, 2])  # private active pages pass
        with pytest.raises(AssertionError):
            c.assert_unfrozen([3])  # registered
        with pytest.raises(AssertionError, match="frozen"):
            # any id at/above the frozen base is read-only by construction,
            # registered or not — a write plan holding one is corruption
            c.assert_unfrozen([17], frozen_base=17)
        c.assert_unfrozen([16], frozen_base=17)


class TestFP4DecodeParity:
    @pytest.mark.parametrize("kv,g,hd,page,pp", [
        (2, 2, 16, 8, 3),   # GQA smoke shape
        (1, 4, 32, 16, 2),  # MQA-ish, bigger head
        (4, 1, 8, 4, 4),    # MHA, many small pages
        (2, 3, 64, 32, 2),  # odd group size (padding path)
    ])
    def test_gqa_kernel_matches_oracle_mixed(self, kv, g, hd, page, pp):
        """Mixed-format tables (frozen FP4 prefix + FP8 tail): the pallas
        kernel (interpret mode) bit-matches the jnp oracle, and both stay
        within the FP4 tolerance tier of the exact unquantized softmax."""
        rng = np.random.default_rng(hash((kv, g, hd, page)) % 2**31)
        h = kv * g
        lens = np.array([page * pp - 3, max(1, page // 2)], np.int32)
        q = jnp.asarray(rng.normal(size=(2, h, hd)).astype(np.float32))
        pool, pt, kc, vc = _mixed_gqa_layer(rng, kv, hd, page, pp, lens,
                                             freeze=pp - 1)
        assert (pt >= pt.shape[0] * pp + 1).any()  # frozen ids in play
        prev = ops.get_backend()
        try:
            ops.set_backend("ref")
            o_ref = ops.paged_decode_attn(q, pool, 0, jnp.asarray(pt),
                                          jnp.asarray(lens))
            ops.set_backend("pallas_interpret")
            o_pal = ops.paged_decode_attn(q, pool, 0, jnp.asarray(pt),
                                          jnp.asarray(lens))
        finally:
            ops.set_backend(prev)
        np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_ref),
                                   rtol=2e-5, atol=2e-5)
        for r in range(2):
            exact = _attn_exact(np.asarray(q[r]), kc[r], vc[r],
                                int(lens[r]), g)
            err = np.abs(np.asarray(o_ref[r]) - exact).max()
            assert err / (np.abs(exact).max() + 1e-9) < FP4_TOL, (r, err)

    @pytest.mark.parametrize("h,r,dr,page,pp", [
        (4, 16, 8, 8, 3),
        (8, 32, 16, 16, 2),
        (3, 16, 8, 4, 4),   # odd head count (bq padding path)
    ])
    def test_mla_kernel_matches_oracle_mixed(self, h, r, dr, page, pp):
        rng = np.random.default_rng(hash((h, r, dr, page)) % 2**31)
        b = 2
        lens = np.array([page * pp - 3, max(1, page // 2)], np.int32)
        pool = kvc.init_mla_pool(1, b * pp, page, r, dr, "fp8_e4m3",
                                 frozen_fmt="fp4_e2m1", n_frozen=b * pp)
        pt = np.zeros((b, pp), np.int32)
        ck = rng.normal(size=(b, 1, 1, pp * page, r)).astype(np.float32)
        kr = rng.normal(size=(b, 1, 1, pp * page, dr)).astype(np.float32)
        base, fidx = b * pp + 1, 0
        for row in range(b):
            npg = kvc.pages_needed(int(lens[row]), page)
            ids = np.arange(row * pp, row * pp + npg, dtype=np.int32)
            pt[row, :npg] = ids
            pool = kvc.splice_prefill(
                pool, {"ckv": jnp.asarray(ck[row]),
                       "krope": jnp.asarray(kr[row])}, ids, int(lens[row]))
            for i in range(min(pp - 1, npg)):
                pool = kvc.transcode_page(pool, int(ids[i]), fidx)
                pt[row, i] = base + fidx
                fidx += 1
        ql = jnp.asarray(rng.normal(size=(b, h, r)).astype(np.float32))
        qr = jnp.asarray(rng.normal(size=(b, h, dr)).astype(np.float32))
        scale = 1.0 / float(r + dr) ** 0.5
        prev = ops.get_backend()
        try:
            ops.set_backend("ref")
            o_ref = ops.paged_mla_decode_attn(
                ql, qr, pool, 0, jnp.asarray(pt), jnp.asarray(lens), scale)
            ops.set_backend("pallas_interpret")
            o_pal = ops.paged_mla_decode_attn(
                ql, qr, pool, 0, jnp.asarray(pt), jnp.asarray(lens), scale)
        finally:
            ops.set_backend(prev)
        np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_ref),
                                   rtol=2e-5, atol=2e-5)
        for row in range(b):
            n = int(lens[row])
            s = (np.asarray(ql[row]) @ ck[row, 0, 0, :n].T
                 + np.asarray(qr[row]) @ kr[row, 0, 0, :n].T) * scale
            p = np.exp(s - s.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            exact = p @ ck[row, 0, 0, :n]
            err = np.abs(np.asarray(o_ref[row]) - exact).max()
            assert err / (np.abs(exact).max() + 1e-9) < FP4_TOL, (row, err)


MIXED = CachePolicy(active_fmt="fp8_e4m3", frozen_fmt="fp4_e2m1")


def _shared_prompts(cfg, n=4, prefix_tokens=24, seed=0):
    rng = np.random.default_rng(seed)
    shared = rng.integers(1, cfg.vocab_size, size=prefix_tokens).tolist()
    return [shared + rng.integers(1, cfg.vocab_size,
                                  size=3 + i).tolist() for i in range(n)]


def _serve_policy(params, cfg, policy, prompts, max_new=8, **kw):
    srv = Server(params, cfg,
                 ServerConfig(slots=3, max_seq=64, page_size=8, a_fmt=None,
                              cache=policy, audit_every=1, **kw))
    for i, p in enumerate(prompts):
        srv.submit(Request(rid=i, prompt=list(p), max_new=max_new))
    done = srv.run_until_drained()
    srv.audit()
    return {r.rid: list(r.tokens) for r in done}, srv


class TestMixedServer:
    def test_warm_prefix_fp4_bounded_divergence(self, trained_tiny):
        """The acceptance workload: a warm shared-prefix batch under
        frozen_fmt='fp4_e2m1' vs the same batch all-FP8. Only the frozen
        prefix pages differ in precision, so greedy streams must stay
        within a bounded divergence — and the frozen residency must land
        at about half the bytes-per-token."""
        cfg, params = trained_tiny
        prompts = _shared_prompts(cfg)
        out8, _ = _serve_policy(params, cfg,
                                CachePolicy(active_fmt="fp8_e4m3"), prompts)
        out4, srv = _serve_policy(params, cfg, MIXED, prompts)
        assert srv.stats["fp4_frozen_pages"] >= 3
        assert srv.stats["prefix_hit_pages"] > 0
        total = agree = 0
        for rid in out8:
            for a, b in zip(out8[rid], out4[rid]):
                total += 1
                agree += a == b
        # bounded divergence: FP4 prefix attention may flip a near-tie,
        # but the bulk of both greedy streams must match position-wise
        assert agree / total >= 0.5, (agree, total, out8, out4)
        resid = srv.cache_residency()
        assert resid["n_frozen_live"] >= 3
        ratio = (resid["frozen_bytes_per_token"]
                 / resid["active_bytes_per_token"])
        assert ratio <= 0.55, ratio

    def test_audit_summary_reports_frozen_classes(self, trained_tiny):
        cfg, params = trained_tiny
        _, srv = _serve_policy(params, cfg, MIXED, _shared_prompts(cfg))
        summary = srv.audit()
        assert summary["frozen_mapped"] + summary["frozen_free"] + \
            summary["pages_parked"] == srv._n_frozen

    def test_fuzz_policy_transitions_steal_happy(self, trained_tiny):
        """freeze -> transcode -> park -> reclaim -> steal under a pool too
        small for the workload, auditing every decode step. Three waves
        with two distinct prefixes force parks (wave drain), unparks
        (warm wave), reclaims (prefix rotation on a full frozen region)
        and page-steal preempt/resume of slots holding mixed tables.

        Pinned to the alternating engine: the reclaim assertion depends on
        its wave timing (the first prefix's pages must hit refcount 0
        before the second registers, so registration rotates the full
        frozen region). The mixed engine overlaps those lifecycles — its
        fp4 transition coverage lives in test_steal_resume_token_identity_
        mixed and tests/test_mixed_engine.py."""
        cfg, params = trained_tiny
        rng = np.random.default_rng(11)
        prefixes = [rng.integers(1, cfg.vocab_size, size=24).tolist()
                    for _ in range(2)]
        srv = Server(params, cfg, ServerConfig(
            slots=3, max_seq=64, page_size=8, a_fmt=None, pool_pages=7,
            cache=CachePolicy(active_fmt="fp8_e4m3", frozen_fmt="fp4_e2m1",
                              frozen_pages=4),
            audit_every=1,
            scheduler=SchedulerConfig(headroom_pages=1, steal_cooldown=1,
                                      engine="alternating")))
        reqs = []
        for wave in range(3):
            for i in range(6):
                rid = wave * 10 + i
                tail = rng.integers(1, cfg.vocab_size,
                                    size=2 + (i + wave) % 4).tolist()
                r = Request(rid=rid, prompt=prefixes[(wave + i) % 2] + tail,
                            max_new=16)
                reqs.append(r)
                srv.submit(r)
            srv.run_until_drained()  # audits every step via audit_every=1
            srv.audit()
        assert all(r.status == "ok" for r in reqs)
        assert srv.stats["fp4_frozen_pages"] >= 3
        assert srv.stats["prefix_reclaims"] >= 1  # frozen-region rotation
        assert srv.stats["preemptions"] >= 1 and srv.stats["resumes"] >= 1

    def test_steal_resume_token_identity_mixed(self, trained_tiny):
        """Spill/resume of mixed-format tables is bit-exact per format:
        the same single-prefix workload served through a pool tight enough
        to force page steals produces token streams identical to an ample
        pool where nothing is ever preempted. (Solo-run comparison would
        be wrong here: a warm-admitted request prefills against the FP4
        frozen prefix, a cold solo run against its own FP8 pages.)"""
        cfg, params = trained_tiny
        rng = np.random.default_rng(5)
        prefix = rng.integers(1, cfg.vocab_size, size=24).tolist()
        prompts = [prefix + rng.integers(1, cfg.vocab_size,
                                         size=2 + i % 5).tolist()
                   for i in range(8)]

        def run(pool_pages):
            srv = Server(params, cfg, ServerConfig(
                slots=3, max_seq=64, page_size=8, a_fmt=None,
                pool_pages=pool_pages, cache=MIXED, audit_every=1,
                scheduler=SchedulerConfig(headroom_pages=1,
                                          steal_cooldown=1)))
            reqs = [Request(rid=i, prompt=list(p), max_new=16)
                    for i, p in enumerate(prompts)]
            for r in reqs:
                srv.submit(r)
            srv.run_until_drained()
            srv.audit()
            return {r.rid: list(r.out) for r in reqs}, srv

        tight, srv_t = run(7)
        ample, srv_a = run(None)
        assert srv_t.stats["preemptions"] >= 1
        assert srv_a.stats["preemptions"] == 0
        assert tight == ample

    def test_mixed_policy_requires_prefix_cache(self, trained_tiny):
        cfg, params = trained_tiny
        with pytest.raises(ValueError, match="prefix cache"):
            Server(params, cfg,
                   ServerConfig(slots=2, max_seq=64, page_size=8,
                                a_fmt=None, prefix_cache=False, cache=MIXED))
