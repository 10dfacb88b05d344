"""Paged FP8 KV cache + decode attention.

Covers: page quantization roundtrips (splice + in-graph append), the
decode-attention kernel vs its jnp oracle (bit-level parity in interpret
mode) and both vs the unquantized bf16 reference at FP8-appropriate
tolerance across a (heads, head_dim, page_size, seq) sweep, the MLA
absorbed paged path vs the contiguous legacy decode, pool bytes-per-token
accounting, and the served end-to-end path (paged bf16 == legacy greedy;
paged FP8 == paged bf16 greedy on a trained tiny config)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import models
from repro.kernels import ops
from repro.runtime import kv_cache as kvc
from repro.runtime.serve import Request, Server, ServerConfig


def _attn_exact(q, k, v, kv_len, g):
    """Unquantized single-token attention oracle. q: (H, hd); k/v: (T, KV, hd)."""
    h, hd = q.shape
    o = np.zeros((h, v.shape[-1]), np.float32)
    for hi in range(h):
        sc = q[hi] @ k[:kv_len, hi // g].T / np.sqrt(hd)
        p = np.exp(sc - sc.max())
        p /= p.sum()
        o[hi] = p @ v[:kv_len, hi // g]
    return o


def _filled_pool(rng, kv, hd, page, pp, lens, fmt):
    """A 1-layer GQA pool spliced with per-row random prompts."""
    b = len(lens)
    n_pages = b * pp
    pool = kvc.init_gqa_pool(1, n_pages, page, kv, hd, fmt)
    pt = np.zeros((b, pp), np.int32)
    kc = rng.normal(size=(b, 1, 1, pp * page, kv, hd)).astype(np.float32)
    vc = rng.normal(size=(b, 1, 1, pp * page, kv, hd)).astype(np.float32)
    for r in range(b):
        npg = kvc.pages_needed(int(lens[r]), page)
        ids = np.arange(r * pp, r * pp + npg, dtype=np.int32)
        pt[r, :npg] = ids
        pool = kvc.splice_prefill(
            pool, {"k": jnp.asarray(kc[r]), "v": jnp.asarray(vc[r])}, ids,
            int(lens[r]))
    return pool, pt, kc[:, 0, 0], vc[:, 0, 0]


class TestPagedPool:
    def test_splice_gather_roundtrip_fp8(self):
        rng = np.random.default_rng(0)
        lens = np.array([13, 5], np.int32)
        pool, pt, kc, _ = _filled_pool(rng, 2, 16, 8, 3, lens, "fp8_e4m3")
        state = kvc.PagedState(jnp.asarray(pt), jnp.asarray(lens))
        got = np.asarray(kvc.gather_pages(pool, 0, "k", state))
        for r, n in enumerate(lens):
            ref = kc[r, :n]
            err = np.abs(got[r, :n] - ref).max() / np.abs(ref).max()
            assert err < 0.07, err  # E4M3 grid with floor-rounded M2 scales

    def test_append_matches_splice(self):
        """Tokens appended one-by-one in-graph decode to (nearly) the same
        values as a one-shot splice of the full sequence."""
        rng = np.random.default_rng(1)
        kv, hd, page = 2, 8, 4
        seq = 11
        stream = rng.normal(size=(seq, kv, hd)).astype(np.float32)
        pool = kvc.init_gqa_pool(1, 4, page, kv, hd, "fp8_e4m3")
        # token 0 arrives as a (1-token) prefill splice — rows with length 0
        # are by convention inactive and never receive decode appends
        pool = kvc.splice_prefill(
            pool, {"k": jnp.asarray(stream[None, None, None, :1]),
                   "v": jnp.asarray(stream[None, None, None, :1])},
            np.array([0]), 1)
        pt = jnp.asarray([[0, 1, 2]], jnp.int32)
        app = jax.jit(kvc.append_paged)
        for t in range(1, seq):
            state = kvc.PagedState(pt, jnp.asarray([t], jnp.int32))
            tok = jnp.asarray(stream[t][None, None])
            pool = app(pool, 0, {"k": tok, "v": tok}, state)
        state = kvc.PagedState(pt, jnp.asarray([seq], jnp.int32))
        got = np.asarray(kvc.gather_pages(pool, 0, "k", state))[0, :seq]
        err = np.abs(got - stream).max() / np.abs(stream).max()
        # appends requantize the touched page; with unchanged scales the
        # decode->encode is exact, so error stays at one-quantization level
        assert err < 0.08, err

    def test_append_empty_rows_hit_null_page(self):
        """Inactive rows (lengths == 0) must not corrupt live pages."""
        rng = np.random.default_rng(2)
        lens = np.array([9, 0], np.int32)
        pool, pt, kc, _ = _filled_pool(rng, 2, 8, 8, 2, lens, "fp8_e4m3")
        state = kvc.PagedState(jnp.asarray(pt), jnp.asarray(lens))
        before = np.asarray(kvc.gather_pages(pool, 0, "k", state))[0, :9]
        new = {"k": jnp.ones((2, 1, 2, 8)), "v": jnp.ones((2, 1, 2, 8))}
        pool = jax.jit(kvc.append_paged)(pool, 0, new, state)
        after = np.asarray(kvc.gather_pages(pool, 0, "k", state))[0, :9]
        np.testing.assert_allclose(after, before, rtol=1e-6, atol=1e-6)

    def test_splice_overhangs_prefill_cache(self):
        """Reserved pages may overhang the prefill cache's max_seq when
        max_seq is not a page multiple — the tail pads with zeros instead
        of crashing."""
        rng = np.random.default_rng(3)
        kv, hd, page, max_seq, n = 2, 8, 8, 20, 18  # 3 pages = 24 > 20
        pool = kvc.init_gqa_pool(1, 4, page, kv, hd, "fp8_e4m3")
        cache = {
            "k": jnp.asarray(rng.normal(size=(1, 1, max_seq, kv, hd)).astype(np.float32)),
            "v": jnp.asarray(rng.normal(size=(1, 1, max_seq, kv, hd)).astype(np.float32)),
        }
        pool = kvc.splice_prefill(pool, cache, np.array([0, 1, 2]), n)
        state = kvc.PagedState(jnp.asarray([[0, 1, 2]], jnp.int32),
                               jnp.asarray([n], jnp.int32))
        got = np.asarray(kvc.gather_pages(pool, 0, "k", state))[0]
        ref = np.asarray(cache["k"][0, 0, :n])
        assert np.abs(got[:n] - ref).max() / np.abs(ref).max() < 0.07
        np.testing.assert_array_equal(got[n:], 0)

    def test_bytes_per_token_halved(self):
        pool = kvc.init_gqa_pool(4, 32, 64, 4, 64, "fp8_e4m3")
        ratio = kvc.pool_bytes_per_token(pool) / kvc.bf16_bytes_per_token(pool)
        assert ratio <= 0.55, ratio
        bf16 = kvc.init_gqa_pool(4, 32, 64, 4, 64, None)
        assert kvc.pool_bytes_per_token(bf16) == kvc.bf16_bytes_per_token(bf16)


class TestPagedDecodeAttn:
    @pytest.mark.parametrize("kv,g,hd,page,pp", [
        (2, 2, 16, 8, 3),   # GQA
        (1, 4, 32, 16, 2),  # MQA-ish, bigger head
        (4, 1, 8, 4, 4),    # MHA, many small pages
        (2, 3, 64, 32, 2),  # odd group size (padding path)
    ])
    def test_fp8_matches_bf16_oracle(self, kv, g, hd, page, pp):
        """The quantized paged decode matches full-precision attention to
        FP8-appropriate tolerance, and the pallas kernel (interpret mode)
        matches the jnp oracle tightly."""
        rng = np.random.default_rng(hash((kv, g, hd, page)) % 2**31)
        h = kv * g
        lens = np.array([page * pp - 3, max(1, page // 2)], np.int32)
        q = jnp.asarray(rng.normal(size=(2, h, hd)).astype(np.float32))
        prev = ops.get_backend()
        try:
            outs = {}
            for fmt in ("fp8_e4m3", None):
                pool, pt, kc, vc = _filled_pool(rng, kv, hd, page, pp, lens, fmt)
                ops.set_backend("ref")
                o_ref = ops.paged_decode_attn(q, pool, 0, jnp.asarray(pt),
                                              jnp.asarray(lens))
                ops.set_backend("pallas_interpret")
                o_pal = ops.paged_decode_attn(q, pool, 0, jnp.asarray(pt),
                                              jnp.asarray(lens))
                np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_ref),
                                           rtol=2e-5, atol=2e-5)
                for r in range(2):
                    exact = _attn_exact(np.asarray(q[r]), kc[r], vc[r],
                                        int(lens[r]), g)
                    err = np.abs(np.asarray(o_ref[r]) - exact).max()
                    scale = np.abs(exact).max() + 1e-9
                    tol = 0.12 if fmt else 0.01
                    assert err / scale < tol, (fmt, err / scale)
                outs[fmt] = o_ref
        finally:
            ops.set_backend(prev)


    def test_sliding_window(self):
        """window > 0 masks history beyond the window in both backends (the
        query for a decode step sits at position kv_len - 1)."""
        rng = np.random.default_rng(5)
        kv, g, hd, page, pp = 2, 2, 16, 8, 3
        window = 6
        lens = np.array([20, 4], np.int32)  # row 1 shorter than the window
        q = jnp.asarray(rng.normal(size=(2, kv * g, hd)).astype(np.float32))
        pool, pt, kc, vc = _filled_pool(rng, kv, hd, page, pp, lens, None)
        prev = ops.get_backend()
        try:
            ops.set_backend("ref")
            o_ref = ops.paged_decode_attn(q, pool, 0, jnp.asarray(pt),
                                          jnp.asarray(lens), window=window)
            ops.set_backend("pallas_interpret")
            o_pal = ops.paged_decode_attn(q, pool, 0, jnp.asarray(pt),
                                          jnp.asarray(lens), window=window)
        finally:
            ops.set_backend(prev)
        np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_ref),
                                   rtol=2e-5, atol=2e-5)
        for r in range(2):
            lo = max(0, int(lens[r]) - window)
            exact = _attn_exact(np.asarray(q[r]), kc[r, lo:], vc[r, lo:],
                                int(lens[r]) - lo, g)
            err = np.abs(np.asarray(o_ref[r]) - exact).max()
            assert err / (np.abs(exact).max() + 1e-9) < 0.01, err


class TestPrefixCacheIndex:
    """The host-side content-addressed index over frozen pages: chained
    page keys, longest-prefix walk, dedup on insert, park/unpark/reclaim
    LRU semantics, and the frozen-page write guard."""

    def test_walk_insert_roundtrip(self):
        c = kvc.PrefixCache(4)
        toks = list(range(12))
        assert c.walk(toks) == []
        assert c.insert(toks[:8], [10, 11]) == [10, 11]
        assert c.walk(toks) == [10, 11]  # third page never registered
        assert c.walk(toks, max_pages=1) == [10]
        assert c.walk(toks[:6]) == [10]  # partial second page: no hit
        assert c.walk([9, 9, 9, 9]) == []  # different content

    def test_chained_keys_do_not_collide_across_depths(self):
        """The same token window under a different history is a different
        page: keys chain on the parent, so depth-1 [4..7] != root [4..7]."""
        c = kvc.PrefixCache(4)
        toks = list(range(8))
        c.insert(toks, [0, 1])
        c.insert(toks[4:8], [2])  # same window at the *root*
        assert c.walk(toks) == [0, 1]
        assert c.walk(toks[4:8]) == [2]

    def test_insert_dedups_to_canonical(self):
        """A second registration of the same chain returns the existing
        pages — the duplicate pid is never registered (the caller adopts
        the canonical page and frees its copy)."""
        c = kvc.PrefixCache(4)
        toks = list(range(8))
        assert c.insert(toks, [0, 1]) == [0, 1]
        assert c.insert(toks, [5, 6]) == [0, 1]
        assert not c.registered(5) and not c.registered(6)

    def test_park_unpark_reclaim_lru(self):
        c = kvc.PrefixCache(4)
        toks = list(range(12))
        c.insert(toks, [0, 1, 2])
        for pid in (0, 1, 2):
            c.park(pid)
        assert c.n_reusable == 3
        c.unpark(1)  # re-acquired: no longer reclaimable
        assert c.reclaim() == 0  # oldest parked first
        assert c.reclaims == 1
        # the chain is broken at depth 1: deeper entries are unreachable
        assert c.walk(toks) == []
        assert c.registered(1) and c.registered(2)
        assert c.reclaim() == 2 and c.reclaim() is None

    def test_assert_unfrozen_guards_registered_pages(self):
        c = kvc.PrefixCache(4)
        c.insert(list(range(4)), [3])
        c.assert_unfrozen([0, 1, 2])  # private pages pass
        with pytest.raises(AssertionError, match="frozen"):
            c.assert_unfrozen([3])


def _mla_smoke_cfg():
    from repro.configs import get_smoke

    return get_smoke("minicpm3-4b")


def _filled_mla_pool(rng, r, dr, page, pp, lens, fmt):
    """A 1-layer MLA latent pool spliced with per-row random prompts."""
    b = len(lens)
    pool = kvc.init_mla_pool(1, b * pp, page, r, dr, fmt)
    pt = np.zeros((b, pp), np.int32)
    ck = rng.normal(size=(b, 1, 1, pp * page, r)).astype(np.float32)
    kr = rng.normal(size=(b, 1, 1, pp * page, dr)).astype(np.float32)
    for row in range(b):
        npg = kvc.pages_needed(int(lens[row]), page)
        ids = np.arange(row * pp, row * pp + npg, dtype=np.int32)
        pt[row, :npg] = ids
        pool = kvc.splice_prefill(
            pool, {"ckv": jnp.asarray(ck[row]), "krope": jnp.asarray(kr[row])},
            ids, int(lens[row]))
    return pool, pt, ck[:, 0, 0], kr[:, 0, 0]


class TestPagedMLAKernel:
    """The latent flash-decoding kernel (KV = 1 head, k = concat(ckv,
    krope), v = ckv view) vs the jnp oracle and the exact numpy softmax."""

    @pytest.mark.parametrize("h,r,dr,page,pp", [
        (4, 16, 8, 8, 3),    # minicpm3-ish smoke
        (8, 32, 16, 16, 2),  # wider latent
        (3, 16, 8, 4, 4),    # odd head count (bq padding path)
        (16, 64, 32, 8, 2),  # many heads, deepseek-ish ratio
    ])
    def test_kernel_matches_oracle(self, h, r, dr, page, pp):
        rng = np.random.default_rng(hash((h, r, dr, page)) % 2**31)
        lens = np.array([page * pp - 3, max(1, page // 2)], np.int32)
        ql = jnp.asarray(rng.normal(size=(2, h, r)).astype(np.float32))
        qr = jnp.asarray(rng.normal(size=(2, h, dr)).astype(np.float32))
        scale = 1.0 / float(r + dr) ** 0.5
        prev = ops.get_backend()
        try:
            for fmt in ("fp8_e4m3", None):
                pool, pt, ck, kr = _filled_mla_pool(rng, r, dr, page, pp,
                                                     lens, fmt)
                ops.set_backend("ref")
                o_ref = ops.paged_mla_decode_attn(
                    ql, qr, pool, 0, jnp.asarray(pt), jnp.asarray(lens), scale)
                ops.set_backend("pallas_interpret")
                o_pal = ops.paged_mla_decode_attn(
                    ql, qr, pool, 0, jnp.asarray(pt), jnp.asarray(lens), scale)
                np.testing.assert_allclose(np.asarray(o_pal),
                                           np.asarray(o_ref),
                                           rtol=2e-5, atol=2e-5)
                # vs the exact (unquantized, unpaged) softmax
                for row in range(2):
                    n = int(lens[row])
                    s = (np.asarray(ql[row]) @ ck[row, :n].T
                         + np.asarray(qr[row]) @ kr[row, :n].T) * scale
                    p = np.exp(s - s.max(-1, keepdims=True))
                    p /= p.sum(-1, keepdims=True)
                    exact = p @ ck[row, :n]
                    err = np.abs(np.asarray(o_ref[row]) - exact).max()
                    tol = 0.12 if fmt else 0.01
                    assert err / (np.abs(exact).max() + 1e-9) < tol, (fmt, err)
        finally:
            ops.set_backend(prev)


class TestPagedMLA:
    @pytest.mark.parametrize("kv_fmt", [None, "fp8_e4m3"])
    def test_paged_decode_matches_legacy(self, kv_fmt):
        """MLA absorbed decode over latent pages vs the contiguous cache."""
        cfg = _mla_smoke_cfg()
        params = models.init_params(cfg, jax.random.PRNGKey(0))
        prompt = np.random.default_rng(0).integers(1, cfg.vocab_size, 7).tolist()
        toks = jnp.asarray([prompt], jnp.int32)
        max_seq, page = 32, 8
        logits, caches = models.prefill(params, cfg, {"tokens": toks}, max_seq)
        t0 = int(jnp.argmax(logits[0]))

        # legacy contiguous decode
        lg_legacy, _ = models.decode_step(
            params, cfg, jnp.asarray([[t0]], jnp.int32), caches, len(prompt))

        # paged decode from a spliced pool
        pools = []
        from repro.models.transformer import segments_for

        for i, seg in enumerate(segments_for(cfg)):
            pool = kvc.init_mla_pool(seg.count, 4, page, cfg.mla.kv_lora_rank,
                                     cfg.mla.qk_rope_dim, kv_fmt)
            pools.append({"kv": kvc.splice_prefill(
                pool, caches[i]["kv"], np.array([0, 1]), len(prompt))})
        state = kvc.PagedState(jnp.asarray([[0, 1, 2, 3]], jnp.int32),
                               jnp.asarray([len(prompt)], jnp.int32))
        lg_paged, _ = models.decode_step(
            params, cfg, jnp.asarray([[t0]], jnp.int32), pools, state)

        a, b = np.asarray(lg_legacy[0]), np.asarray(lg_paged[0])
        scale = np.abs(a).max() + 1e-9
        tol = 0.1 if kv_fmt else 2e-2
        assert np.abs(a - b).max() / scale < tol


def _greedy_legacy(params, cfg, prompt, max_new, max_seq=64, frames=None):
    """Reference greedy loop over the contiguous (non-paged) cache — the
    pre-paged-engine decode path kept by the model layer."""
    batch = {"tokens": jnp.asarray([prompt], jnp.int32)}
    if frames is not None:
        batch["frames"] = jnp.asarray(frames[None])
    logits, caches = models.prefill(params, cfg, batch, max_seq)
    out = [int(jnp.argmax(logits[0]))]
    idx = len(prompt)
    while len(out) < max_new:
        logits, caches = models.decode_step(
            params, cfg, jnp.asarray([[out[-1]]], jnp.int32), caches, idx)
        out.append(int(jnp.argmax(logits[0])))
        idx += 1
    return out


class TestServerPaged:
    def _prompts(self, cfg):
        rng = np.random.default_rng(0)
        return [rng.integers(1, cfg.vocab_size, size=n).tolist()
                for n in (5, 9, 3)]

    def _serve(self, params, cfg, kv_fmt, prompts, max_new=6):
        srv = Server(params, cfg,
                     ServerConfig(slots=len(prompts), max_seq=64,
                                  kv_fmt=kv_fmt, page_size=8, a_fmt=None))
        for i, p in enumerate(prompts):
            srv.submit(Request(rid=i, prompt=p, max_new=max_new))
        done = srv.run_until_drained()
        return {r.rid: list(r.tokens) for r in done}, srv

    def test_bf16_paged_matches_legacy_greedy(self, trained_tiny):
        """Per-slot true lengths: a mixed-length batch reproduces each
        request's solo contiguous-cache generation exactly (the old
        synchronized max-length engine could not)."""
        cfg, params = trained_tiny
        prompts = self._prompts(cfg)
        batch, _ = self._serve(params, cfg, None, prompts)
        for i, p in enumerate(prompts):
            assert batch[i] == _greedy_legacy(params, cfg, p, 6), i

    def test_fp8_token_identical_to_bf16(self, trained_tiny):
        cfg, params = trained_tiny
        prompts = self._prompts(cfg)
        out_bf16, _ = self._serve(params, cfg, None, prompts)
        out_fp8, srv = self._serve(params, cfg, "fp8_e4m3", prompts)
        assert out_bf16 == out_fp8
        ratio = srv.kv_bytes_per_token() / srv.kv_bf16_bytes_per_token()
        assert ratio <= 0.55, ratio

    def test_run_until_drained_returns_finished(self, trained_tiny):
        cfg, params = trained_tiny
        prompts = self._prompts(cfg)
        srv = Server(params, cfg,
                     ServerConfig(slots=2, max_seq=64, kv_fmt="fp8_e4m3",
                                  page_size=8, a_fmt=None))
        for i, p in enumerate(prompts):
            srv.submit(Request(rid=i, prompt=p, max_new=4))
        done = srv.run_until_drained()
        assert sorted(r.rid for r in done) == [0, 1, 2]
        assert all(r.ok and len(r.tokens) == 4 for r in done)
        assert srv.queue == [] and not any(srv.active)
        # pages recycled: 3 requests served through a 2-slot pool (full
        # prompt pages stay parked in the prefix cache's reusable LRU —
        # still allocatable, so the pool is whole)
        assert (len(srv.free_pages) + len(srv.reusable_pages)
                == len(srv.page_table.flatten()))
        assert (srv.page_refs == 0).all()

    def test_page_recycling_under_pressure(self, trained_tiny):
        """More requests than the pool can hold at once: admission waits for
        retirements, every request still completes correctly."""
        cfg, params = trained_tiny
        prompts = self._prompts(cfg) * 2
        srv = Server(params, cfg,
                     ServerConfig(slots=2, max_seq=64, kv_fmt="fp8_e4m3",
                                  page_size=8, pool_pages=4, a_fmt=None))
        for i, p in enumerate(prompts):
            srv.submit(Request(rid=i, prompt=p, max_new=4))
        done = srv.run_until_drained()
        assert len(done) == len(prompts)
        by_rid = {r.rid: r.tokens for r in done}
        assert by_rid[0] == by_rid[3] and by_rid[2] == by_rid[5]

    def test_sliding_window_config_matches_legacy(self, trained_tiny):
        """A window > 0 config must thread its sliding-window mask through
        the paged decode path, not silently attend full history."""
        import dataclasses

        cfg, params = trained_tiny
        wcfg = dataclasses.replace(cfg, window=4)
        prompts = self._prompts(cfg)
        batch, _ = self._serve(params, wcfg, None, prompts)
        for i, p in enumerate(prompts):
            assert batch[i] == _greedy_legacy(params, wcfg, p, 6), i

    def test_infeasible_request_fails_fast(self, trained_tiny):
        """A request that can never fit the pool raises at submit instead of
        head-of-line blocking the queue forever."""
        cfg, params = trained_tiny
        srv = Server(params, cfg,
                     ServerConfig(slots=1, max_seq=64, kv_fmt="fp8_e4m3",
                                  page_size=8, pool_pages=2, a_fmt=None))
        with pytest.raises(ValueError, match="pages"):
            srv.submit(Request(rid=0, prompt=list(range(1, 20)), max_new=10))

    def test_mla_served_greedy_matches_legacy(self, trained_tiny_mla):
        """The acceptance claim for MLA: the paged engine (latent decode
        kernel path) reproduces the legacy contiguous-cache greedy output,
        bf16 and fp8, on a trained model with decisive logits."""
        cfg, params = trained_tiny_mla
        rng = np.random.default_rng(2)
        prompts = [rng.integers(1, cfg.vocab_size, size=n).tolist()
                   for n in (5, 11, 3)]
        for kv_fmt in (None, "fp8_e4m3"):
            batch, _ = self._serve(params, cfg, kv_fmt, prompts)
            for i, p in enumerate(prompts):
                assert batch[i] == _greedy_legacy(params, cfg, p, 6), (kv_fmt, i)


class TestServerEncDec:
    """Whisper-style enc-dec on the paged engine: write-once cross pages +
    paged decoder self-attention, admission charging prompt + encoder
    pages — the family that used to keep the legacy monolithic engine."""

    def _reqs(self, cfg, rng, n=3):
        prompts = [rng.integers(1, cfg.vocab_size, size=m).tolist()
                   for m in (5, 9, 3)[:n]]
        frames = [rng.normal(size=(cfg.encoder_seq, cfg.d_model))
                  .astype(np.float32) for _ in prompts]
        return prompts, frames

    def _serve(self, params, cfg, kv_fmt, prompts, frames, max_new=6):
        srv = Server(params, cfg,
                     ServerConfig(slots=len(prompts), max_seq=64,
                                  kv_fmt=kv_fmt, page_size=8, a_fmt=None))
        for i, (p, f) in enumerate(zip(prompts, frames)):
            srv.submit(Request(rid=i, prompt=list(p), max_new=max_new,
                               frames=f))
        done = srv.run_until_drained()
        return {r.rid: list(r.tokens) for r in done}, srv

    @pytest.mark.parametrize("kv_fmt", [None, "fp8_e4m3"])
    def test_paged_matches_legacy_greedy(self, trained_tiny_encdec, kv_fmt):
        """Acceptance: enc-dec greedy through the paged engine (bf16 and
        fp8 pages) is token-identical to the pre-paged legacy engine."""
        cfg, params = trained_tiny_encdec
        rng = np.random.default_rng(0)
        prompts, frames = self._reqs(cfg, rng)
        batch, srv = self._serve(params, cfg, kv_fmt, prompts, frames)
        for i, (p, f) in enumerate(zip(prompts, frames)):
            assert batch[i] == _greedy_legacy(params, cfg, p, 6, frames=f), i
        if kv_fmt:  # FP8 cross+self pages still halve the KV bytes
            ratio = srv.kv_bytes_per_token() / srv.kv_bf16_bytes_per_token()
            assert ratio <= 0.55, ratio

    def test_admission_charges_encoder_pages(self, trained_tiny_encdec):
        """Admission must charge pages(prompt) + pages(encoder_seq): a pool
        that fits the prompt but not the cross pages cannot admit."""
        cfg, params = trained_tiny_encdec
        rng = np.random.default_rng(1)
        prompts, frames = self._reqs(cfg, rng, n=1)
        cross_pp = kvc.pages_needed(cfg.encoder_seq, 8)
        srv = Server(params, cfg,
                     ServerConfig(slots=1, max_seq=64, kv_fmt="fp8_e4m3",
                                  page_size=8, pool_pages=cross_pp, a_fmt=None))
        with pytest.raises(ValueError, match="pages"):
            srv.submit(Request(rid=0, prompt=prompts[0], max_new=4,
                               frames=frames[0]))

    def test_missing_frames_fails_fast(self, trained_tiny_encdec):
        cfg, params = trained_tiny_encdec
        srv = Server(params, cfg,
                     ServerConfig(slots=1, max_seq=32, kv_fmt="fp8_e4m3",
                                  page_size=8, a_fmt=None))
        # decoder K/V depends on the encoder frames, not just the token
        # prefix — the prefix cache stays ON (radix chains hang off a
        # per-frames-digest root, see test_encdec_prefix_cache), but a
        # request without frames still fails fast at submit
        assert srv._prefix is not None
        with pytest.raises(ValueError, match="frames"):
            srv.submit(Request(rid=0, prompt=[1, 2, 3], max_new=4))

    def test_encdec_prefix_cache_parity_and_collision_safety(
            self, trained_tiny_encdec):
        """Enc-dec prefix sharing keys pages on (frames digest, token
        prefix): two requests with the same prompt and the SAME frames hit
        the cache (second serve pays no prefill for the shared pages) and
        stay token-identical to a cold run; the same prompt under
        DIFFERENT frames must never share pages — decoder K/V depends on
        the frames through cross-attention — and each still decodes
        exactly its own cold-run tokens."""
        cfg, params = trained_tiny_encdec
        rng = np.random.default_rng(9)
        prompt = rng.integers(1, cfg.vocab_size, size=17).tolist()
        f_a = rng.normal(size=(cfg.encoder_seq, cfg.d_model)).astype(np.float32)
        f_b = rng.normal(size=(cfg.encoder_seq, cfg.d_model)).astype(np.float32)

        def cold(frames):
            out, _ = self._serve(params, cfg, "fp8_e4m3", [prompt], [frames])
            return out[0]

        ref_a, ref_b = cold(f_a), cold(f_b)
        # digests are bit-exact content hashes: distinct frames -> distinct
        # radix roots (collision safety does not depend on output deltas)
        assert (Request(rid=98, prompt=[1], frames=f_a).frames_digest()
                != Request(rid=97, prompt=[1], frames=f_b).frames_digest())

        # same frames twice, then the same prompt under different frames
        srv = Server(params, cfg,
                     ServerConfig(slots=1, max_seq=64, kv_fmt="fp8_e4m3",
                                  page_size=8, a_fmt=None))
        outs = {}
        for rid, frames in ((0, f_a), (1, f_a), (2, f_b)):
            r = Request(rid=rid, prompt=list(prompt), max_new=6, frames=frames)
            srv.submit(r)
            srv.run_until_drained()
            outs[rid] = list(r.out)
        assert outs[0] == ref_a and outs[1] == ref_a  # parity + hit path
        assert outs[2] == ref_b  # no cross-frames aliasing
        # the repeat under identical frames mapped the frozen pages
        # ((17 - 1) // 8 = 2 full pages); the f_b request walked a disjoint
        # radix chain and hit nothing
        assert srv.stats["prefix_hit_pages"] == 2
        assert srv.audit()["violations"] == 0

    def test_cross_pages_survive_steal_resume(self, trained_tiny_encdec):
        """Preemption spills cross pages with the rest of the payload:
        a stolen-and-resumed enc-dec request is token-identical to an
        uncontended solo run."""
        cfg, params = trained_tiny_encdec
        rng = np.random.default_rng(4)
        prompts, frames = self._reqs(cfg, rng, n=2)
        # prompts (5, 9) charge 2+1 and 3+1 pages + cross_pp each; both fit
        # at admission, but growth to 15 and 19 tokens (4 + 5 pages) wants
        # one page more than the pool holds -> exactly one steal + resume
        cross_pp = kvc.pages_needed(cfg.encoder_seq, 4)
        srv = Server(params, cfg,
                     ServerConfig(slots=2, max_seq=32, kv_fmt="fp8_e4m3",
                                  page_size=4, pool_pages=8 + 2 * cross_pp,
                                  a_fmt=None))
        reqs = [Request(rid=i, prompt=list(p), max_new=10, frames=f)
                for i, (p, f) in enumerate(zip(prompts, frames))]
        for r in reqs:
            srv.submit(r)
        srv.run_until_drained()
        assert srv.stats["preemptions"] >= 1 and srv.stats["resumes"] >= 1
        for r in reqs:
            solo = Server(params, cfg,
                          ServerConfig(slots=1, max_seq=32, kv_fmt="fp8_e4m3",
                                       page_size=4, a_fmt=None))
            ref = Request(rid=99, prompt=list(r.prompt), max_new=10,
                          frames=r.frames)
            solo.submit(ref)
            solo.run_until_drained()
            assert r.out == ref.out, (r.rid, r.out, ref.out)
