"""Benchmark harness — one function per paper table/figure plus the
roofline table and kernel microbenchmarks.

Prints ``name,us_per_call,derived`` CSV at the end (harness contract).
"""
from __future__ import annotations

import argparse
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np


def kernel_microbench(tiny: bool = False):
    """us/call of the quantization primitives (CPU timings — relative cost
    of ref vs pallas-interpret paths; TPU wall-time needs real hardware).

    Times the fused single-pass pipeline against the split three-pass path
    (act_quant -> HBM -> matmul -> LoRC matmuls) on every shape and emits
    BENCH_kernels.json (name -> us_per_call, plus explicit ``speedup/*``
    keys the CI benchmark-smoke job gates on) so the perf trajectory is
    tracked across PRs. Asserts the fused path is never slower than split.

    ``tiny`` (CI smoke / REPRO_BENCH_TINY=1): shrunken shapes + a reduced
    autotune candidate set so the job finishes in seconds.
    """
    import json

    from repro.core.policy import QuantPolicy
    from repro.core.ptq import pack_linear
    from repro.kernels import ref
    from repro.kernels.act_quant import act_quant_pallas
    from repro.kernels.w4a8_fused import w4a8_fused_matmul_pallas
    from repro.kernels.w4a8_matmul import w4a8_matmul_pallas
    from .common import timed

    tiny = tiny or os.environ.get("REPRO_BENCH_TINY") == "1"
    rng = np.random.default_rng(0)
    d = 256 if tiny else 1024
    x = jnp.asarray(rng.normal(size=(64 if tiny else 256, d)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(d, d)).astype(np.float32) * 0.05)
    pl_w = pack_linear(w, QuantPolicy(w_fmt="fp4_e2m1", a_fmt="fp8_e4m3",
                                      group_size=256, scale_mode="m2"))
    xq = jnp.asarray(rng.normal(size=(x.shape[0], d)).astype(np.float32)).astype(jnp.bfloat16)

    rows = []
    print("\n== kernel microbench (CPU) ==")
    aq_ref = jax.jit(lambda v: ref.act_quant_ref(v, "fp8_e4m3"))
    t = timed(aq_ref, x)
    rows.append(("kernel/act_quant_ref", t, 0.0))
    t2 = timed(lambda v: act_quant_pallas(v, "fp8_e4m3", interpret=True), x)
    rows.append(("kernel/act_quant_pallas_interp", t2, 0.0))
    mm_ref = jax.jit(lambda v: ref.w4a8_matmul_ref(v, pl_w.codes, pl_w.scale))
    t3 = timed(mm_ref, xq)
    rows.append(("kernel/w4a8_matmul_ref", t3, 0.0))
    t4 = timed(lambda v: w4a8_matmul_pallas(v, pl_w.codes, pl_w.scale,
                                            s_max=pl_w.s_max, shifts=pl_w.shifts,
                                            interpret=True), xq)
    rows.append(("kernel/w4a8_matmul_pallas_interp", t4, 0.0))

    # ---- fused single-pass vs split three-pass, per shape -----------------
    # The fused path runs with autotuned block sizes (the sweep also
    # populates the persistent cache the ops dispatch layer reads), the
    # split path with its production defaults — i.e. each path as deployed.
    # Shapes: prefill (256 tokens), slot-batched decode (64 concurrent
    # serving slots x 1 token), and a LoRC-heavy projection. (Single-digit-M
    # decode is omitted: CPU-interpret emulation overhead swamps the fusion
    # win there; on TPU that bandwidth-bound case is where fusion wins most,
    # and the autotune cache remains the arbiter on real hardware.)
    from repro.kernels import autotune

    if tiny:
        shapes = [("m256", 64, 256, 256, 0), ("decode64", 16, 256, 256, 0),
                  ("lorc16", 16, 256, 256, 8)]
        candidates = ((128, 128), (64, 128), (16, 128), (8, 128))
    else:
        shapes = [("m256", 256, 1024, 1024, 0), ("decode64", 64, 1024, 1024, 0),
                  ("lorc16", 64, 512, 1024, 16)]
        candidates = autotune.DEFAULT_CANDIDATES
    slower = []
    for tag, m, n, k, rank in shapes:
        pw = pack_linear(
            jnp.asarray(rng.normal(size=(n, k)).astype(np.float32) * 0.05),
            QuantPolicy(w_fmt="fp4_e2m1", a_fmt="fp8_e4m3", group_size=256,
                        scale_mode="m2", lorc_rank=rank))
        xs = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32)).astype(jnp.bfloat16)

        def split(v, pw=pw):
            qv, sc = act_quant_pallas(v, pw.a_fmt, interpret=True)
            xqv = (qv * sc).astype(jnp.bfloat16)
            y = w4a8_matmul_pallas(xqv, pw.codes, pw.scale, s_max=pw.s_max,
                                   shifts=pw.shifts, group_size=256, interpret=True)
            if pw.lorc_a is not None:
                y = y + (xqv @ pw.lorc_b.T.astype(jnp.bfloat16)).astype(jnp.bfloat16) \
                    @ pw.lorc_a.T.astype(jnp.bfloat16)
            return y

        def fused(v, bm, bn, pw=pw):
            return w4a8_fused_matmul_pallas(
                v, pw.codes, pw.scale, pw.s_max, pw.shifts, pw.lorc_a, pw.lorc_b,
                w_fmt="fp4_e2m1", a_fmt="fp8_e4m3", group_size=256,
                bm=bm, bn=bn, interpret=True)

        sig = dict(batch=1, m=m, n=n, k=k, w_fmt="fp4_e2m1", a_fmt="fp8_e4m3",
                   group_size=256, m2=True, lorc_rank=rank)
        bm, bn = autotune.autotune_gemm(
            lambda bm, bn: (lambda: fused(xs, bm, bn)),
            autotune.cache_key("fused", **sig), candidates=candidates,
            dims=(m, n))

        # interleave the two paths so slow box-load drift hits both equally;
        # tiny mode (CI smoke) takes more reps — shapes are cheap there and
        # shared runners are noisy, and the speedup gate sits at exactly 1.0x
        jax.block_until_ready(split(xs))
        jax.block_until_ready(fused(xs, bm, bn))
        t_split, t_fused = [], []
        for _ in range(21 if tiny else 9):
            t0 = time.perf_counter()
            jax.block_until_ready(split(xs))
            t_split.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            jax.block_until_ready(fused(xs, bm, bn))
            t_fused.append(time.perf_counter() - t0)
        med = lambda a: sorted(a)[len(a) // 2] * 1e6
        ts, tf = med(t_split), med(t_fused)
        rows.append((f"kernel/w4a8_split_{tag}", ts, 0.0))
        rows.append((f"kernel/w4a8_fused_{tag}", tf, ts / tf))
        if tf > ts:
            slower.append((tag, tf, ts))

    # ---- paged FP8 decode attention (tracked, not gated: on CPU the
    # pallas path runs under the interpreter, so only the jnp-oracle
    # number is a meaningful trend line) -----------------------------------
    from repro.kernels import ops as kops
    from repro.runtime import kv_cache as kvc

    kv, hd, page, pp, b = (2, 32, 8, 2, 2) if tiny else (4, 64, 16, 4, 4)
    pool = kvc.init_gqa_pool(1, b * pp, page, kv, hd, "fp8_e4m3")
    kc = jnp.asarray(rng.normal(size=(1, 1, pp * page, kv, hd)).astype(np.float32))
    pt = np.zeros((b, pp), np.int32)
    for r in range(b):
        ids = np.arange(r * pp, (r + 1) * pp, dtype=np.int32)
        pt[r] = ids
        pool = kvc.splice_prefill(pool, {"k": kc, "v": kc}, ids, pp * page)
    qd = jnp.asarray(rng.normal(size=(b, kv * 2, hd)).astype(np.float32))
    lens = jnp.full((b,), pp * page, jnp.int32)
    ptj = jnp.asarray(pt)
    prev = kops.get_backend()
    try:
        kops.set_backend("ref")
        t_ref = timed(jax.jit(lambda q: kops.paged_decode_attn(q, pool, 0, ptj, lens)), qd)
        rows.append(("kernel/paged_decode_attn_ref", t_ref, 0.0))
        kops.set_backend("pallas_interpret")
        t_pal = timed(lambda q: kops.paged_decode_attn(q, pool, 0, ptj, lens), qd)
        rows.append(("kernel/paged_decode_attn_pallas_interp", t_pal, 0.0))
    finally:
        kops.set_backend(prev)

    # ---- paged decode attention vs the monolithic engine (CI-gated
    # speedup/* trend line): paged decode gathers + attends only the pages
    # a row actually owns (true context), where the legacy engine attends
    # — and masks — the full max_seq row it reserved. Sized so compute
    # dominates dispatch overhead; the work ratio (`over`x tokens) keeps
    # the >= 1.0x gate far from CPU timing noise. -------------------------
    pb, pkv, pg2, phd, ppp = (8, 2, 4, 64, 8) if tiny else (8, 4, 4, 64, 16)
    over = 8  # max_seq = over x the true context
    t_true = ppp * 16
    max_ctx = t_true * over
    pool2 = kvc.init_gqa_pool(1, pb * ppp, 16, pkv, phd, "fp8_e4m3")
    pt2 = np.zeros((pb, ppp), np.int32)
    kc2 = jnp.asarray(rng.normal(size=(1, 1, t_true, pkv, phd)).astype(np.float32))
    for r in range(pb):
        ids = np.arange(r * ppp, (r + 1) * ppp, dtype=np.int32)
        pt2[r] = ids
        pool2 = kvc.splice_prefill(pool2, {"k": kc2, "v": kc2}, ids, t_true)
    q2 = jnp.asarray(rng.normal(size=(pb, pkv * pg2, phd)).astype(np.float32))
    lens2 = jnp.full((pb,), t_true, jnp.int32)
    pt2j = jnp.asarray(pt2)
    kfull = jnp.asarray(rng.normal(size=(pb, max_ctx, pkv, phd))
                        .astype(np.float32)).astype(jnp.bfloat16)
    vfull = jnp.asarray(rng.normal(size=(pb, max_ctx, pkv, phd))
                        .astype(np.float32)).astype(jnp.bfloat16)

    def legacy_decode(qv):
        kf = jnp.repeat(kfull, pg2, axis=2)
        vf = jnp.repeat(vfull, pg2, axis=2)
        s = jnp.einsum("bhd,bthd->bht", qv.astype(jnp.bfloat16), kf,
                       preferred_element_type=jnp.float32)
        s = s / np.sqrt(phd)
        s = jnp.where(jnp.arange(max_ctx)[None, None] < lens2[:, None, None],
                      s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bht,bthd->bhd", p.astype(jnp.bfloat16), vf,
                          preferred_element_type=jnp.float32)

    prev = kops.get_backend()
    try:
        kops.set_backend("ref")
        t_paged = timed(jax.jit(
            lambda q: kops.paged_decode_attn(q, pool2, 0, pt2j, lens2)), q2)
        t_mono = timed(jax.jit(legacy_decode), q2)
    finally:
        kops.set_backend(prev)
    rows.append(("kernel/paged_decode_true_ctx", t_paged, t_mono / t_paged))
    rows.append(("kernel/mono_decode_max_seq", t_mono, 0.0))

    # ---- MLA latent paged decode (CI-gated speedup/* trend line): the
    # kernel's latent dataflow — scores against k = concat(ckv, krope)
    # with v = the ckv view, so attention runs in (r + dr) dims per token
    # — vs the *materialized* gathered decode an engine without absorbed
    # MLA runs over the same pages: dequantize the latent table, expand it
    # through wk_b/wv_b into per-head K/V (h x (nope + v) dims per token),
    # standard softmax attention. Both paths are timed end to end from
    # (q_nope, q_rope) to the (B, H, v) head outputs, so the absorbed
    # path's q/out projections are charged too; the latent side is timed
    # via its jnp oracle (on CPU the pallas path runs the interpreter —
    # same convention as the paged_decode_true_ctx line).
    # sized so the head expansion dominates dispatch overhead: the
    # materialized baseline writes T x H x (nope + v) while the latent
    # path stays at T x (r + dr) — an 8x byte ratio at these dims, which
    # is what keeps the >= 1.0x gate far from CPU timing noise
    mb, mh, mr, mdr, mpage, mpp = ((2, 16, 64, 32, 16, 64) if tiny
                                   else (2, 32, 128, 64, 16, 64))
    m_nope, m_v = mr // 2, mr // 2
    mpool = kvc.init_mla_pool(1, mb * mpp, mpage, mr, mdr, "fp8_e4m3")
    mpt = np.zeros((mb, mpp), np.int32)
    mt = mpp * mpage
    ckv_src = jnp.asarray(rng.normal(size=(1, 1, mt, mr)).astype(np.float32))
    kr_src = jnp.asarray(rng.normal(size=(1, 1, mt, mdr)).astype(np.float32))
    for r in range(mb):
        ids = np.arange(r * mpp, (r + 1) * mpp, dtype=np.int32)
        mpt[r] = ids
        mpool = kvc.splice_prefill(mpool, {"ckv": ckv_src, "krope": kr_src},
                                   ids, mt)
    mptj = jnp.asarray(mpt)
    mlens = jnp.full((mb,), mt, jnp.int32)
    mscale = 1.0 / float(m_nope + mdr) ** 0.5
    qn = jnp.asarray(rng.normal(size=(mb, mh, m_nope)).astype(np.float32))
    qr_q = jnp.asarray(rng.normal(size=(mb, mh, mdr)).astype(np.float32))
    wk_b = jnp.asarray(rng.normal(size=(mh, m_nope, mr)).astype(np.float32)
                       * 0.1).astype(jnp.bfloat16)
    wv_b = jnp.asarray(rng.normal(size=(mh, m_v, mr)).astype(np.float32)
                       * 0.1).astype(jnp.bfloat16)
    mstate = kvc.PagedState(mptj, mlens)

    def mla_latent(qn, qr):  # absorbed: q/out fold through wk_b/wv_b
        q_lat = jnp.einsum("bhn,hnr->bhr", qn.astype(jnp.bfloat16), wk_b,
                           preferred_element_type=jnp.float32)
        ctx = kops.paged_mla_decode_attn(q_lat, qr, mpool, 0, mptj, mlens,
                                         mscale)
        return jnp.einsum("bhr,hvr->bhv", ctx.astype(jnp.bfloat16), wv_b,
                          preferred_element_type=jnp.float32)

    def mla_materialized(qn, qr):  # expand pages to per-head K/V, attend
        ckv = kvc.gather_pages(mpool, 0, "ckv", mstate).astype(jnp.bfloat16)
        krope = kvc.gather_pages(mpool, 0, "krope", mstate).astype(jnp.bfloat16)
        k_nope = jnp.einsum("btr,hnr->bthn", ckv, wk_b,
                            preferred_element_type=jnp.float32)
        vh = jnp.einsum("btr,hvr->bthv", ckv, wv_b,
                        preferred_element_type=jnp.float32)
        # a materialized engine holds the expanded per-head K/V as real
        # tensors (that is the thing MLA's absorbed form avoids); the
        # barrier stops XLA from algebraically re-absorbing the expansion
        # into the score contraction and un-materializing the baseline
        k_nope, vh = jax.lax.optimization_barrier((k_nope, vh))
        s = (jnp.einsum("bhn,bthn->bht", qn, k_nope)
             + jnp.einsum("bhd,btd->bht", qr, krope.astype(jnp.float32))
             ) * mscale
        msk = jnp.where(jnp.arange(mt)[None, None] < mlens[:, None, None],
                        0.0, -1e30)
        att = jax.nn.softmax(s + msk, axis=-1)
        return jnp.einsum("bht,bthv->bhv", att, vh)

    prev = kops.get_backend()
    try:
        kops.set_backend("ref")
        f_lat = jax.jit(mla_latent)
        f_mat = jax.jit(mla_materialized)
        # interleaved min-of timing (like the fused-vs-split loop): load
        # noise only ever inflates a wall time, so the per-path minimum is
        # the stable estimator for a >= 1.0x gate on shared runners
        jax.block_until_ready(f_lat(qn, qr_q))
        jax.block_until_ready(f_mat(qn, qr_q))
        ts_lat, ts_mat = [], []
        for _ in range(21 if tiny else 9):
            t0 = time.perf_counter()
            jax.block_until_ready(f_lat(qn, qr_q))
            ts_lat.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            jax.block_until_ready(f_mat(qn, qr_q))
            ts_mat.append(time.perf_counter() - t0)
        t_mla, t_mat = min(ts_lat) * 1e6, min(ts_mat) * 1e6
    finally:
        kops.set_backend(prev)
    rows.append(("kernel/mla_paged_decode", t_mla, t_mat / t_mla))
    rows.append(("kernel/mla_materialized_decode", t_mat, 0.0))

    for name, us, _ in rows:
        print(f"{name:36s} {us:10.1f} us/call")

    payload = {name: us for name, us, _ in rows}
    # explicit speedup keys: the CI benchmark-smoke job fails the build if
    # any of these regresses below 1.0x
    for tag, _m, _n, _k, _r in shapes:
        split = payload[f"kernel/w4a8_split_{tag}"]
        fusedt = payload[f"kernel/w4a8_fused_{tag}"]
        payload[f"speedup/w4a8_fused_{tag}"] = split / fusedt
    payload["speedup/paged_decode_true_ctx"] = (
        payload["kernel/mono_decode_max_seq"]
        / payload["kernel/paged_decode_true_ctx"])
    payload["speedup/mla_paged_decode"] = (
        payload["kernel/mla_materialized_decode"]
        / payload["kernel/mla_paged_decode"])
    out_path = os.path.join(os.path.dirname(__file__), "..", "BENCH_kernels.json")
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
    print(f"[wrote {os.path.normpath(out_path)}]")
    assert not slower, f"fused slower than split on: {slower}"
    return rows


def serving_bench(tiny: bool = False):
    """Long-tail ``max_new`` serving workload: reserve-on-admit vs the
    token-budget scheduler on the same tight FP8 page pool.

    Reserve-on-admit charges worst-case pages (prompt + max_new) up front,
    so one long-tail request blocks slots the short requests could use;
    the token-budget scheduler charges prompt + headroom, grows pages on
    demand and preempts by page steal. Same model, same requests, same
    pool — the only variable is the admission policy, and both schedulers
    produce bit-identical greedy tokens (resume is token-identical), so
    tokens/sec and slot utilization are directly comparable.

    Emits BENCH_serving.json: utilization + tokens/sec per scheduler and
    the ``speedup/serving_tokens_per_sec`` key the serving-smoke CI job
    gates >= 1.0x (plus ``utilization/token_budget >=
    utilization/reserve_worst_case``). Each scheduler is run twice and the
    second (hot jit cache) run is timed, so wall-clock compares steady
    state, not compilation.

    A second workload measures the content-addressed prefix cache: every
    request shares a 64-token system prompt (the dominant shape of heavy
    multi-user traffic), served cold (``prefix_cache=False`` — every
    request re-prefills and re-quantizes the identical K/V) vs warm (full
    scale-frozen prompt pages are shared by refcount; only the per-request
    tail streams through the prefill). Both produce token-identical greedy
    output, so ``speedup/prefix_cache_tokens_per_sec`` isolates the cache;
    the serving-smoke CI job gates it >= 1.0x and the hit rate > 0.
    """
    import json

    from repro import models
    from repro.models.config import ArchConfig
    from repro.runtime.serve import (CachePolicy, Request, SamplingParams,
                                     SchedulerConfig, Server, ServerConfig)

    tiny = tiny or os.environ.get("REPRO_BENCH_TINY") == "1"
    cfg = ArchConfig(
        name="serve-bench", family="dense", n_layers=2, d_model=64, n_heads=4,
        n_kv_heads=2, d_ff=128, vocab_size=128, attn_kind="gqa",
        norm_kind="layernorm", act_kind="relu", mlp_gated=False,
        use_bias=True, pos_embedding="learned", tie_embeddings=True,
        max_position=256, attn_chunk=128,
    )
    params = models.init_params(cfg, jax.random.PRNGKey(0))
    n_req = 10 if tiny else 24
    base_new, tail_new, tail_every = 4, 64, 2
    slots, page, pool_pages = 4, 8, (10 if tiny else 14)
    max_seq = 96 if tiny else 160
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, size=int(n)).tolist()
               for n in rng.integers(4, 9, size=n_req)]
    max_new = [tail_new if i % tail_every == 0 else base_new
               for i in range(n_req)]

    def run(sched):
        srv = Server(params, cfg,
                     ServerConfig(slots=slots, max_seq=max_seq,
                                  cache=CachePolicy(active_fmt="fp8_e4m3"), page_size=page,
                                  pool_pages=pool_pages, a_fmt=None,
                                  scheduler=SchedulerConfig(policy=sched)))
        reqs = [Request(rid=i, prompt=list(p), max_new=mn)
                for i, (p, mn) in enumerate(zip(prompts, max_new))]
        for r in reqs:
            srv.submit(r)
        t0 = time.perf_counter()
        done = srv.run_until_drained()
        dt = time.perf_counter() - t0
        assert len(done) == n_req, (sched, len(done))
        toks = sum(len(r.out) for r in reqs)
        return {"sec": dt, "tokens": toks, "tps": toks / dt,
                "util": srv.utilization(), "steps": srv.stats["steps"],
                "preemptions": srv.stats["preemptions"],
                "resumes": srv.stats["resumes"],
                "failed": srv.stats["failed"],
                "outs": {r.rid: tuple(r.out) for r in reqs}}

    print("\n== serving bench (long-tail max_new, CPU) ==")
    run("reserve")        # warmup: compile every prefill/decode shape
    run("token_budget")

    def timed_best(sched):
        # best-of-2 (min-wall-time) per scheduler: noise only ever inflates
        # wall time, so the min is the stable estimator — keeps the strict
        # in-bench tokens/sec assert from flaking on a loaded CI runner
        a, b = run(sched), run(sched)
        return a if a["tps"] >= b["tps"] else b

    rv = timed_best("reserve")
    tb = timed_best("token_budget")
    assert rv["outs"] == tb["outs"], \
        "schedulers must produce bit-identical greedy tokens"
    for name, r in (("reserve", rv), ("token_budget", tb)):
        print(f"{name:14s} {r['tokens']} tok in {r['sec']:.2f}s = "
              f"{r['tps']:7.1f} tok/s | util {r['util']:.3f} | "
              f"{r['steps']} steps | {r['preemptions']} preemptions")

    # ---- shared-system-prompt workload: cold vs prefix-cached -------------
    # 8 requests sharing a 64-token system prompt; cold re-prefills (and
    # re-quantizes) the identical K/V per request, warm maps the frozen
    # pages by refcount and streams only the tail. Greedy outputs are
    # token-identical (full pages are scale-frozen and the shared prefix
    # is chunk-aligned), so tokens/sec isolates the prefill saved.
    shared = rng.integers(1, cfg.vocab_size, size=64).tolist()
    pprompts = [shared + rng.integers(1, cfg.vocab_size, size=int(t)).tolist()
                for t in rng.integers(4, 8, size=8)]

    def run_prefix(warm):
        srv = Server(params, cfg,
                     ServerConfig(slots=slots, max_seq=96, cache=CachePolicy(active_fmt="fp8_e4m3"),
                                  page_size=8, a_fmt=None, prefix_cache=warm,
                                  scheduler=SchedulerConfig(policy="token_budget")))
        reqs = [Request(rid=i, prompt=list(p), max_new=8)
                for i, p in enumerate(pprompts)]
        for r in reqs:
            srv.submit(r)
        t0 = time.perf_counter()
        done = srv.run_until_drained()
        dt = time.perf_counter() - t0
        assert len(done) == len(reqs)
        toks = sum(len(r.out) for r in reqs)
        return {"sec": dt, "tps": toks / dt,
                "hit_rate": srv.prefix_hit_rate(),
                "hit_tokens": srv.stats["prefix_hit_tokens"],
                "prefill_tokens": srv.stats["prefill_tokens"],
                "outs": {r.rid: tuple(r.out) for r in reqs}}

    run_prefix(False)  # warmup: compile every prefill/decode shape
    run_prefix(True)

    def timed_best_prefix(warm):
        a, b = run_prefix(warm), run_prefix(warm)
        return a if a["tps"] >= b["tps"] else b

    cold = timed_best_prefix(False)
    warm = timed_best_prefix(True)
    assert cold["outs"] == warm["outs"], \
        "prefix cache must not change greedy tokens"
    assert warm["hit_tokens"] == 7 * 64, warm["hit_tokens"]  # all but req 0
    assert cold["hit_tokens"] == 0
    assert warm["prefill_tokens"] == cold["prefill_tokens"] - 7 * 64
    print(f"{'prefix_cold':14s} {cold['sec']:.2f}s = {cold['tps']:7.1f} tok/s"
          f" | hit rate {cold['hit_rate']:.3f}")
    print(f"{'prefix_warm':14s} {warm['sec']:.2f}s = {warm['tps']:7.1f} tok/s"
          f" | hit rate {warm['hit_rate']:.3f} "
          f"({warm['hit_tokens']} prefill tokens saved)")

    # ---- mixed-precision cache policy: packed FP4 frozen prefix pages -----
    # The same warm shared-prefix workload under CachePolicy(frozen_fmt=
    # 'fp4_e2m1'): shared pages are transcoded FP8 -> packed FP4 exactly
    # once, at the freeze point. Gated in-bench: the frozen page class must
    # cost <= 0.55x the active-FP8 bytes-per-token, greedy streams must
    # stay within bounded divergence of the all-FP8 warm run (only the
    # frozen prefix differs in precision), and the drain audit must hold
    # with mixed-format pages live.
    def run_fp4(policy):
        srv = Server(params, cfg,
                     ServerConfig(slots=slots, max_seq=96, cache=policy,
                                  page_size=8, a_fmt=None,
                                  scheduler=SchedulerConfig(policy="token_budget")))
        reqs = [Request(rid=i, prompt=list(p), max_new=8)
                for i, p in enumerate(pprompts)]
        for r in reqs:
            srv.submit(r)
        t0 = time.perf_counter()
        done = srv.run_until_drained()
        dt = time.perf_counter() - t0
        assert len(done) == len(reqs)
        srv.audit()  # mixed-format pages live at drain
        toks = sum(len(r.out) for r in reqs)
        return {"sec": dt, "tps": toks / dt,
                "residency": srv.cache_residency(),
                "frozen_pages": srv.stats["fp4_frozen_pages"],
                "failed": srv.stats["failed"],
                "outs": {r.rid: tuple(r.out) for r in reqs}}

    mixed = CachePolicy(active_fmt="fp8_e4m3", frozen_fmt="fp4_e2m1")
    warm8 = run_fp4(CachePolicy(active_fmt="fp8_e4m3"))
    run_fp4(mixed)  # warmup: compile the mixed-table decode shapes
    a, b = run_fp4(mixed), run_fp4(mixed)
    warm4 = a if a["tps"] >= b["tps"] else b
    assert warm8["outs"] == warm["outs"], \
        "CachePolicy(active_fmt='fp8_e4m3') must reproduce the kv_fmt run"
    r8, r4 = warm8["residency"], warm4["residency"]
    # the page-class density win: frozen FP4 vs active FP8 bytes-per-token
    fp4_density = r4["frozen_bytes_per_token"] / r4["active_bytes_per_token"]
    assert fp4_density <= 0.55, fp4_density
    assert warm4["frozen_pages"] >= len(shared) // 8, warm4["frozen_pages"]
    # blended residency win: tokens held per live byte at drain, fp4 / fp8
    resident_ratio = ((r4["resident_tokens"] / r4["live_bytes"])
                      / (r8["resident_tokens"] / r8["live_bytes"]))
    assert resident_ratio >= 1.0, resident_ratio
    # bounded greedy divergence: only the frozen prefix pages differ in
    # precision, so the bulk of both streams must agree position-wise
    fp4_total = fp4_agree = 0
    for rid in warm8["outs"]:
        for x, y in zip(warm8["outs"][rid], warm4["outs"][rid]):
            fp4_total += 1
            fp4_agree += x == y
    fp4_agreement = fp4_agree / fp4_total
    assert fp4_agreement >= 0.5, (fp4_agreement, warm8["outs"], warm4["outs"])
    print(f"{'frozen_fp4':14s} {warm4['sec']:.2f}s = {warm4['tps']:7.1f} "
          f"tok/s | frozen/active B/token {fp4_density:.3f}x | "
          f"{warm4['frozen_pages']} pages transcoded | greedy agreement "
          f"{fp4_agreement:.2f}")

    # ---- degraded mode: the token-budget workload under injected faults ----
    # Same requests, same pool, plus a deterministic fault schedule: two
    # NaN-poisoned decode rows, the first host spill bit-flipped, one
    # transient allocator-exhaustion tick, and the pool auditor running
    # every 4 decode steps. Gates graceful degradation: exactly the
    # injected requests fail (strict=False), every survivor's greedy
    # tokens match the fault-free run bit-exactly (the corrupted spill
    # recovers through the CRC-verify -> tail re-prefill path), and
    # survivor throughput stays >= 0.8x clean — fault handling must not
    # stall the batch. ``serving/degraded/survivor_tps_ratio`` is
    # deliberately NOT a ``speedup/*`` key: those are gated >= 1.0 by
    # convention, and degraded mode is allowed to cost up to 20%.
    from repro.runtime.serve import FaultPlan

    def run_degraded():
        plan = FaultPlan(seed=0, nan_logits=((6, 0), (9, 2)),
                         corrupt_spills=(0,), alloc_fail_ticks=(12,))
        srv = Server(params, cfg,
                     ServerConfig(slots=slots, max_seq=max_seq,
                                  cache=CachePolicy(active_fmt="fp8_e4m3"), page_size=page,
                                  pool_pages=pool_pages, a_fmt=None,
                                  strict=False, audit_every=4,
                                  scheduler=SchedulerConfig(policy="token_budget")),
                     faults=plan)
        reqs = [Request(rid=i, prompt=list(p), max_new=mn)
                for i, (p, mn) in enumerate(zip(prompts, max_new))]
        for r in reqs:
            srv.submit(r)
        t0 = time.perf_counter()
        srv.run_until_drained()
        dt = time.perf_counter() - t0
        failed = {r.rid for r in reqs if r.status == "failed"}
        assert failed == {rid for (_, _, rid) in plan.nan_hits}, \
            (failed, plan.nan_hits)
        assert len(failed) == len(plan.nan_logits), \
            "every scheduled NaN row must land on a live request"
        assert srv.stats["spill_integrity_failures"] >= 1
        assert plan.corrupted_rids and plan.blocked_ticks == [12]
        for r in reqs:  # survivors are token-identical to the clean run
            if r.rid not in failed:
                assert r.status == "ok" and tuple(r.out) == tb["outs"][r.rid]
        assert srv.audit()["violations"] == 0  # pool whole at drain
        toks = sum(len(r.out) for r in reqs if r.rid not in failed)
        return {"sec": dt, "tokens": toks, "tps": toks / dt,
                "failed": len(failed),
                "integrity": srv.stats["spill_integrity_failures"],
                "injected": len(plan.nan_logits)}

    run_degraded()  # warmup: the audit/fail paths add no new jit shapes
    dga, dgb = run_degraded(), run_degraded()
    dg = dga if dga["tps"] >= dgb["tps"] else dgb
    # clean-run rate over the surviving requests only (generous to clean:
    # its wall clock also produced the failed rids' tokens)
    clean_survivor_tps = dg["tokens"] / tb["sec"]
    degraded_ratio = dg["tps"] / clean_survivor_tps
    print(f"{'degraded':14s} {dg['tokens']} surviving tok in "
          f"{dg['sec']:.2f}s = {dg['tps']:7.1f} tok/s | "
          f"{dg['failed']}/{dg['injected']} injected failures | "
          f"{dg['integrity']} spill integrity event(s) | "
          f"{degraded_ratio:.2f}x clean")
    assert rv["failed"] == 0 and tb["failed"] == 0, \
        "clean path must not fail requests"
    assert degraded_ratio >= 0.8, degraded_ratio

    # ---- sampled mode: the long-tail workload with per-request sampling ----
    # Same requests, same pool, but every request samples
    # (temperature/top-k/top-p, seed = rid). The sampling epilogue is
    # compiled into every decode step (fixed trace — greedy rows pay it
    # too), so this leg measures the marginal cost of *using* it: the
    # in-graph masks + categorical draw, plus whatever schedule drift
    # different sampled tokens cause (shorter/longer page growth). Gated
    # >= 0.9x greedy in CI; deliberately NOT a ``speedup/*`` key (those
    # are gated >= 1.0 by convention, and sampling is allowed to cost up
    # to 10%). Two runs must be token-identical: per-request seeds make
    # sampled serving as reproducible as greedy.
    def run_sampled():
        srv = Server(params, cfg,
                     ServerConfig(slots=slots, max_seq=max_seq,
                                  cache=CachePolicy(active_fmt="fp8_e4m3"), page_size=page,
                                  pool_pages=pool_pages, a_fmt=None,
                                  scheduler=SchedulerConfig(
                                      policy="token_budget")))
        reqs = [Request(rid=i, prompt=list(p), max_new=mn,
                        sampling=SamplingParams(temperature=0.8, top_k=20,
                                                top_p=0.95, seed=i))
                for i, (p, mn) in enumerate(zip(prompts, max_new))]
        for r in reqs:
            srv.submit(r)
        t0 = time.perf_counter()
        done = srv.run_until_drained()
        dt = time.perf_counter() - t0
        assert len(done) == n_req
        toks = sum(len(r.tokens) for r in done)
        return {"sec": dt, "tokens": toks, "tps": toks / dt,
                "outs": {r.rid: r.tokens for r in done}}

    run_sampled()  # warmup (no new shapes; keeps timing symmetric)
    spa, spb = run_sampled(), run_sampled()
    assert spa["outs"] == spb["outs"], \
        "seeded sampling must be run-to-run deterministic"
    sp = spa if spa["tps"] >= spb["tps"] else spb
    assert any(sp["outs"][i] != tb["outs"][i] for i in sp["outs"]), \
        "sampled leg must actually sample (outputs all match greedy)"
    sampled_ratio = sp["tps"] / tb["tps"]
    print(f"{'sampled':14s} {sp['tokens']} tok in {sp['sec']:.2f}s = "
          f"{sp['tps']:7.1f} tok/s | {sampled_ratio:.2f}x greedy")
    assert sampled_ratio >= 0.9, sampled_ratio

    # ---- mixed-engine leg: long prompts, short decodes --------------------
    # The chunked-prefill piggyback's target workload: prompts several
    # pages long, a handful of decode tokens each. The alternating engine
    # burns whole programs on prefill chunks while every decode row
    # waits; the mixed engine carries the chunk on the decode step, so
    # its decoded-tokens-per-program-slot (``Server.engine_utilization``)
    # must be strictly higher — the CI-gated claim
    # (``serving/mixed/engine_utilization`` >
    # ``serving/alternating/engine_utilization``). Greedy tokens are
    # asserted bit-identical: the fused step changes scheduling, never
    # numerics.
    mprompts = [rng.integers(1, cfg.vocab_size, size=int(t)).tolist()
                for t in rng.integers(20, 33, size=(8 if tiny else 12))]

    def run_engine(engine):
        srv = Server(params, cfg,
                     ServerConfig(slots=slots, max_seq=max_seq,
                                  cache=CachePolicy(active_fmt="fp8_e4m3"),
                                  page_size=page, a_fmt=None,
                                  scheduler=SchedulerConfig(
                                      policy="token_budget", engine=engine,
                                      prefill_token_budget=2 * page)))
        assert srv.engine == engine
        reqs = [Request(rid=i, prompt=list(p), max_new=4)
                for i, p in enumerate(mprompts)]
        for r in reqs:
            srv.submit(r)
        t0 = time.perf_counter()
        done = srv.run_until_drained()
        dt = time.perf_counter() - t0
        assert len(done) == len(reqs)
        toks = sum(len(r.out) for r in reqs)
        return {"sec": dt, "tps": toks / dt,
                "eu": srv.engine_utilization(),
                "programs": srv.stats["programs"],
                "prefill_tokens": srv.stats["prefill_tokens"],
                "outs": {r.rid: tuple(r.out) for r in reqs}}

    run_engine("alternating")  # warmup: dedicated chunk + decode programs
    run_engine("mixed")        # warmup: the fused chunk+decode family
    ea, eb = run_engine("alternating"), run_engine("alternating")
    alt = ea if ea["tps"] >= eb["tps"] else eb
    ma, mb = run_engine("mixed"), run_engine("mixed")
    mx = ma if ma["tps"] >= mb["tps"] else mb
    assert mx["outs"] == alt["outs"], \
        "mixed engine must produce bit-identical greedy tokens"
    assert mx["prefill_tokens"] == alt["prefill_tokens"]
    for name, r in (("alternating", alt), ("mixed", mx)):
        print(f"{'engine_' + name:14s} {r['sec']:.2f}s = {r['tps']:7.1f} "
              f"tok/s | {r['programs']} programs | engine util "
              f"{r['eu']:.3f}")
    assert mx["eu"] > alt["eu"], (mx["eu"], alt["eu"])

    # ---- Poisson-arrival leg: TTFT / inter-token latency ------------------
    # The drained legs measure throughput with every request queued up
    # front; real traffic arrives over time and cares about time-to-first-
    # token and inter-token latency. Clients submit into the *running*
    # scheduler through the asyncio front-end with exponential
    # inter-arrival gaps (deterministic seed), and every token's host
    # timestamp comes from the engine's decode loop (RequestResult
    # token_times -> ttft/itl). p50/p95 land in BENCH_serving.json for
    # BOTH engines (``serving/poisson/*`` is the mixed default,
    # ``serving/poisson_alternating/*`` the baseline); CI gates
    # presence, not values — wall-clock latency on a shared runner is
    # not a stable regression signal, but the keys vanishing is.
    import asyncio

    from repro.runtime.frontend import AsyncServer

    def run_poisson(engine):
        starts = np.cumsum(np.random.default_rng(7).exponential(
            scale=0.01, size=n_req))

        async def client(front, rid, delay):
            await asyncio.sleep(delay)
            async for _ in front.generate(
                    list(prompts[rid]), max_new=max_new[rid],
                    sampling=SamplingParams(temperature=0.8, top_k=20,
                                            top_p=0.95, seed=rid),
                    rid=rid):
                pass
            return front.result(rid)

        async def main():
            srv = Server(params, cfg,
                         ServerConfig(slots=slots, max_seq=max_seq,
                                      cache=CachePolicy(active_fmt="fp8_e4m3"), page_size=page,
                                      pool_pages=pool_pages, a_fmt=None,
                                      scheduler=SchedulerConfig(
                                          policy="token_budget",
                                          engine=engine)))
            front = AsyncServer(srv)
            t0 = time.perf_counter()
            results = await asyncio.gather(*[
                client(front, i, float(starts[i])) for i in range(n_req)])
            dt = time.perf_counter() - t0
            await front.close()
            return results, dt

        results, dt = asyncio.run(main())
        assert all(r is not None and r.ok for r in results)
        ttft = np.asarray([r.ttft for r in results]) * 1e3
        itl = np.asarray([g for r in results for g in r.itl]) * 1e3
        toks = sum(len(r.tokens) for r in results)
        return {"sec": dt, "tps": toks / dt,
                "ttft_ms_p50": float(np.percentile(ttft, 50)),
                "ttft_ms_p95": float(np.percentile(ttft, 95)),
                "itl_ms_p50": float(np.percentile(itl, 50)),
                "itl_ms_p95": float(np.percentile(itl, 95))}

    run_poisson("mixed")  # warmup: first async run pays residual compiles
    poa, pob = run_poisson("mixed"), run_poisson("mixed")
    po = poa if poa["tps"] >= pob["tps"] else pob
    run_poisson("alternating")
    ala, alb = run_poisson("alternating"), run_poisson("alternating")
    poalt = ala if ala["tps"] >= alb["tps"] else alb
    for name, r in (("poisson", po), ("poisson_alt", poalt)):
        print(f"{name:14s} {r['sec']:.2f}s = {r['tps']:7.1f} tok/s | "
              f"TTFT p50 {r['ttft_ms_p50']:.1f}ms "
              f"p95 {r['ttft_ms_p95']:.1f}ms"
              f" | ITL p50 {r['itl_ms_p50']:.1f}ms "
              f"p95 {r['itl_ms_p95']:.1f}ms")

    payload = {
        "serving/tokens_per_sec/reserve": rv["tps"],
        "serving/tokens_per_sec/token_budget": tb["tps"],
        "utilization/reserve_worst_case": rv["util"],
        "utilization/token_budget": tb["util"],
        "serving/steps/reserve": float(rv["steps"]),
        "serving/steps/token_budget": float(tb["steps"]),
        "serving/preemptions/token_budget": float(tb["preemptions"]),
        "serving/resumes/token_budget": float(tb["resumes"]),
        "speedup/serving_tokens_per_sec": tb["tps"] / rv["tps"],
        "serving/tokens_per_sec/prefix_cold": cold["tps"],
        "serving/tokens_per_sec/prefix_warm": warm["tps"],
        "prefix_cache/hit_rate": warm["hit_rate"],
        "prefix_cache/prefill_tokens_saved": float(warm["hit_tokens"]),
        "speedup/prefix_cache_tokens_per_sec": warm["tps"] / cold["tps"],
        "serving/failed/clean": float(rv["failed"] + tb["failed"]
                                      + warm8["failed"] + warm4["failed"]),
        "serving/fp4/bytes_per_token_ratio": fp4_density,
        "serving/fp4/resident_tokens_ratio": resident_ratio,
        "serving/fp4/warm_tps": warm4["tps"],
        "serving/fp4/greedy_agreement": fp4_agreement,
        "serving/fp4/frozen_pages_transcoded": float(warm4["frozen_pages"]),
        "serving/degraded/injected_faults": float(dg["injected"]),
        "serving/degraded/failed": float(dg["failed"]),
        "serving/degraded/spill_integrity_failures": float(dg["integrity"]),
        "serving/degraded/survivor_tps_ratio": degraded_ratio,
        "serving/tokens_per_sec/sampled": sp["tps"],
        "serving/sampling/tps_ratio_vs_greedy": sampled_ratio,
        "serving/poisson/tokens_per_sec": po["tps"],
        "serving/poisson/ttft_ms_p50": po["ttft_ms_p50"],
        "serving/poisson/ttft_ms_p95": po["ttft_ms_p95"],
        "serving/poisson/itl_ms_p50": po["itl_ms_p50"],
        "serving/poisson/itl_ms_p95": po["itl_ms_p95"],
        "serving/poisson_alternating/tokens_per_sec": poalt["tps"],
        "serving/poisson_alternating/ttft_ms_p50": poalt["ttft_ms_p50"],
        "serving/poisson_alternating/ttft_ms_p95": poalt["ttft_ms_p95"],
        "serving/poisson_alternating/itl_ms_p50": poalt["itl_ms_p50"],
        "serving/poisson_alternating/itl_ms_p95": poalt["itl_ms_p95"],
        "serving/mixed/engine_utilization": mx["eu"],
        "serving/alternating/engine_utilization": alt["eu"],
        "serving/mixed/tokens_per_sec": mx["tps"],
        "serving/alternating/tokens_per_sec": alt["tps"],
        "serving/mixed/programs": float(mx["programs"]),
        "serving/alternating/programs": float(alt["programs"]),
    }
    out_path = os.path.join(os.path.dirname(__file__), "..", "BENCH_serving.json")
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
    print(f"[wrote {os.path.normpath(out_path)}]")

    rows = [
        ("serving/step_reserve", rv["sec"] / rv["steps"] * 1e6, rv["tps"]),
        ("serving/step_token_budget", tb["sec"] / tb["steps"] * 1e6, tb["tps"]),
        ("serving/prefix_cold", cold["sec"] * 1e6, cold["tps"]),
        ("serving/prefix_warm", warm["sec"] * 1e6, warm["tps"]),
        ("serving/prefix_warm_fp4", warm4["sec"] * 1e6, warm4["tps"]),
        ("serving/engine_mixed", mx["sec"] * 1e6, mx["tps"]),
        ("serving/engine_alternating", alt["sec"] * 1e6, alt["tps"]),
    ]
    # the paper-level claim this PR gates in CI: on-demand paging converts
    # FP8's bytes-per-token win into strictly more concurrent work
    assert tb["util"] > rv["util"], (tb["util"], rv["util"])
    assert tb["tps"] > rv["tps"], (tb["tps"], rv["tps"])
    # ... and frozen-scale pages are bit-reusable: sharing them can only
    # remove prefill work, never slow the engine down
    assert warm["hit_rate"] > 0.0
    assert warm["tps"] >= cold["tps"], (warm["tps"], cold["tps"])
    return rows


_SHARDED_SCRIPT = r'''
import json, os, sys, time

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")
import jax  # noqa: E402  (flags must be set before the backend inits)
import numpy as np  # noqa: E402

from repro import models
from repro.models.config import ArchConfig
from repro.runtime.serve import (CachePolicy, MeshPlan, Request,
                                 SchedulerConfig, Server, ServerConfig)

tiny = os.environ.get("REPRO_BENCH_TINY") == "1"
cfg = ArchConfig(
    name="serve-bench", family="dense", n_layers=2, d_model=64, n_heads=4,
    n_kv_heads=2, d_ff=128, vocab_size=128, attn_kind="gqa",
    norm_kind="layernorm", act_kind="relu", mlp_gated=False,
    use_bias=True, pos_embedding="learned", tie_embeddings=True,
    max_position=256, attn_chunk=128,
)
params = models.init_params(cfg, jax.random.PRNGKey(0))
n_req = 8 if tiny else 16
rng = np.random.default_rng(0)
prompts = [rng.integers(1, cfg.vocab_size, size=int(n)).tolist()
           for n in rng.integers(4, 9, size=n_req)]


def run(plan):
    srv = Server(params, cfg,
                 ServerConfig(slots=4, max_seq=64,
                              cache=CachePolicy(active_fmt="fp8_e4m3"),
                              page_size=8, pool_pages=12, a_fmt=None,
                              mesh=plan))
    for i, p in enumerate(prompts):
        srv.submit(Request(rid=i, prompt=list(p), max_new=8))
    t0 = time.perf_counter()
    done = srv.run_until_drained()
    dt = time.perf_counter() - t0
    assert len(done) == n_req, len(done)
    assert all(r.status == "ok" for r in done), [r.status for r in done]
    toks = sum(len(r.tokens) for r in done)
    return {"sec": dt, "tps": toks / dt,
            "outs": {r.rid: list(r.tokens) for r in done},
            "residency": srv.shard_residency()}


def best(plan):
    # best-of-2 (hot jit cache): noise only inflates wall time
    a, b = run(plan), run(plan)
    return a if a["tps"] >= b["tps"] else b


run(None)                                # warmup: compile single-device
single = best(None)
run(MeshPlan(data=1, model=2))           # warmup: compile sharded
sharded = best(MeshPlan(data=1, model=2))
agree = float(single["outs"] == sharded["outs"])
print(json.dumps({
    "devices": 2.0,
    "tokens_per_sec": sharded["tps"],
    "tokens_per_sec_single": single["tps"],
    "tps_ratio_vs_single": sharded["tps"] / single["tps"],
    "greedy_agreement": agree,
    "residency_devices": float(len(sharded["residency"])),
    "residency_min_bytes": float(min(sharded["residency"].values())),
    "residency_max_bytes": float(max(sharded["residency"].values())),
}))
'''


def sharded_serving_bench(tiny: bool = False):
    """Tensor-parallel serving leg: the same tiny GQA workload served by
    the single-device engine vs a ``MeshPlan(data=1, model=2)`` mesh of
    simulated host devices (KV pages + decode attention sharded by head).

    Runs in a subprocess because ``--xla_force_host_platform_device_count``
    must be set before the JAX backend initializes — the parent process
    has already committed to one device. Merges ``serving/sharded/*``
    keys into BENCH_serving.json (read-modify-write: ``serving_bench``
    writes the file wholesale, so this leg must not clobber it) for the
    serving-sharded-smoke CI job, which gates greedy agreement == 1.0
    and per-shard residency spread across both model shards.

    On CPU the sharded leg is expected to be *slower* than single-device
    (shard_map overhead with no real parallel hardware); the tracked
    claim is token identity + balanced residency, not CPU tokens/sec.
    """
    import json
    import subprocess
    import tempfile

    tiny = tiny or os.environ.get("REPRO_BENCH_TINY") == "1"
    root = os.path.normpath(os.path.join(os.path.dirname(__file__), ".."))
    with tempfile.NamedTemporaryFile("w", suffix=".py", delete=False) as f:
        f.write(_SHARDED_SCRIPT)
        script = f.name
    # a simulated-CPU-mesh measurement by design: the child never touches
    # an accelerator, which on a TPU host stays with this (parent) process
    env = dict(os.environ, PYTHONPATH=os.path.join(root, "src"),
               JAX_PLATFORMS="cpu")
    if tiny:
        env["REPRO_BENCH_TINY"] = "1"
    print("\n== sharded serving bench (2 simulated devices, CPU) ==")
    proc = subprocess.run([sys.executable, script], env=env, cwd=root,
                          capture_output=True, text=True, timeout=900)
    os.unlink(script)
    if proc.returncode != 0:
        raise RuntimeError(f"sharded bench subprocess failed:\n{proc.stderr[-2000:]}")
    res = json.loads(proc.stdout.strip().splitlines()[-1])

    out_path = os.path.join(root, "BENCH_serving.json")
    payload = {}
    if os.path.exists(out_path):
        with open(out_path) as f:
            payload = json.load(f)
    payload.update({f"serving/sharded/{k}": v for k, v in res.items()})
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
    print(f"[wrote {os.path.normpath(out_path)}]")
    print(f"{'sharded(1x2)':14s} {res['tokens_per_sec']:7.1f} tok/s | "
          f"single {res['tokens_per_sec_single']:7.1f} tok/s | "
          f"ratio {res['tps_ratio_vs_single']:.2f}x | "
          f"residency {int(res['residency_devices'])} devices "
          f"[{int(res['residency_min_bytes'])}, "
          f"{int(res['residency_max_bytes'])}] bytes")

    # the claims the serving-sharded-smoke CI job gates: sharded greedy
    # decode is token-identical, and pool bytes actually land on both
    # model shards (balanced within the uint8-codes asymmetry slack)
    assert res["greedy_agreement"] == 1.0, "sharded tokens diverged"
    assert res["residency_devices"] >= 2.0, res
    assert res["residency_min_bytes"] > 0.0, res
    return [("serving/sharded_tps", 0.0, res["tokens_per_sec"]),
            ("serving/sharded_ratio", 0.0, res["tps_ratio_vs_single"])]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="substring filter of benchmarks")
    ap.add_argument("--skip-tables", action="store_true",
                    help="skip the (slow) trained-model paper tables")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from . import paper_tables as pt
    from .roofline_table import roofline_table

    benches = [
        ("fig2", pt.fig2_outlier_vector),
        ("fig1", pt.fig1_activation_stats),
        ("table1", pt.table1_act_quant),
        ("table2", pt.table2_quant_matrix),
        ("table3", pt.table3_scale_constraints),
        ("tableA1", pt.table_a1_fp4_formats),
        ("roofline", roofline_table),
        ("kernels", kernel_microbench),
        ("serving", serving_bench),
        ("sharded", sharded_serving_bench),
    ]
    slow = {"fig1", "table1", "table2", "table3", "tableA1"}

    rows = []
    failures = []
    for name, fn in benches:
        if args.only and args.only not in name:
            continue
        if args.skip_tables and name in slow:
            continue
        t0 = time.time()
        try:
            rows.extend(fn() or [])
            print(f"[{name} done in {time.time() - t0:.0f}s]")
        except AssertionError as e:  # directional-claim violation
            failures.append((name, str(e)))
            print(f"[{name} CLAIM FAILED: {e}]")
        except Exception as e:  # noqa: BLE001
            failures.append((name, f"{type(e).__name__}: {e}"))
            print(f"[{name} ERROR: {e}]")

    print("\nname,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.2f},{derived:.6g}")
    if failures:
        print(f"\n{len(failures)} benchmark(s) failed: {failures}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
