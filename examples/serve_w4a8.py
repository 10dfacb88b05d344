"""Batched W4A8 serving with the packed deployment checkpoint.

    PYTHONPATH=src python examples/serve_w4a8.py [--backend pallas_interpret]

Loads (or trains) the benchmark model, packs it to the W4A8 deployment form
(FP4-E2M1 nibbles + M2 pow-2 scales + LoRC factors), then serves a stream of
batched requests through the continuous-batching engine. ``--backend
pallas_interpret`` executes every quantized matmul through the Pallas TPU
kernel in interpret mode (slow on CPU; bit-identical quantization).

``--families`` additionally serves the whisper-tiny enc-dec config (write-
once cross-attention pages) and the minicpm3 MLA config (latent decode
kernel) through the same paged FP8 engine, asserting each request's greedy
tokens are identical to the legacy contiguous-cache decode path.

``--shared-prefix N`` prepends an N-token shared system prompt to every
request: after the first request freezes its full prompt pages, every
later request maps them straight from the content-addressed prefix cache
(refcount++, zero prefill compute) and streams only its own tail. Compare
against ``--no-prefix-cache`` to see the cold-engine cost.

``--frozen-kv-fmt fp4_e2m1`` (with ``--shared-prefix``) switches the
cache policy to mixed precision: shared pages are transcoded FP8 ->
packed FP4 E2M1 exactly once at the moment the prefix cache freezes
them, roughly halving the bytes-per-token of prefix residency. The
drain prints live page counts per format and the density ratio.

``--temperature T`` (with ``--top-k/--top-p/--seed``) switches every
request from greedy argmax to in-graph seeded sampling — same compiled
decode step, per-row fixed-trace masks, reproducible run-to-run.

``--inject-faults SEED`` serves the same workload through a seeded
deterministic fault schedule (a NaN-poisoned decode row, a bit-flipped
host spill, a transient allocator stall): exactly the poisoned requests
end ``status='failed'``, the tampered spill is caught by its CRC and
re-prefilled, and everything else finishes untouched. ``--audit-every N``
runs the pool-ownership auditor every N decode steps; the drain always
ends with an audit, so a broken pool invariant fails loudly.

``--mesh N`` serves through a ``(data=1, model=N)`` device mesh (simulated
host devices on CPU): KV pages, their scales and the decode attention are
sharded by head across the N model shards while the host scheduler stays
a single brain. Greedy tokens are identical to ``--mesh 1`` and the drain
prints per-shard page residency next to the per-format residency stats.
"""
import argparse
import os
import sys
import time
from collections import Counter

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

# --mesh N shards the engine over N simulated host devices; the XLA flag
# must be set before the backend initializes, and the repro imports below
# pull in jax — so pre-scan argv here, ahead of argparse
if any(a == "--mesh" or a.startswith("--mesh=") for a in sys.argv[1:]):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")

import numpy as np

from repro import models
from repro.core.policy import QuantPolicy
from repro.core.ptq import quantize_tree
from repro.kernels import ops
from repro.runtime.serve import (CachePolicy, FaultPlan, MeshPlan, Request,
                                 SamplingParams, SchedulerConfig, Server,
                                 ServerConfig)

from benchmarks.common import BENCH_CFG, trained_params


def _train_smoke(cfg, tag, steps=150, with_frames=False):
    """Briefly train a smoke config (cached in .bench_cache) so greedy
    logit gaps are decisive and fp8-vs-legacy token identity is meaningful."""
    import jax
    import jax.numpy as jnp

    from repro.checkpoint.manager import latest_step, restore, save
    from repro.data.pipeline import DataConfig, SyntheticLM
    from repro.launch.steps import TrainState, make_train_step
    from repro.optimizer import AdamWConfig, adamw_init

    from benchmarks.common import CACHE

    ckpt = os.path.join(CACHE, f"{tag}_{steps}")
    init = models.init_params(cfg, jax.random.PRNGKey(0))
    if latest_step(ckpt) is not None:
        return restore(ckpt, init)
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=24, global_batch=8,
                    seed=7)
    oc = AdamWConfig(lr=6e-3, warmup=20, total_steps=steps)
    state = TrainState(params=init, opt=adamw_init(init, oc))
    step_fn = jax.jit(make_train_step(cfg, oc), donate_argnums=(0,))
    data = SyntheticLM(dc)
    frng = np.random.default_rng(11)
    for step in range(steps):
        b = dict(data.batch(step))
        if with_frames:
            b["frames"] = jnp.asarray(frng.normal(
                size=(dc.global_batch, cfg.encoder_seq, cfg.d_model)
            ).astype(np.float32))
        state, _ = step_fn(state, b)
    save(ckpt, steps, state.params)
    return state.params


def _greedy_legacy(params, cfg, prompt, max_new, max_seq, frames=None):
    import jax.numpy as jnp

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


def serve_families(backend):
    """Whisper-tiny (enc-dec cross pages) and minicpm3 (MLA latent decode)
    through the paged FP8 engine, token-identical to the legacy decode."""
    from repro.configs import get_smoke

    rng = np.random.default_rng(0)
    for arch, tag in (("whisper-tiny", "whisper_smoke"),
                      ("minicpm3-4b", "mla_smoke")):
        cfg = get_smoke(arch)
        encdec = cfg.encoder_layers > 0
        params = _train_smoke(cfg, tag, with_frames=encdec)
        srv = Server(params, cfg,
                     ServerConfig(slots=3, max_seq=64,
                                  cache=CachePolicy(active_fmt="fp8_e4m3"),
                                  page_size=8, kernel_backend=backend,
                                  a_fmt=None))
        reqs = []
        for rid in range(3):
            prompt = rng.integers(1, cfg.vocab_size,
                                  size=rng.integers(3, 10)).tolist()
            frames = (rng.normal(size=(cfg.encoder_seq, cfg.d_model))
                      .astype(np.float32) if encdec else None)
            r = Request(rid=rid, prompt=prompt, max_new=6, frames=frames)
            reqs.append(r)
            srv.submit(r)
        srv.run_until_drained()
        for r in reqs:
            ref = _greedy_legacy(params, cfg, r.prompt, 6, 64, r.frames)
            assert r.out == ref, (arch, r.rid, r.out, ref)
        extra = (f", cross pages for {cfg.encoder_seq} encoder frames"
                 if encdec else ", latent decode kernel path")
        print(f"{arch}: {len(reqs)} requests through the paged FP8 engine"
              f"{extra}; greedy tokens identical to the legacy decode")
        for r in reqs[:2]:
            print(f"  req {r.rid}: {r.prompt} -> {r.out}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default="ref",
                    choices=["ref", "pallas", "pallas_interpret"])
    ap.add_argument("--kv-fmt", default="fp8_e4m3", choices=["fp8_e4m3", "bf16"],
                    help="active KV page payload: packed FP8 codes with "
                         "per-(page, head) M2 scales, or bf16 (fallback)")
    ap.add_argument("--frozen-kv-fmt", default="none",
                    choices=["none", "fp4_e2m1"],
                    help="frozen (prefix-cache-registered) page payload: "
                         "'fp4_e2m1' transcodes each shared page FP8 -> "
                         "packed FP4 exactly once at the freeze point "
                         "(needs --shared-prefix and FP8 --kv-fmt); 'none' "
                         "keeps frozen pages in the active format")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=3)
    ap.add_argument("--scheduler", default="token_budget",
                    choices=["reserve", "token_budget"],
                    help="admission policy: reserve-on-admit (worst-case "
                         "pages up front) or token-budget (prompt pages + "
                         "headroom, on-demand growth, page-steal preemption)")
    ap.add_argument("--engine", default="mixed",
                    choices=["mixed", "alternating"],
                    help="engine step shape: 'mixed' piggybacks one "
                         "request's next prefill chunk onto every decode "
                         "step (one fused program, decode rows never "
                         "stall); 'alternating' runs dedicated prefill "
                         "and decode programs (the legacy baseline)")
    ap.add_argument("--prefill-token-budget", type=int, default=0,
                    help="max prompt tokens piggybacked per mixed step "
                         "(rounded down to a page multiple; 0 = the "
                         "prefill-chunk default). Smaller = smoother "
                         "decode latency, larger = faster prompt "
                         "ingestion")
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy argmax, the "
                         "default; > 0 samples in-graph with the "
                         "fixed-trace top-k/top-p masks)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="keep only the k most likely tokens before "
                         "sampling (0 = disabled)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling: keep the smallest probability "
                         "mass >= p (1.0 = disabled)")
    ap.add_argument("--seed", type=int, default=0,
                    help="per-request RNG seed base; request rid uses "
                         "seed+rid so streams differ but each is "
                         "reproducible run-to-run")
    ap.add_argument("--max-new-tail", type=int, default=0,
                    help="long-tail workload: every third request gets this "
                         "max_new instead of --max-new (0 = uniform). "
                         "Reproduces the serving benchmark's long-tail mix")
    ap.add_argument("--pool-pages", type=int, default=0,
                    help="page-pool capacity (0 = fully backed slots); set "
                         "it tight to watch the token-budget scheduler "
                         "preempt by page steal")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="prepend a shared system prompt of this many "
                         "tokens to every request — full scale-frozen "
                         "pages of it are served from the content-"
                         "addressed prefix cache (refcounted, zero "
                         "prefill compute) after the first request")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable the shared-prefix page cache (cold "
                         "baseline for --shared-prefix)")
    ap.add_argument("--families", action="store_true",
                    help="also serve the whisper-tiny enc-dec and minicpm3 "
                         "MLA smoke configs through the paged FP8 engine "
                         "(asserts token identity vs the legacy decode)")
    ap.add_argument("--inject-faults", type=int, default=0, metavar="SEED",
                    help="draw a seeded FaultPlan (NaN decode row, "
                         "corrupted spill, transient allocator stall) and "
                         "serve through it: exactly the poisoned requests "
                         "fail, everyone else is unaffected (0 = off)")
    ap.add_argument("--audit-every", type=int, default=0, metavar="N",
                    help="run the pool-ownership auditor every N decode "
                         "steps (raises PoolCorruptionError with a state "
                         "dump on any broken invariant; 0 = off)")
    ap.add_argument("--mesh", type=int, default=1, metavar="N",
                    help="shard the engine over a (1, N) device mesh: KV "
                         "pages + decode attention split by head across N "
                         "model-axis shards (simulated host devices on "
                         "CPU); greedy tokens stay identical to --mesh 1 "
                         "and the drain prints per-shard page residency")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.families:
        serve_families(None if args.backend == "ref" else args.backend)
        return

    params = trained_params()
    policy = QuantPolicy(w_fmt="fp4_e2m1", a_fmt="fp8_e4m3", scale_mode="m2",
                        lorc_rank=8)
    packed = quantize_tree(params, models.build_def(BENCH_CFG), policy)

    # deployment footprint
    import jax

    dense_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    packed_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(packed))
    print(f"checkpoint: {dense_bytes/2**20:.1f} MiB dense -> "
          f"{packed_bytes/2**20:.1f} MiB packed W4A8 "
          f"({dense_bytes/packed_bytes:.2f}x smaller)")

    rng = np.random.default_rng(0)
    # 'pallas' routes every PackedLinear matmul through the fused single-pass
    # W4A8 kernel, compiled for the TPU; 'pallas_interpret' runs the same
    # kernels under the Pallas interpreter on any platform
    kv_fmt = None if args.kv_fmt == "bf16" else args.kv_fmt
    frozen_fmt = None if args.frozen_kv_fmt == "none" else args.frozen_kv_fmt
    if frozen_fmt and not args.shared_prefix:
        ap.error("--frozen-kv-fmt needs --shared-prefix: frozen FP4 pages "
                 "only ever hold prefix-cache-registered pages")
    cache = CachePolicy(active_fmt=kv_fmt, frozen_fmt=frozen_fmt)
    page_size = 16 if args.shared_prefix else 32
    plan = None
    if args.inject_faults:
        # draw faults inside the first half of the workload's decode-step
        # span so they land while every slot is still busy
        n_tail = (args.requests + 2) // 3 if args.max_new_tail else 0
        total = (n_tail * args.max_new_tail
                 + (args.requests - n_tail) * args.max_new)
        span = max(4, total // max(1, args.slots) // 2)
        plan = FaultPlan.seeded(args.inject_faults, slots=args.slots,
                                max_step=span)
        print(f"fault schedule (seed {args.inject_faults}): "
              f"NaN rows at {plan.nan_logits}, corrupt spill ordinals "
              f"{plan.corrupt_spills}, allocator blanked on ticks "
              f"{plan.alloc_fail_ticks}")
    mesh_plan = MeshPlan(data=1, model=args.mesh) if args.mesh > 1 else None
    server = Server(packed, BENCH_CFG,
                    ServerConfig(slots=args.slots, max_seq=96,
                                 kernel_backend=args.backend, cache=cache,
                                 page_size=page_size,
                                 pool_pages=args.pool_pages or None,
                                 prefix_cache=not args.no_prefix_cache,
                                 strict=False, audit_every=args.audit_every,
                                 scheduler=SchedulerConfig(
                                     policy=args.scheduler,
                                     engine=args.engine,
                                     prefill_token_budget=(
                                         args.prefill_token_budget or None)),
                                 mesh=mesh_plan),
                    faults=plan)
    frozen_note = (f" + frozen {args.frozen_kv_fmt}" if frozen_fmt else "")
    mesh_note = (f"; mesh=(1, {args.mesh}) — KV heads split over "
                 f"{args.mesh} model shards" if mesh_plan else "")
    print(f"kv cache: paged {args.kv_fmt}{frozen_note}, "
          f"{server.kv_bytes_per_token():.0f} B/token "
          f"(bf16 baseline {server.kv_bf16_bytes_per_token():.0f} B/token); "
          f"scheduler={args.scheduler}{mesh_note}")
    shared = (rng.integers(1, BENCH_CFG.vocab_size,
                           size=args.shared_prefix).tolist()
              if args.shared_prefix else [])
    if args.temperature > 0:
        print(f"sampling: temperature={args.temperature}, "
              f"top_k={args.top_k}, top_p={args.top_p}, "
              f"seed base {args.seed} (+rid per request)")
    reqs = []
    for rid in range(args.requests):
        prompt = shared + rng.integers(1, BENCH_CFG.vocab_size,
                                       size=rng.integers(3, 10)).tolist()
        max_new = args.max_new
        if args.max_new_tail and rid % 3 == 0:
            max_new = args.max_new_tail
        sp = SamplingParams(temperature=args.temperature, top_k=args.top_k,
                            top_p=args.top_p, seed=args.seed + rid)
        r = Request(rid=rid, prompt=prompt, max_new=max_new, sampling=sp)
        reqs.append(r)
        server.submit(r)

    t0 = time.time()
    steps = 0
    while True:
        went = server.step()
        steps += 1
        if steps > 2000:
            break
        if not went:
            if server.queue or server.preempted:
                continue  # deferred admission (e.g. injected alloc stall)
            break
    dt = time.time() - t0
    done = sum(r.done for r in reqs)
    toks = sum(len(r.out) for r in reqs)
    by_status = Counter(r.status for r in reqs)
    status = ", ".join(f"{n} {s}" for s, n in sorted(by_status.items()))
    print(f"served {done}/{len(reqs)} requests, {toks} tokens in {dt:.1f}s "
          f"({steps} engine steps, backend={args.backend}; {status})")
    st = server.stats
    n_steps = max(st["steps"], 1)
    print(f"engine={server.engine}: {st['prefill_tokens']} prefill + "
          f"{st['decoded_tokens']} decode tokens across {st['programs']} "
          f"jitted programs; per-step mix "
          f"{st['prefill_tokens'] / n_steps:.1f} prefill / "
          f"{st['decoded_tokens'] / n_steps:.1f} decode tokens, "
          f"engine utilization {server.engine_utilization():.3f}")
    print(f"slot utilization {server.utilization():.3f}, "
          f"{server.stats['preemptions']} preemptions / "
          f"{server.stats['resumes']} resumes "
          f"({server.stats['pages_stolen']} pages stolen), "
          f"{server.stats['truncated']} truncated at max_seq")
    print(f"prefix cache: {server.stats['prefix_hit_tokens']} prompt tokens "
          f"served from shared pages ({server.prefix_hit_rate():.1%} hit "
          f"rate, {server.stats['prefix_hit_pages']} page hits, "
          f"{server.stats['prefix_reclaims']} reclaims)")
    if plan is not None:
        hit_rids = sorted(rid for (_, _, rid) in plan.nan_hits)
        failed = sorted(r.rid for r in reqs if r.status == "failed")
        print(f"fault injection landed: NaN rows hit requests {hit_rids}, "
              f"spills tampered for rids "
              f"{sorted(plan.corrupted_rids + plan.dropped_rids)} "
              f"({server.stats['spill_integrity_failures']} caught by CRC), "
              f"allocator blanked on ticks {plan.blocked_ticks}")
        assert failed == hit_rids, (failed, hit_rids)
        for r in reqs:
            if r.status == "failed":
                print(f"  req {r.rid} quarantined: {r.error}")
    summary = server.audit()  # raises PoolCorruptionError if anything broke
    print(f"pool audit clean at drain: {summary['pages_mapped']} mapped / "
          f"{summary['pages_free']} free / {summary['pages_parked']} parked "
          f"pages, {summary['slabs_free']} slabs free")
    resid = server.cache_residency()
    print(f"page residency: {resid['n_active_live']} live "
          f"{args.kv_fmt} pages ({resid['active_bytes_per_token']:.0f} "
          f"B/token) + {resid['n_frozen_live']} live frozen pages "
          f"({resid['frozen_bytes_per_token']:.0f} B/token)")
    if frozen_fmt:
        ratio = (resid["frozen_bytes_per_token"]
                 / resid["active_bytes_per_token"])
        print(f"  {server.stats['fp4_frozen_pages']} pages transcoded "
              f"FP8 -> packed FP4 at freeze; frozen/active page density "
              f"{ratio:.2f}x")
    if mesh_plan is not None:
        per = server.shard_residency()
        detail = ", ".join(f"{dev}: {b / 2**10:.1f} KiB"
                           for dev, b in per.items())
        print(f"per-shard page residency ({len(per)} devices): {detail}")
    for r in reqs[:3]:
        tag = " [truncated]" if r.truncated else ""
        print(f"  req {r.rid}: {r.prompt} -> {r.out}{tag}")
    ops.set_backend("ref")


if __name__ == "__main__":
    main()
