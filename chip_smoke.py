#!/usr/bin/env python3
"""Serve OLMo-1B at its published widths through the compiled W4A8 kernels
on a TPU — the quickest proof that the serving path still runs on the chip.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # MeshPlan(data=1, model=4) vs one chip

The normal entry points, in order: random weights from ``--seed`` are
quantized with the paper's W4A8 policy (``quantize_tree``: FP4-E2M1
weights, FP8-E4M3 activations, M2 scales, LoRC rank 8), then a ``Server``
with ``kernel_backend="pallas"`` serves a few requests through the mixed
engine step, the fused W4A8 GEMM and the paged FP8 decode kernel, over a
fully backed page pool.

One chip: the same requests are served again with ``kernel_backend="ref"``
(the jnp oracle) in the same process, and the share of greedy tokens that
match is printed. Two comparisons are bounded. Once every request
decodes, the paged decode kernel runs on the server's own live page pool,
page tables and lengths, every layer, against the oracle
(``DECODE_TOL``). Then every layer and the prefill logits at each
request's last prompt position are compared teacher-forced
(``LOGIT_TOL``). ``--chips 4`` runs only the sharded path: the same
requests through ``MeshPlan(data=1, model=4)`` (16 KV heads, 4 per shard)
against one chip, printing per-device residency, token agreement and the
same two bounded comparisons.

Exits nonzero, printing no result, when JAX finds no TPU or any phase
fails. The last line of stdout is the device record as one JSON object.
The compilation cache is ``JAX_COMPILATION_CACHE_DIR`` when set, else
``<repo>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# a fully backed pool: 8 slots x 2048 tokens in pages of 32
SLOTS, MAX_SEQ, PAGE_SIZE = 8, 2048, 32
# prompts from one page to several hundred tokens; the lengths keep the
# bucketed (chunk, table-width) shapes the mixed step compiles to four
PROMPT_LENS = (32, 96, 224, 352, 480, 500)
MAX_NEW = 16
# The pallas-vs-ref tolerance of the paged decode kernel in
# tests/test_kv_cache.py (rtol = atol = 2e-5), taken relative to the
# largest output. The kernel scores in f32 on the VPU, so the oracle's
# einsums run at full f32 precision to match (the TPU's default would
# round their operands to bf16).
DECODE_TOL = 2e-5
# The tolerance of the pallas-vs-ref tests of a whole quantized layer (the
# MoE and MLA forwards in tests/test_w4a8_fused.py: rtol = atol = 5e-2;
# one fused GEMM against its oracle holds 2e-2). It bounds each layer's
# update and the logits, measured teacher-forced: every layer of both
# backends reads the reference's hidden state, so a difference is the
# kernels' own and not the FP8 activation grid amplifying rounding noise
# through depth (that free-running gap is printed, not bounded).
LOGIT_TOL = 5e-2


def log(msg: str) -> None:
    print(msg, flush=True)


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (or loading from
    the persistent cache), summed from its ``/jax/core/compile/`` events."""

    def __init__(self):
        import jax

        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **_kw):
        if name.startswith("/jax/core/compile/"):
            self.total += secs


def build_model(cfg, seed: int):
    """Random weights from ``seed``, quantized to the W4A8 serving tree."""
    import jax

    from repro import models
    from repro.core.policy import QuantPolicy
    from repro.core.ptq import quantize_tree

    policy = QuantPolicy(w_fmt="fp4_e2m1", a_fmt="fp8_e4m3", scale_mode="m2",
                         lorc_rank=8)
    params = models.init_params(cfg, jax.random.PRNGKey(seed))
    packed = quantize_tree(params, models.build_def(cfg), policy)
    return jax.block_until_ready(packed)


def make_prompts(cfg, seed: int, lens=PROMPT_LENS):
    import numpy as np

    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, size=n).tolist() for n in lens]


def new_server(packed, cfg, backend: str, mesh=None):
    from repro.runtime.serve import CachePolicy, Server, ServerConfig

    return Server(packed, cfg, ServerConfig(
        slots=SLOTS, max_seq=MAX_SEQ, page_size=PAGE_SIZE,
        kernel_backend=backend, cache=CachePolicy(active_fmt="fp8_e4m3"),
        strict=True, mesh=mesh))


def serve(server, prompts, first_rid: int = 0, probe=None):
    """Submit ``prompts`` and drain; returns {rid: RequestResult}. With a
    ``probe``, the engine is stepped until every request has finished its
    prefill, ``probe(server)`` looks at the live state, and the drain
    goes on."""
    from repro.runtime.serve import Request

    reqs = [Request(rid=first_rid + i, prompt=list(p), max_new=MAX_NEW)
            for i, p in enumerate(prompts)]
    start = len(server.finished)
    for r in reqs:
        server.submit(r)
    if probe is not None:
        while any(not r.out and r.status != "failed" for r in reqs):
            if not server.step():
                fail("the engine stopped before every prefill finished")
        probe(server)
    server.run_until_drained()
    done = {r.rid: r.result() for r in server.finished[start:]}
    if len(done) != len(prompts):
        fail(f"{len(prompts)} requests sent, {len(done)} finished")
    bad = {rid: (r.status, r.error) for rid, r in done.items() if not r.ok}
    if bad:
        fail(f"requests ended other than 'ok': {bad}")
    return done


def _padded(prompts):
    """Right-padded (B, S) tokens and each prompt's last position (causal:
    padding never reaches an earlier position)."""
    import jax.numpy as jnp
    import numpy as np

    toks = np.zeros((len(prompts), max(len(p) for p in prompts)), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
    return jnp.asarray(toks), jnp.asarray([len(p) - 1 for p in prompts])


def _head(params, cfg, hidden, last):
    """Final norm + tied head at each row's ``last`` position."""
    import jax
    import jax.numpy as jnp

    from repro.models.layers import norm

    h = norm(params["final_ln"], hidden[jnp.arange(hidden.shape[0]), last],
             cfg.norm_kind, cfg.norm_eps)
    return jax.lax.dot_general(h, params["embed"], (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _under(backend, mesh, fn, *args):
    """Trace ``fn`` with the kernel ``backend`` selected and the kernels
    sharded over ``mesh`` (None: one device); both are read at trace
    time."""
    from repro.kernels import ops

    prev = ops.get_backend(), ops.get_serving_mesh()
    ops.set_backend(backend)
    ops.set_serving_mesh(mesh)
    try:
        return fn(*args)
    finally:
        ops.set_backend(prev[0])
        ops.set_serving_mesh(prev[1])


def _onto(mesh, a):
    """``a`` replicated over ``mesh``'s devices (None: left as it is)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    if mesh is None:
        return a
    return jax.device_put(a, NamedSharding(mesh, PartitionSpec()))


def prompt_logits(cfg, prompts, params, backend: str, mesh=None):
    """Last-position logits of one free-running batched forward of the
    prompts: ``params`` under ``backend``, sharded over ``mesh`` as a mesh
    server places them."""
    import jax
    import numpy as np

    from repro import models

    toks, last = _padded(prompts)

    def fwd(params, tokens, last):
        hidden, _, _ = models.forward_hidden(params, cfg, {"tokens": tokens})
        # forward_hidden applies the final norm; the head alone is left
        return jax.lax.dot_general(
            hidden[np.arange(tokens.shape[0]), last], params["embed"],
            (((1,), (1,)), ((), ())), preferred_element_type=np.float32)

    run = jax.jit(lambda *a: _under(backend, mesh, fwd, *a))
    return np.asarray(run(params, _onto(mesh, toks), _onto(mesh, last)))


def teacher_forced(cfg, prompts, ref, test):
    """Every layer of two sides on the ``ref`` side's input; a side is
    (params, kernel backend, mesh or None). Returns (per-layer gap (L, B):
    max |test - ref| over each request's real positions relative to the
    max |update| the ref layer made; ref and test logits at the last
    prompt positions, each from its side's last layer)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import transformer as tf

    (seg,) = tf.segments_for(cfg)  # a dense decoder: one stacked segment
    toks, last = _padded(prompts)
    pos = jnp.arange(toks.shape[1])
    real = np.asarray(pos[None, :] <= last[:, None])[..., None]

    def side_fns(params, backend, mesh):
        block = lambda p, h: tf.block_apply(p, h, cfg, seg, pos)[0]
        layer = jax.jit(lambda p, h: _under(backend, mesh, block, p, h))
        head = jax.jit(lambda p, h, i: _under(backend, mesh, _head, p, cfg,
                                              h, i))
        return params["segments"][0], layer, head

    (r_stack, r_layer, r_head), (t_stack, t_layer, t_head) = (
        side_fns(*ref), side_fns(*test))
    f32 = lambda a: np.where(real, np.asarray(a, np.float32), 0.0)
    h = jax.jit(tf._embed_tokens, static_argnums=1)(ref[0], cfg, toks)
    gaps = []
    for i in range(seg.count):
        nxt = r_layer(jax.tree.map(lambda a: a[i], r_stack), h)
        out = t_layer(jax.tree.map(lambda a: a[i], t_stack),
                      _onto(test[2], h))
        upd = np.abs(f32(nxt) - f32(h)).max(axis=(1, 2))
        gaps.append(np.abs(f32(out) - f32(nxt)).max(axis=(1, 2)) / upd)
        h = nxt
    return (np.stack(gaps), np.asarray(r_head(ref[0], h, last)),
            np.asarray(t_head(test[0], out, _onto(test[2], last))))


def live_decode_gap(server, seed: int, mesh=None):
    """The paged decode kernel on ``server``'s live pool, page tables and
    lengths, every layer, against the oracle: random queries for every
    decoding slot; the kernel sharded over ``mesh`` as the server runs it.
    Returns (per-layer max |pallas - ref| / max |ref|, slots, lengths)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops

    rows = [s for s, r in enumerate(server.active)
            if r is not None and r.out]
    pt = jnp.asarray(server.page_table[rows])
    lens = jnp.asarray(server.lengths[rows])
    kv = server.pools[0]["kv"]  # a dense decoder: one stacked segment
    cfg = server.cfg
    q = jnp.asarray(np.random.default_rng(seed).normal(
        size=(len(rows), cfg.n_heads, cfg.resolved_head_dim)).astype(np.float32))

    def attn(backend, m):
        return jax.jit(lambda pool, layer, q, pt, lens: _under(
            backend, m, ops.paged_decode_attn, q, pool, layer, pt, lens))

    pal, ref = attn("pallas", mesh), attn("ref", None)
    gaps = []
    for i in range(cfg.n_layers):
        o_pal = np.asarray(pal(kv, i, q, pt, lens))
        with jax.default_matmul_precision("highest"):
            o_ref = np.asarray(ref(kv, i, q, pt, lens))
        gaps.append(np.abs(o_pal - o_ref).max() / np.abs(o_ref).max())
    return np.asarray(gaps), rows, np.asarray(lens)


class Checks:
    """Bounded comparisons: each is printed when made, and the run fails
    after all of them if any was outside its bound."""

    def __init__(self):
        self.failed = []

    def bound(self, what: str, worst: float, tol: float) -> None:
        ok = bool(worst <= tol)
        log(f"  {'within' if ok else 'OUTSIDE'} {tol}: {what} "
            f"(worst {worst:.3e})")
        if not ok:
            self.failed.append(f"{what}: {worst:.3e} > {tol}")

    def decode(self, server, seed: int, label: str, mesh=None) -> None:
        gaps, rows, lens = live_decode_gap(server, seed, mesh)
        log(f"live paged decode, {label}: slots {rows} at lengths "
            f"{lens.tolist()}; max |diff| / max |ref| per layer "
            f"{[float(g) for g in gaps]}")
        self.bound(f"live paged decode, {label}", float(gaps.max()),
                   DECODE_TOL)

    def finish(self) -> None:
        if self.failed:
            fail("; ".join(self.failed))


def logit_gap(a, b):
    """Per-request max |a - b| over the vocabulary, relative to max |b|."""
    import numpy as np

    return np.abs(a - b).max(axis=-1) / np.abs(b).max(axis=-1)


def token_agreement(a, b) -> float:
    """Share of emitted positions at which two runs chose the same token."""
    same = total = 0
    for rid in a:
        x, y = a[rid].tokens, b[rid].tokens
        total += max(len(x), len(y))
        same += sum(u == v for u, v in zip(x, y))
    return same / max(total, 1)


def packed_bytes(tree) -> int:
    import jax

    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def one_chip(cfg, packed, seed: int) -> None:
    clock = CompileClock()
    checks = Checks()
    prompts = make_prompts(cfg, seed)
    srv = new_server(packed, cfg, "pallas")
    log(f"kv pool: {SLOTS} slots x {MAX_SEQ} tokens in pages of "
        f"{PAGE_SIZE}, {srv.kv_bytes_per_token():.0f} B/token (fp8_e4m3), "
        f"engine={srv.engine}")
    c0, t0 = clock.total, time.perf_counter()
    cold = serve(srv, prompts, probe=lambda s: checks.decode(
        s, seed, "pallas vs ref"))
    cold_s = time.perf_counter() - t0
    compile_s = clock.total - c0
    # steady: fresh prompts of the same lengths reuse every compiled shape
    # (fresh tokens, so the prefix cache cannot skip their prefill)
    c1, t1 = clock.total, time.perf_counter()
    steady = serve(srv, make_prompts(cfg, seed + 1), len(prompts))
    steady_s = time.perf_counter() - t1
    recompiled = clock.total - c1
    toks = sum(len(r.tokens) for r in cold.values())
    toks_steady = sum(len(r.tokens) for r in steady.values())
    log(f"requests sent: {2 * len(prompts)} (prompt lengths "
        f"{list(PROMPT_LENS)} twice, max_new {MAX_NEW}); ok: "
        f"{len(cold) + len(steady)}; failed: 0")
    log(f"tokens emitted: {toks + toks_steady}")
    log(f"compile seconds: {compile_s:.2f} (first drain, {cold_s:.2f} s "
        f"wall, live decode check included)")
    log(f"steady seconds: {steady_s:.2f} for {toks_steady} tokens "
        f"({recompiled:.2f} s of it compiling)")
    del srv
    gc.collect()

    ref_srv = new_server(packed, cfg, "ref")
    ref = serve(ref_srv, prompts)
    del ref_srv
    gc.collect()
    log(f"greedy tokens matching the ref backend (not bounded): "
        f"{token_agreement(cold, ref):.4f}")

    compare(cfg, prompts, (packed, "ref", None), (packed, "pallas", None),
            "pallas vs ref", checks)
    checks.finish()


def compare(cfg, prompts, ref, test, label: str, checks: Checks,
            free_running: bool = True) -> None:
    """Print and bound the teacher-forced layer and logit gaps of the
    ``test`` side against ``ref``; print the free-running logit gap."""
    gaps, lr, lt = teacher_forced(cfg, prompts, ref, test)
    gap = logit_gap(lt, lr)
    worst = gaps.max(axis=1)
    log(f"teacher-forced layers, {label} (max |diff| / max |ref update|): "
        f"worst {float(worst.max()):.3e} at layer {int(worst.argmax())}; "
        f"per layer {[float(g) for g in worst]}")
    checks.bound(f"teacher-forced layer updates, {label}",
                 float(gaps.max()), LOGIT_TOL)
    log(f"prefill logits at the last prompt position, teacher-forced, "
        f"{label}: max |diff| / max |ref| per request "
        f"{[float(g) for g in gap]}")
    checks.bound(f"teacher-forced last-position logits, {label}",
                 float(gap.max()), LOGIT_TOL)
    if not free_running:
        return
    free_t = prompt_logits(cfg, prompts, *test)
    free_r = prompt_logits(cfg, prompts, *ref)
    log(f"free-running forward, {label} (not bounded): max |diff| / max "
        f"|ref| per request {[float(g) for g in logit_gap(free_t, free_r)]}"
        f"; argmax agreement "
        f"{float((free_t.argmax(-1) == free_r.argmax(-1)).mean()):.4f}")


def four_chips(cfg, packed, seed: int) -> None:
    import jax

    from repro.runtime.serve import MeshPlan

    checks = Checks()
    prompts = make_prompts(cfg, seed)
    plan = MeshPlan(data=1, model=4)
    mesh = plan.build()
    srv = new_server(packed, cfg, "pallas", mesh=plan)
    t0 = time.perf_counter()
    sharded = serve(srv, prompts, probe=lambda s: checks.decode(
        s, seed, "four chips (head-sharded) vs ref", mesh))
    log(f"mesh (data=1, model=4): {len(sharded)} requests ok in "
        f"{time.perf_counter() - t0:.2f} s, engine={srv.engine}")
    resid = srv.shard_residency()
    pool_total = sum(x.nbytes for x in jax.tree.leaves(srv.pools))
    log(f"per-device pool residency (of {pool_total / 2**20:.1f} MiB): "
        + ", ".join(f"{d}: {b / 2**20:.1f} MiB" for d, b in resid.items()))
    param_resid = {}
    for leaf in jax.tree.leaves(srv.params):
        for sh in leaf.addressable_shards:
            key = str(sh.device)
            param_resid[key] = param_resid.get(key, 0) + sh.data.nbytes
    log("per-device weight residency: " + ", ".join(
        f"{d}: {b / 2**20:.1f} MiB" for d, b in sorted(param_resid.items())))
    # KV pages split by head: each device holds about a quarter of the
    # pool (plus the replicated per-page scales), none holds it all
    if len(resid) != 4 or not all(0.2 * pool_total <= b <= 0.3 * pool_total
                                  for b in resid.values()):
        fail(f"the page pool is not split over four devices: {resid}")
    if len(param_resid) != 4 or not all(param_resid.values()):
        fail(f"weights are not placed on all four devices: {param_resid}")
    mesh_side = (srv.params, "pallas", mesh)
    del srv
    gc.collect()

    one = new_server(packed, cfg, "pallas")
    single = serve(one, prompts)
    del one
    gc.collect()
    log(f"greedy tokens matching one chip (not bounded): "
        f"{token_agreement(sharded, single):.4f}")
    # (no free-running forward here: two more full-depth compiles on the
    # four-chip machine, and the greedy streams above already run free)
    compare(cfg, prompts, (packed, "pallas", None), mesh_side,
            "four chips vs one", checks, free_running=False)
    checks.finish()


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    try:
        import jax

        from repro.configs.olmo_1b import CONFIG
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        fail(f"cannot import the serving stack next to this script: {e}")

    cache = enable_compile_cache()
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        fail(f"JAX found no TPU (platform {platform!r}); this smoke runs "
             "the compiled kernels on the chip only")
    if len(devices) < args.chips:
        fail(f"--chips {args.chips} needs {args.chips} devices, JAX sees "
             f"{len(devices)}")

    cfg = CONFIG
    log(f"device: {platform} {devices[0].device_kind} x {len(devices)}; "
        f"compile cache {cache}")
    log(f"model: {cfg.name} — {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads} heads ({cfg.n_kv_heads} KV), d_ff {cfg.d_ff}, vocab "
        f"{cfg.vocab_size}; random weights from seed {args.seed}")
    t0 = time.perf_counter()
    packed = build_model(cfg, args.seed)
    log(f"packed W4A8 checkpoint: {packed_bytes(packed)} bytes "
        f"({packed_bytes(packed) / 2**30:.3f} GiB) in "
        f"{time.perf_counter() - t0:.1f} s")
    if args.chips == 1:
        one_chip(cfg, packed, args.seed)
    else:
        four_chips(cfg, packed, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
