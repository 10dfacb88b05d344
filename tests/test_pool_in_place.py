"""The page pool stays in one buffer through the engine step.

Paged GQA/MLA segments carry their pools whole through the layer scan and
address them as ``(pool, layer)``, so the step updates the touched pages in
place. Covers: the compiled step's structure (no instruction materializes
one layer's code store; the whole store is copied once at most, from the
step's argument into the pool it returns), the decode kernels reading one
layer of a stacked store (in place where the store is lane-dense, through
the layer's slice where it is narrower), bit-identity against the sliced
dataflow (each layer's slice carried in and out of the scan) for decode,
mixed and chunk steps on GQA, MLA and mixed FP8/FP4 pools, and the host
paths that touch the pool between steps (spill and resume, prefix freeze,
audit, enc-dec admission) against the same sliced dataflow.
"""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import tiny_lm_cfg

from repro import models
from repro.configs import get_smoke
from repro.models import transformer
from repro.runtime import kv_cache as kvc
from repro.runtime import sampling as smp
from repro.runtime import serve
from repro.runtime.serve import (CachePolicy, Request, SchedulerConfig,
                                 Server, ServerConfig)

PAGE = 4
SLOTS = 3


def _cfg(kind):
    return tiny_lm_cfg() if kind == "gqa" else get_smoke("minicpm3-4b")


_HLO_TYPE = {"uint8": "u8", "int32": "s32", "float32": "f32"}


def _hlo_shape(leaf, dims=None):
    """A leaf's HLO result type, ``u8[2,25,4,2,16]``."""
    dims = leaf.shape if dims is None else dims
    return f"{_HLO_TYPE[jnp.dtype(leaf.dtype).name]}[{','.join(map(str, dims))}]"


def _step_args(srv, mode):
    """Abstract step arguments for a decode or a mixed step, as the engine
    passes them."""
    sds = jax.ShapeDtypeStruct
    i32 = jnp.int32
    n = srv.slots

    def samp(m):
        return (sds((m,), jnp.float32), sds((m,), i32), sds((m,), jnp.float32),
                sds((m,), jnp.uint32), sds((m,), i32))

    dec = kvc.PagedState(page_table=sds(srv.page_table.shape, i32),
                         lengths=sds((n,), i32))
    if mode == "decode":
        return (sds((n, 1), i32), dec, sds((n,), jnp.bool_), samp(n))
    pre = kvc.PagedState(page_table=sds((1, 4), i32), lengths=sds((1,), i32),
                         chunk_len=sds((1,), i32))
    return (sds((1, n + 8), i32), dec._replace(prefill=pre),
            sds((n + 1,), jnp.bool_), samp(n + 1))


class TestStepStructure:
    @pytest.mark.parametrize("mode", ["decode", "mixed"])
    @pytest.mark.parametrize("kind", ["gqa", "mla"])
    def test_pool_updated_in_place(self, kind, mode):
        cfg = _cfg(kind)
        params = models.init_params(cfg, jax.random.PRNGKey(0))
        srv = Server(params, cfg, ServerConfig(
            slots=SLOTS, max_seq=32, page_size=PAGE, a_fmt=None,
            cache=CachePolicy(active_fmt="fp8_e4m3")))
        like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
        pools = jax.tree.map(like, srv.pools)
        text = serve._decode_step_jit.lower(
            jax.tree.map(like, srv.params), pools, *_step_args(srv, mode),
            cfg=cfg, a_fmt=None, backend="ref").compile().as_text()

        entry = text[text.index("\nENTRY "):]
        params_of_step = set(re.findall(r"(%[\w.]+) = \S+ parameter\(",
                                        entry))
        copied = []
        for name in kvc.pool_keys(pools[0]["kv"]):
            store = pools[0]["kv"][name]
            one_layer = re.escape(_hlo_shape(store, store.shape[1:]))
            assert not re.search(rf"= {one_layer}\{{", text), \
                f"{name}: an instruction materializes one layer's store"
            whole = re.escape(_hlo_shape(store))
            copied += re.findall(
                rf"= {whole}\{{[^}}]*\}} copy\((%[\w.]+)\)", text)
        # the one copy the step may make of a store: its argument into the
        # carry it updates and returns (the pool is not donated)
        copied = sorted(set(copied))  # k and v share a shape
        assert set(copied) <= params_of_step, copied
        for arg in copied:
            assert len(re.findall(rf" copy\({re.escape(arg)}\)", text)) == 1, \
                f"the whole stacked store {arg} is copied more than once"


# ---------------------------------------------------------------------------
# The decode kernels read one layer of a stacked store
# ---------------------------------------------------------------------------
def _stacked(pool, n_layers, layer, rng):
    """``pool`` with ``n_layers`` layers, its own data at ``layer`` and
    other random codes and scales elsewhere, so that a read of the wrong
    layer shows."""
    out = {}
    for name, leaf in pool.items():
        if leaf.size == 0:
            out[name] = jnp.zeros((n_layers,) + leaf.shape[1:], leaf.dtype)
            continue
        if leaf.dtype == jnp.uint8:
            noise = rng.integers(0, 0x78, size=(n_layers,) + leaf.shape[1:])
        elif leaf.dtype == jnp.int32:
            noise = rng.integers(-2, 1, size=(n_layers,) + leaf.shape[1:])
        else:
            noise = rng.uniform(0.5, 2.0, size=(n_layers,) + leaf.shape[1:])
        stack = jnp.asarray(noise, leaf.dtype)
        out[name] = stack.at[layer].set(leaf[0])
    return out


class TestKernelReadsLayer:
    @pytest.mark.parametrize("kind,width", [
        ("gqa", 128),  # lane-dense codes: read in place
        ("gqa", 64),   # narrower: sliced to the layer
        ("mla", 64),   # ckv lane-dense, krope sliced
    ])
    def test_layer_of_a_stack(self, kind, width):
        """The interpreted kernel reading layer 2 of a three-layer stack
        gives the bits it gives on that layer alone, and the oracle's
        answer."""
        from repro.kernels import ops

        rng = np.random.default_rng(width)
        page, pp, n = 8, 3, 3
        lens = jnp.asarray([page * pp - 3, 5], jnp.int32)
        table = jnp.asarray([[0, 1, 2], [3, 6, 6]], jnp.int32)
        if kind == "gqa":
            kv, g = 2, 2
            one = kvc.init_gqa_pool(1, 6, page, kv, width, "fp8_e4m3")
            src = {k: jnp.asarray(rng.normal(size=(1, 1, 2 * pp * page, kv,
                                                   width)), jnp.float32)
                   for k in ("k", "v")}
            q = (jnp.asarray(rng.normal(size=(2, kv * g, width)),
                             jnp.float32),)
            attend = ops.paged_decode_attn
        else:
            r = 128
            one = kvc.init_mla_pool(1, 6, page, r, width, "fp8_e4m3")
            src = {"ckv": jnp.asarray(rng.normal(size=(1, 1, 2 * pp * page,
                                                       r)), jnp.float32),
                   "krope": jnp.asarray(rng.normal(size=(1, 1, 2 * pp * page,
                                                         width)), jnp.float32)}
            q = (jnp.asarray(rng.normal(size=(2, 4, r)), jnp.float32),
                 jnp.asarray(rng.normal(size=(2, 4, width)), jnp.float32))
            attend = functools.partial(ops.paged_mla_decode_attn,
                                       scale=(r + width) ** -0.5)
        one = kvc.splice_prefill(one, src, np.arange(6, dtype=np.int32),
                                 2 * pp * page)
        stack = _stacked(one, n, 2, rng)
        prev = ops.get_backend()
        try:
            ops.set_backend("pallas_interpret")
            got = attend(*q, stack, 2, table, lens)
            alone = attend(*q, one, 0, table, lens)
            ops.set_backend("ref")
            want = attend(*q, stack, 2, table, lens)
        finally:
            ops.set_backend(prev)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(alone))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Bit-identity against the sliced dataflow
# ---------------------------------------------------------------------------
def _sliced_segment_scan(p_stack, x, cfg, seg, positions, caches,
                         cache_index, a_fmt, enc_out, remat):
    """The layer scan with each layer's pool slice passed in as ``xs`` and
    stacked back as ``ys``: the dataflow the carried pool replaces. Each
    block sees its slice as a one-layer stack at layer 0."""
    def body(carry, layer_in):
        h, aux_acc = carry
        p_layer, cache_layer = layer_in
        one = jax.tree.map(lambda a: a[None], cache_layer)
        h, new, aux = transformer.block_apply(
            p_layer, h, cfg, seg, positions, one, cache_index, a_fmt,
            enc_out, layer=0)
        return (h, aux_acc + aux), jax.tree.map(lambda a: a[0], new)

    (x, aux), new = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32)), (p_stack, caches))
    return x, aux, new


def _sliced_step(params, caches, tokens, cache_index, poison, samp, cfg,
                 a_fmt, backend):
    # a function of its own, so that its jit traces afresh (under the
    # patched scan) instead of reusing another jit's trace of the step
    return serve._decode_step(params, caches, tokens, cache_index, poison,
                              samp, cfg, a_fmt, backend)


def _filled_pools(cfg, kind, rng, frozen):
    """A served-shape pool of 3 slots x 4 pages, every layer spliced with
    random K/V; with ``frozen``, the first page of rows 0 and 1 is
    transcoded into the packed FP4 region and the table points there."""
    n_pages = SLOTS * 4
    L = cfg.n_layers
    kw = dict(frozen_fmt="fp4_e2m1", n_frozen=4) if frozen else {}
    if kind == "gqa":
        kv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
        pool = kvc.init_gqa_pool(L, n_pages, PAGE, kv, hd, "fp8_e4m3", **kw)
        dims = {"k": (kv, hd), "v": (kv, hd)}
    else:
        r, dr = cfg.mla.kv_lora_rank, cfg.mla.qk_rope_dim
        pool = kvc.init_mla_pool(L, n_pages, PAGE, r, dr, "fp8_e4m3", **kw)
        dims = {"ckv": (r,), "krope": (dr,)}
    table = np.full((SLOTS, 4), n_pages, np.int32)
    lens = [10, 4, 0]
    for row in range(2):
        ids = np.arange(row * 4, row * 4 + 4, dtype=np.int32)
        table[row] = ids
        src = {name: jnp.asarray(rng.normal(size=(L, 1, 16) + d)
                                 .astype(np.float32))
               for name, d in dims.items()}
        pool = kvc.splice_prefill(pool, src, ids[:3], 12)
        if frozen:
            pool = kvc.transcode_page(pool, int(ids[0]), row)
            table[row, 0] = n_pages + 1 + row
    return [{"kv": pool}], table, np.asarray(lens, np.int32)


def _state(mode, table, lens, rng, vocab):
    """Tokens and cache index of a decode, mixed or chunk step: decode
    rows 0 and 1 (row 2 empty); the chunk streams row 1 from position 4,
    five true tokens bucketed to eight, the table's overhang on the null
    page."""
    chunk_table = table[1:2].copy()
    chunk_table[0, 3] = SLOTS * 4  # the null page
    chunk = kvc.PagedState(jnp.asarray(chunk_table),
                           jnp.asarray([4], np.int32),
                           chunk_len=jnp.asarray([5], np.int32))
    if mode == "chunk":
        return rng.integers(1, vocab, size=(1, 8)), chunk
    dec_lens = lens.copy()
    if mode == "mixed":
        dec_lens[1] = 0  # the streaming row rides its decode lane zeroed
        dec = kvc.PagedState(jnp.asarray(table), jnp.asarray(dec_lens),
                             prefill=chunk)
        return rng.integers(1, vocab, size=(1, SLOTS + 8)), dec
    return (rng.integers(1, vocab, size=(SLOTS, 1)),
            kvc.PagedState(jnp.asarray(table), jnp.asarray(dec_lens)))


class TestSlicedEquivalence:
    @pytest.mark.parametrize("backend", ["ref", "pallas_interpret"])
    @pytest.mark.parametrize("mode", ["decode", "mixed", "chunk"])
    @pytest.mark.parametrize("kind,frozen", [("gqa", False), ("mla", False),
                                             ("gqa", True)])
    def test_bit_identical(self, monkeypatch, kind, frozen, mode, backend):
        """The decode kernels (``pallas_interpret``) read layer 1 of a
        two-layer stack through their index maps; the sliced dataflow
        hands them a one-layer stack."""
        cfg = _cfg(kind)
        rng = np.random.default_rng(7)
        params = models.init_params(cfg, jax.random.PRNGKey(1))
        pools, table, lens = _filled_pools(cfg, kind, rng, frozen)
        tok, state = _state(mode, table, lens, rng, cfg.vocab_size)
        m = tok.shape[0] if mode == "decode" else (
            SLOTS + 1 if mode == "mixed" else 1)
        args = (jnp.asarray(tok, jnp.int32), state,
                jnp.zeros((m,), jnp.bool_),
                smp.as_tuple(smp.slot_arrays(m)))
        kw = dict(cfg=cfg, a_fmt=None, backend=backend)

        nxt, ok, new = serve._decode_step_jit(params, pools, *args, **kw)
        with monkeypatch.context() as mp:
            mp.setattr(transformer, "_segment_scan", _sliced_segment_scan)
            sliced = jax.jit(_sliced_step,
                             static_argnames=serve._STEP_STATIC)
            nxt_s, ok_s, new_s = sliced(params, pools, *args, **kw)
        np.testing.assert_array_equal(np.asarray(nxt), np.asarray(nxt_s))
        np.testing.assert_array_equal(np.asarray(ok), np.asarray(ok_s))
        assert np.asarray(ok).all()
        for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(new),
                                jax.tree.leaves(new_s)):
            assert np.array_equal(np.asarray(a), np.asarray(b)), path


# ---------------------------------------------------------------------------
# Host paths between steps, against the sliced dataflow
# ---------------------------------------------------------------------------
def _serve(params, cfg, prompts, sliced, frames=None, **config):
    """Serve ``prompts`` and audit the pool; with ``sliced`` the server
    steps through a jit of the same step traced under the sliced scan."""
    srv = Server(params, cfg, ServerConfig(**config))
    if sliced:
        srv._decode = functools.partial(
            jax.jit(_sliced_step, static_argnames=serve._STEP_STATIC),
            cfg=cfg, a_fmt=srv.a_fmt)
    reqs = [Request(rid=i, prompt=list(p), max_new=16,
                    frames=None if frames is None else frames[i])
            for i, p in enumerate(prompts)]
    for r in reqs:
        srv.submit(r)
    srv.run_until_drained()
    srv.audit()
    return {r.rid: list(r.out) for r in reqs}, srv


def _serve_both(monkeypatch, *args, **kw):
    out, srv = _serve(*args, False, **kw)
    with monkeypatch.context() as mp:
        mp.setattr(transformer, "_segment_scan", _sliced_segment_scan)
        ref, srv_ref = _serve(*args, True, **kw)
    return out, srv, ref, srv_ref


class TestHostPaths:
    @pytest.mark.parametrize("engine", ["mixed", "alternating"])
    def test_spill_resume_freeze_audit(self, monkeypatch, engine):
        """Page steals (spill and resume), freeze-time FP4 transcodes and
        an audit every step, served with the carried pool and with the
        sliced dataflow: the same tokens, stats and pool bytes."""
        cfg = tiny_lm_cfg()
        params = models.init_params(cfg, jax.random.PRNGKey(2))
        rng = np.random.default_rng(5)
        prefix = rng.integers(1, cfg.vocab_size, size=24).tolist()
        prompts = [prefix + rng.integers(1, cfg.vocab_size,
                                         size=2 + i % 5).tolist()
                   for i in range(8)]
        config = dict(slots=3, max_seq=64, page_size=8, a_fmt=None,
                      pool_pages=7, audit_every=1,
                      cache=CachePolicy(active_fmt="fp8_e4m3",
                                        frozen_fmt="fp4_e2m1"),
                      scheduler=SchedulerConfig(headroom_pages=1,
                                                steal_cooldown=1,
                                                engine=engine))
        out, srv, ref, srv_ref = _serve_both(monkeypatch, params, cfg,
                                             prompts, **config)
        assert srv.stats["preemptions"] >= 1 and srv.stats["resumes"] >= 1
        assert srv.stats["fp4_frozen_pages"] >= 1
        assert out == ref
        assert srv.stats == srv_ref.stats
        for a, b in zip(jax.tree.leaves(srv.pools),
                        jax.tree.leaves(srv_ref.pools)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_encdec_admission(self, monkeypatch):
        """Enc-dec admission writes the cross pages into the pool the last
        step returned; decoding reads them back."""
        cfg = get_smoke("whisper-tiny")
        params = models.init_params(cfg, jax.random.PRNGKey(3))
        rng = np.random.default_rng(1)
        prompts = [rng.integers(1, cfg.vocab_size, size=m).tolist()
                   for m in (5, 9, 3)]
        frames = [rng.normal(size=(cfg.encoder_seq, cfg.d_model))
                  .astype(np.float32) for _ in prompts]
        config = dict(slots=2, max_seq=64, page_size=8, a_fmt=None,
                      cache=CachePolicy(active_fmt="fp8_e4m3"))
        out, srv, ref, _ = _serve_both(monkeypatch, params, cfg, prompts,
                                       frames=frames, **config)
        assert out == ref
