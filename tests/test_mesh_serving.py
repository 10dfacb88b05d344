"""Sharded paged serving (ServerConfig.mesh = MeshPlan).

Single-device process: MeshPlan validation fail-fasts and the total == 1
bit-identity guarantee (no Mesh is ever built — the engine installs the
same module-level jitted step as mesh=None, so the path is identical by
construction, and we assert it).

Subprocess (XLA_FLAGS=--xla_force_host_platform_device_count=8, set
before the jax import — the reason these run out-of-process): greedy
decode token-identity of the sharded engine vs the single-device engine
for GQA (bf16 + fp8 pages), MLA and MoE tiny configs on simulated 2- and
8-device meshes, plus the chaos capstone (NaN quarantine + corrupted
spill CRC + transient alloc faults + steal/spill/resume + prefix cache +
audit_every) on a mesh.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax

from conftest import tiny_lm_cfg

from repro import models
from repro.runtime.serve import MeshPlan, Request, Server, ServerConfig
from repro.runtime import serve as serve_mod


def _run_script(tmp_path, name, body):
    script = tmp_path / name
    script.write_text(body)
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, str(script)], capture_output=True,
                         text=True, timeout=900, env=env, cwd="/root/repo")
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-4000:])
    return json.loads(out.stdout.strip().splitlines()[-1])


class TestMeshPlan:
    def test_validation(self):
        with pytest.raises(ValueError):
            MeshPlan(data=0)
        with pytest.raises(ValueError):
            MeshPlan(model=-2)
        assert MeshPlan().total == 1
        assert MeshPlan(data=2, model=4).total == 8

    def test_build_needs_devices(self):
        # the test process runs on 1 CPU device
        if len(jax.devices()) > 1:
            pytest.skip("single-device assertion")
        with pytest.raises(ValueError, match="devices"):
            MeshPlan(model=2).build()

    def test_rejects_non_page_families(self):
        from repro.configs import get_smoke

        cfg = get_smoke("whisper-tiny")
        params = models.init_params(cfg, jax.random.PRNGKey(0))
        with pytest.raises(ValueError, match="page families"):
            Server(params, cfg,
                   ServerConfig(slots=1, max_seq=32, kv_fmt=None,
                                page_size=8, a_fmt=None,
                                mesh=MeshPlan(model=2)))

    def test_rejects_indivisible_heads(self):
        cfg = tiny_lm_cfg()  # 4 heads, 2 kv heads
        params = models.init_params(cfg, jax.random.PRNGKey(0))
        with pytest.raises(ValueError, match="n_heads"):
            Server(params, cfg,
                   ServerConfig(slots=1, max_seq=32, kv_fmt=None,
                                page_size=8, a_fmt=None,
                                mesh=MeshPlan(model=3)))
        with pytest.raises(ValueError, match="n_kv_heads"):
            Server(params, cfg,
                   ServerConfig(slots=1, max_seq=32, kv_fmt=None,
                                page_size=8, a_fmt=None,
                                mesh=MeshPlan(model=4)))

    def test_total_one_is_bit_identical_single_device_engine(
            self, trained_tiny):
        """MeshPlan with total == 1 must never build a Mesh: the server
        installs the shared module-level jitted step — the same executable
        object the mesh=None engine uses — so output is bit-identical by
        construction (asserted on the wiring AND the tokens)."""
        cfg, params = trained_tiny
        rng = np.random.default_rng(0)
        prompts = [rng.integers(1, cfg.vocab_size, 7).tolist()
                   for _ in range(2)]

        def serve(mesh):
            srv = Server(params, cfg,
                         ServerConfig(slots=2, max_seq=64, kv_fmt="fp8_e4m3",
                                      page_size=8, a_fmt=None, mesh=mesh))
            assert srv._mesh is None
            assert srv._decode.func is serve_mod._decode_step_jit
            for i, p in enumerate(prompts):
                srv.submit(Request(rid=i, prompt=p, max_new=6))
            return {r.rid: list(r.tokens) for r in srv.run_until_drained()}

        assert serve(None) == serve(MeshPlan(data=1, model=1))


_COMMON = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import numpy as np
    import jax

    from repro.runtime.serve import MeshPlan, Request, Server, ServerConfig

    def serve_tokens(params, cfg, prompts, kv_fmt, mesh, max_new=6, **kw):
        kw.setdefault("slots", len(prompts))
        kw.setdefault("max_seq", 64)
        kw.setdefault("page_size", 8)
        srv = Server(params, cfg,
                     ServerConfig(kv_fmt=kv_fmt, a_fmt=None, mesh=mesh, **kw))
        for i, p in enumerate(prompts):
            srv.submit(Request(rid=i, prompt=p, max_new=max_new))
        done = srv.run_until_drained()
        return {int(r.rid): list(r.tokens) for r in done}, srv
""")


def _train_tiny_block():
    return textwrap.dedent("""
        import sys
        sys.path.insert(0, "tests")
        from conftest import tiny_lm_cfg
        from repro.data.pipeline import DataConfig
        from repro.optimizer import AdamWConfig
        from repro.runtime.train import TrainLoopConfig, train_loop

        cfg = tiny_lm_cfg()
        dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=32,
                        global_batch=8, seed=3)
        oc = AdamWConfig(lr=8e-3, warmup=20, total_steps=150)
        state, _ = train_loop(cfg, dc, oc,
                              TrainLoopConfig(steps=150, log_every=150))
        params = state.params
    """)


class TestShardedTokenIdentity:
    def test_gqa_bf16_and_fp8(self, tmp_path):
        """GQA pages (codes + co-sharded scales) on 2- and 8-device meshes:
        greedy decode must be token-identical to the single-device engine,
        and KV bytes must actually land on every model shard."""
        body = _COMMON + _train_tiny_block() + textwrap.dedent("""
            rng = np.random.default_rng(0)
            prompts = [rng.integers(1, cfg.vocab_size, n).tolist()
                       for n in (5, 9, 13)]
            ok = True
            residency_devices = 0
            for kv_fmt in (None, "fp8_e4m3"):
                ref, _ = serve_tokens(params, cfg, prompts, kv_fmt, None)
                for plan in (MeshPlan(data=1, model=2),
                             MeshPlan(data=4, model=2)):
                    got, srv = serve_tokens(params, cfg, prompts, kv_fmt, plan)
                    ok = ok and (got == ref)
                    per = srv.shard_residency()
                    residency_devices = max(residency_devices, len(per))
            print(json.dumps({"ok": ok,
                              "residency_devices": residency_devices}))
        """)
        rec = _run_script(tmp_path, "gqa_mesh.py", body)
        assert rec["ok"]
        assert rec["residency_devices"] >= 8

    def test_w4a8_gemm_on_mesh(self, tmp_path):
        """On a serving mesh the fused W4A8 GEMM runs under shard_map (a
        TPU kernel call cannot be partitioned automatically): column-
        parallel, bit-identical to one device where each shard's N slice
        tiles, and refused where it does not."""
        body = _COMMON + textwrap.dedent("""
            import jax.numpy as jnp
            from repro.core.policy import QuantPolicy
            from repro.core.ptq import pack_linear
            from repro.kernels import ops
            from repro.launch.mesh import make_mesh

            mesh = make_mesh((1, 2), ("data", "model"))
            rng = np.random.default_rng(0)
            pol = QuantPolicy(w_fmt="fp4_e2m1", a_fmt="fp8_e4m3",
                              scale_mode="m2", lorc_rank=4)
            x = jnp.asarray(rng.normal(size=(2, 5, 256)).astype(np.float32))
            ops.set_backend("pallas_interpret")
            packed = {n: pack_linear(jnp.asarray(
                rng.normal(size=(n, 256)).astype(np.float32) * 0.05), pol)
                for n in (256, 96)}  # 128 rows per shard; 48
            one = np.asarray(jax.jit(ops.w4a8_matmul)(x, packed[256]))
            ops.set_serving_mesh(mesh)
            two = np.asarray(jax.jit(ops.w4a8_matmul)(x, packed[256]))
            try:
                jax.jit(ops.w4a8_matmul)(x, packed[96])
                refused = False
            except ValueError:
                refused = True
            ops.set_serving_mesh(None)
            print(json.dumps({"ok": bool(np.array_equal(one, two))
                              and refused}))
        """)
        assert _run_script(tmp_path, "gemm_mesh.py", body)["ok"]

    def test_mla_latent_pages(self, tmp_path):
        """MLA latent pages replicate; absorbed q heads shard. Token
        identity vs single-device on 2- and 4-way model meshes."""
        body = _COMMON + textwrap.dedent("""
            from repro.configs import get_smoke
            from repro.data.pipeline import DataConfig
            from repro.optimizer import AdamWConfig
            from repro.runtime.train import TrainLoopConfig, train_loop

            cfg = get_smoke("minicpm3-4b")
            dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=32,
                            global_batch=8, seed=5)
            oc = AdamWConfig(lr=6e-3, warmup=20, total_steps=150)
            state, _ = train_loop(cfg, dc, oc,
                                  TrainLoopConfig(steps=150, log_every=150))
            params = state.params

            rng = np.random.default_rng(1)
            prompts = [rng.integers(1, cfg.vocab_size, n).tolist()
                       for n in (5, 11)]
            ok = True
            for kv_fmt in (None, "fp8_e4m3"):
                ref, _ = serve_tokens(params, cfg, prompts, kv_fmt, None)
                for plan in (MeshPlan(data=1, model=2),
                             MeshPlan(data=2, model=4)):
                    got, _ = serve_tokens(params, cfg, prompts, kv_fmt, plan)
                    ok = ok and (got == ref)
            print(json.dumps({"ok": ok}))
        """)
        assert _run_script(tmp_path, "mla_mesh.py", body)["ok"]

    def test_moe_expert_parallel_decode(self, tmp_path):
        """MoE decode routes expert-parallel (replicated einsum dispatch,
        shard_map'ed expert FFNs): token-identical to the single-device
        einsum path on 2- and 8-way EP."""
        body = _COMMON + textwrap.dedent("""
            from repro.configs import get_smoke
            from repro.data.pipeline import DataConfig
            from repro.optimizer import AdamWConfig
            from repro.runtime.train import TrainLoopConfig, train_loop

            cfg = get_smoke("olmoe-1b-7b")  # 8 experts, 4 heads / 4 kv
            dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=32,
                            global_batch=8, seed=9)
            oc = AdamWConfig(lr=6e-3, warmup=20, total_steps=150)
            state, _ = train_loop(cfg, dc, oc,
                                  TrainLoopConfig(steps=150, log_every=150))
            params = state.params

            rng = np.random.default_rng(2)
            prompts = [rng.integers(1, cfg.vocab_size, n).tolist()
                       for n in (5, 9)]
            ref, _ = serve_tokens(params, cfg, prompts, "fp8_e4m3", None)
            ok = True
            for plan in (MeshPlan(data=1, model=2),
                         MeshPlan(data=4, model=2)):
                got, _ = serve_tokens(params, cfg, prompts, "fp8_e4m3", plan)
                ok = ok and (got == ref)
            print(json.dumps({"ok": ok}))
        """)
        assert _run_script(tmp_path, "moe_mesh.py", body)["ok"]

    def test_chaos_suite_on_mesh(self, tmp_path):
        """The PR 6 chaos machinery runs unchanged on a mesh: NaN rows
        quarantined + scrubbed, a tampered spill fails its CRC (computed
        over the host-gathered payload) and re-prefills token-identically,
        transient alloc faults absorbed, audit_every clean throughout, on
        a steal-happy 2-way model mesh with the prefix cache on."""
        body = _COMMON + _train_tiny_block() + textwrap.dedent("""
            from repro.runtime.faults import FaultPlan

            rng = np.random.default_rng(11)
            prompts = [rng.integers(1, cfg.vocab_size, 5).tolist()
                       for _ in range(2)]
            plan = MeshPlan(data=1, model=2)
            # steal-happy pool (mirrors tests/test_faults.py): two 15-token
            # requests through 6 pages of 4 forces preempt + spill + resume
            kw = dict(max_new=10, max_seq=32, page_size=4, pool_pages=6,
                      audit_every=2)
            ref, _ = serve_tokens(params, cfg, prompts, "fp8_e4m3", plan,
                                  **kw)

            faults = FaultPlan(corrupt_spills=(0,), alloc_fail_ticks=(4,))
            srv = Server(params, cfg,
                         ServerConfig(slots=2, max_seq=32, kv_fmt="fp8_e4m3",
                                      page_size=4, a_fmt=None, mesh=plan,
                                      pool_pages=6, audit_every=2),
                         faults=faults)
            reqs = [Request(rid=i, prompt=p, max_new=10)
                    for i, p in enumerate(prompts)]
            for r in reqs:
                srv.submit(r)
            srv.run_until_drained()
            audit = srv.audit()
            print(json.dumps({
                "violations": audit["violations"],
                "all_ok": all(r.status == "ok" and len(r.out) == 10
                              for r in reqs),
                "token_identical": all(list(r.out) == ref[r.rid]
                                       for r in reqs),
                "preemptions": srv.stats["preemptions"],
                "crc_failures": srv.stats["spill_integrity_failures"],
                "blocked": list(faults.blocked_ticks),
            }))
        """)
        rec = _run_script(tmp_path, "chaos_mesh.py", body)
        assert rec["violations"] == 0
        assert rec["all_ok"] and rec["token_identical"]
        assert rec["preemptions"] >= 1
        assert rec["crc_failures"] == 1

    def test_nan_quarantine_and_scrub_on_mesh(self, tmp_path):
        """An injected NaN row on a mesh fails exactly that request; the
        scrub path re-pins the pools and batchmates finish
        token-identically."""
        body = _COMMON + _train_tiny_block() + textwrap.dedent("""
            from repro.runtime.faults import FaultPlan

            rng = np.random.default_rng(13)
            prompts = [rng.integers(1, cfg.vocab_size, 5).tolist()
                       for _ in range(2)]
            plan = MeshPlan(data=1, model=2)
            ref, _ = serve_tokens(params, cfg, prompts, "fp8_e4m3", plan,
                                  max_new=8)

            faults = FaultPlan(nan_logits=((2, 1),))
            srv = Server(params, cfg,
                         ServerConfig(slots=2, max_seq=64, kv_fmt="fp8_e4m3",
                                      page_size=8, a_fmt=None, mesh=plan),
                         faults=faults)
            reqs = [Request(rid=i, prompt=p, max_new=8)
                    for i, p in enumerate(prompts)]
            for r in reqs:
                srv.submit(r)
            srv.run_until_drained()
            nan_rids = {rid for _, _, rid in faults.nan_hits}
            print(json.dumps({
                "violations": srv.audit()["violations"],
                "injected": len(nan_rids),
                "failed_match": sorted(r.rid for r in reqs
                                       if r.status == "failed")
                                == sorted(nan_rids),
                "survivors_ok": all(list(r.out) == ref[r.rid] for r in reqs
                                    if r.rid not in nan_rids),
            }))
        """)
        rec = _run_script(tmp_path, "nan_mesh.py", body)
        assert rec["violations"] == 0
        assert rec["injected"] == 1
        assert rec["failed_match"] and rec["survivors_ok"]
