"""Multi-device tests (distributed GEMT, sharded train step, roofline parser,
compressed psum).  These need >1 device, so each runs through the
``virtual_devices`` conftest fixture — a subprocess with XLA_FLAGS set
before jax initializes.  The distributed *engine* path (planned Pallas
kernels inside the shard_map schedule) is covered by
``test_distributed_engine.py``."""


class TestDistributedGemt:
    def test_shardmap_stationary_tensor_all_axes(self, virtual_devices):
        virtual_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import gemt3, gemt3_shardmap, gemt3_auto
        from repro.core.transforms import coefficient_matrix
        mesh = jax.make_mesh((2, 2, 2), ("data", "model", "pod"))
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.normal(size=(8, 6, 4)).astype(np.float32))
        cs = [coefficient_matrix("dct", n) for n in x.shape]
        ref = gemt3(x, *cs)
        for axes in [("data", "model", None), ("data", "model", "pod"),
                     (("data", "pod"), "model", None)]:
            y = jax.jit(gemt3_shardmap(mesh, axes=axes))(x, *cs)
            np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                       rtol=1e-4, atol=1e-5)
        y = gemt3_auto(mesh, axes=("data", "model", "pod"))(x, *cs)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)
        print("OK")
        """)

    def test_shardmap_collective_schedule_is_minimal(self, virtual_devices):
        """TriADA schedule: only psum_scatter collectives, no all-gathers of
        the tensor (stationarity), coefficients replicated."""
        out = virtual_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import gemt3_shardmap
        mesh = jax.make_mesh((2, 2), ("data", "model"))
        f = jax.jit(gemt3_shardmap(mesh, axes=("data", "model", None)))
        sds = jax.ShapeDtypeStruct
        hlo = f.lower(sds((8, 8, 8), jnp.float32),
                      sds((8, 8), jnp.float32), sds((8, 8), jnp.float32),
                      sds((8, 8), jnp.float32)).compile().as_text()
        import re
        ags = [l for l in hlo.splitlines() if re.search(r'\\ball-gather\\b', l)]
        rs = [l for l in hlo.splitlines() if 'reduce-scatter' in l]
        ar = [l for l in hlo.splitlines() if re.search(r'\\ball-reduce\\b', l)]
        print('AG', len(ags), 'RS', len(rs), 'AR', len(ar))
        assert len(ags) == 0, ags
        assert len(rs) + len(ar) >= 2  # the two sharded-mode combines
        """)
        assert "AG 0" in out

    def test_sharded_train_step_runs(self, virtual_devices):
        """Real sharded execution of one train step (smoke config, 8 devs)."""
        virtual_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.configs import load_config
        from repro.data import TokenSource
        from repro.launch.mesh import (act_rules, param_rules,
                                       shardings_from_axes)
        from repro.models import ShardCtx
        from repro.optim import OptConfig
        from repro.train import (build_train_step, init_train_state,
                                 train_state_axes)
        import dataclasses
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        cfg = load_config("qwen1_5_0_5b", smoke=True).finalize_for_mesh(4)
        prules = param_rules(cfg, multi_pod=False)
        prules = {k: (v if v != ("data",) or True else v) for k, v in prules.items()}
        arules = act_rules(cfg, multi_pod=False)
        ctx = ShardCtx(mesh=mesh, rules=arules)
        ocfg = OptConfig(lr=1e-3, warmup_steps=0, total_steps=5)
        state = init_train_state(jax.random.PRNGKey(0), cfg, ocfg)
        sh = shardings_from_axes(mesh, train_state_axes(cfg), prules)
        state = jax.device_put(state, sh)
        step = jax.jit(build_train_step(cfg, ctx, ocfg),
                       in_shardings=(sh, None), out_shardings=(sh, None),
                       donate_argnums=(0,))
        src = TokenSource(vocab_size=cfg.vocab_size, seq_len=32,
                          global_batch=4, seed=0)
        b = {k: jnp.asarray(v) for k, v in src.batch(0).items()}
        l0 = None
        for i in range(3):
            state, m = step(state, b)
            if l0 is None: l0 = float(m["loss"])
        assert np.isfinite(float(m["loss"]))
        print("loss", l0, "->", float(m["loss"]))
        """)

    def test_moe_shardmap_matches_local(self, virtual_devices):
        """Expert-parallel shard_map MoE == single-device local MoE."""
        virtual_devices("""
        import numpy as np, jax, jax.numpy as jnp, dataclasses
        from repro.configs import load_config
        from repro.models.ffn import apply_moe, init_moe
        from repro.models import ShardCtx
        from repro.configs.base import BlockCfg
        cfg = load_config("granite_moe_1b", smoke=True)
        cfg = dataclasses.replace(cfg, param_dtype=jnp.float32,
                                  act_dtype=jnp.float32)
        block = BlockCfg("attn", "moe")
        p = init_moe(jax.random.PRNGKey(0), cfg, block)
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(4, 8, cfg.d_model)).astype(np.float32))
        y_local, aux_local = apply_moe(p, x, cfg, block, ShardCtx())
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        ctx = ShardCtx(mesh=mesh, rules={"batch": ("data",),
                                         "expert": "model"})
        y_ep, aux_ep = jax.jit(lambda p, x: apply_moe(p, x, cfg, block, ctx))(p, x)
        np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_local),
                                   rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(float(aux_ep), float(aux_local), rtol=1e-4)
        print("OK")
        """)

    def test_compressed_psum_multi_device(self, virtual_devices):
        virtual_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.runtime import compressed_psum
        mesh = jax.make_mesh((4,), ("x",))
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(4, 256)).astype(np.float32))
        f = shard_map(lambda t: compressed_psum(t[0], "x"), mesh=mesh,
                      in_specs=P("x"), out_specs=P(), check_vma=False)
        got = np.asarray(f(x))
        want = np.asarray(x).sum(0)
        denom = np.maximum(np.abs(want), 1.0)
        assert np.max(np.abs(got - want) / denom) < 0.08
        print("OK")
        """, devices=4)

    def test_elastic_restore_smaller_mesh(self, virtual_devices):
        """Checkpoint on 8 devices, restore + run on 4 (elastic re-mesh)."""
        virtual_devices("""
        import numpy as np, jax, jax.numpy as jnp, tempfile, dataclasses
        from repro.configs import load_config
        from repro.launch.mesh import act_rules, param_rules, shardings_from_axes
        from repro.models import ShardCtx
        from repro.optim import OptConfig
        from repro.train import build_train_step, init_train_state, train_state_axes
        from repro import ckpt as ckpt_lib
        from repro.runtime import make_elastic_mesh
        cfg = load_config("qwen1_5_0_5b", smoke=True).finalize_for_mesh(4)
        ocfg = OptConfig(lr=1e-3, warmup_steps=0, total_steps=5)
        state = init_train_state(jax.random.PRNGKey(0), cfg, ocfg)
        d = tempfile.mkdtemp()
        ckpt_lib.save(d, 3, state)
        # "lose" 4 devices: restore onto a 1x4 mesh (same TP=4, dp 2->1)
        mesh2 = make_elastic_mesh(jax.devices()[:4], tp=4)
        prules = param_rules(cfg, multi_pod=False)
        sh = shardings_from_axes(mesh2, train_state_axes(cfg), prules)
        restored, step0 = ckpt_lib.restore(d, shardings=sh)
        assert step0 == 3
        ctx = ShardCtx(mesh=mesh2, rules=act_rules(cfg, multi_pod=False))
        stepf = jax.jit(build_train_step(cfg, ctx, ocfg),
                        in_shardings=(sh, None), out_shardings=(sh, None))
        from repro.data import TokenSource
        src = TokenSource(vocab_size=cfg.vocab_size, seq_len=16,
                          global_batch=4, seed=0)
        b = {k: jnp.asarray(v) for k, v in src.batch(3).items()}
        _, m = stepf(restored, b)
        assert np.isfinite(float(m["loss"]))
        print("OK")
        """)


class TestRooflineParser:
    def test_scan_collective_ground_truth(self, virtual_devices):
        out = virtual_devices("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.roofline import analyze_hlo
        D, L = 128, 4
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        def scan_coll(ws, x):
            def body(c, w):
                return c @ w, None
            y, _ = jax.lax.scan(body, x, ws)
            return jnp.sum(y)
        ws = jax.ShapeDtypeStruct((L, D, D), jnp.float32)
        x = jax.ShapeDtypeStruct((64, D), jnp.float32)
        jf = jax.jit(scan_coll,
                     in_shardings=(NamedSharding(mesh, P(None, None, "model")),
                                   NamedSharding(mesh, P("data", "model"))),
                     out_shardings=NamedSharding(mesh, P()))
        c = analyze_hlo(jf.lower(ws, x).compile().as_text(), 8)
        exp_flops = 2*32*32*128*L
        exp_ag = 32*128*4*(3/4)*L
        assert abs(c.flops - exp_flops)/exp_flops < 0.01, c.flops
        ag = c.coll_by_kind.get("all-gather", 0.0)
        assert abs(ag - exp_ag)/exp_ag < 0.01, ag
        assert max(c.while_trips.values()) == L
        print("PARSED-OK")
        """)
        assert "PARSED-OK" in out
