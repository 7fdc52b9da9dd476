"""Fault tolerance, checkpointing, gradient compression, elastic re-mesh."""
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro import ckpt as ckpt_lib
from repro.runtime import (InjectedFailure, ResilienceConfig, RunReport,
                           dequantize_int8, error_feedback_update,
                           quantize_int8, remesh_plan, run_resilient)


class TestCheckpoint:
    def test_roundtrip(self, tmp_path):
        state = {"params": {"w": jnp.arange(6.0).reshape(2, 3),
                            "b": jnp.ones((3,), jnp.bfloat16)},
                 "opt": {"step": jnp.int32(7)}}
        ckpt_lib.save(str(tmp_path), 7, state)
        restored, step = ckpt_lib.restore(str(tmp_path))
        assert step == 7
        np.testing.assert_array_equal(restored["params"]["w"],
                                      np.asarray(state["params"]["w"]))
        assert restored["params"]["b"].dtype == np.asarray(
            state["params"]["b"]).dtype

    def test_retention(self, tmp_path):
        state = {"x": jnp.zeros(2)}
        for s in (1, 2, 3, 4, 5):
            ckpt_lib.save(str(tmp_path), s, state, keep=2)
        assert ckpt_lib.latest_step(str(tmp_path)) == 5
        steps = sorted(os.listdir(tmp_path))
        assert len([d for d in steps if d.startswith("step_")]) == 2

    def test_restore_missing(self, tmp_path):
        with pytest.raises(FileNotFoundError):
            ckpt_lib.restore(str(tmp_path / "nope"))


class TestResilience:
    def _setup(self, tmp_path):
        def init_state():
            return {"w": jnp.zeros(()), "n": jnp.int32(0)}

        def train_step(state, batch):
            w = state["w"] + batch
            return {"w": w, "n": state["n"] + 1}, {"loss": float(w)}

        def batch_fn(step):
            return jnp.float32(step)

        return init_state, train_step, batch_fn

    def test_restart_recovers_and_is_deterministic(self, tmp_path):
        init_state, step_fn, batch_fn = self._setup(tmp_path)
        rcfg = ResilienceConfig(ckpt_dir=str(tmp_path), ckpt_every=5)
        state, report = run_resilient(init_state, step_fn, batch_fn, 20, rcfg,
                                      fail_at={7, 13})
        assert report.restarts == 2
        assert report.steps_done == 20
        # sum over steps 0..19 regardless of restarts (exact resume)
        assert float(state["w"]) == sum(range(20))

    def test_too_many_failures_raises(self, tmp_path):
        init_state, step_fn, batch_fn = self._setup(tmp_path)
        rcfg = ResilienceConfig(ckpt_dir=str(tmp_path), ckpt_every=100,
                                max_restarts=1)
        with pytest.raises(InjectedFailure):
            # two distinct failures but only one restart allowed
            run_resilient(init_state, step_fn, batch_fn, 10, rcfg,
                          fail_at={3, 4})

    def test_straggler_accounting(self, tmp_path):
        import time
        init_state, step_fn, batch_fn = self._setup(tmp_path)

        def slow_step(state, batch):
            s, m = step_fn(state, batch)
            if int(s["n"]) == 15:
                time.sleep(0.25)
            return s, m

        rcfg = ResilienceConfig(ckpt_dir=str(tmp_path), ckpt_every=50,
                                straggler_factor=3.0)
        _, report = run_resilient(init_state, slow_step, batch_fn, 20, rcfg)
        assert report.stragglers >= 1


class TestCompression:
    def test_quantize_roundtrip_error(self):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(1000,)).astype(np.float32)) * 5
        q, s, shape = quantize_int8(x, block=128)
        xr = dequantize_int8(q, s, shape)
        err = float(jnp.max(jnp.abs(xr - x))) / float(jnp.max(jnp.abs(x)))
        assert err < 1.0 / 127 + 1e-3

    def test_compressed_psum_single_axis(self):
        """shard_map over the (single-device) mesh: psum semantics hold."""
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.runtime import compressed_psum
        mesh = jax.make_mesh((1,), ("x",))
        x = jnp.asarray(np.random.default_rng(1).normal(size=(256,)),
                        dtype=jnp.float32)
        f = shard_map(lambda t: compressed_psum(t, "x"), mesh=mesh,
                      in_specs=P(), out_specs=P(), check_vma=False)
        np.testing.assert_allclose(np.asarray(f(x)), np.asarray(x),
                                   rtol=2e-2, atol=2e-2)

    def test_error_feedback_reduces_bias(self):
        rng = np.random.default_rng(2)
        g = {"w": jnp.asarray(rng.normal(size=(512,)).astype(np.float32))}
        resid = {"w": jnp.zeros((512,))}

        def compress(tree):
            return jax.tree.map(
                lambda x: dequantize_int8(*quantize_int8(x, 64)), tree)

        total_sent = jax.tree.map(jnp.zeros_like, g)
        for _ in range(20):
            sent, resid = error_feedback_update(g, resid, compress)
            total_sent = jax.tree.map(jnp.add, total_sent, sent)
        # mean of sent ≈ g after EF warms up (residual stays bounded)
        avg = jax.tree.map(lambda t: t / 20, total_sent)
        err = float(jnp.max(jnp.abs(avg["w"] - g["w"])))
        assert err < 0.02


class TestAsyncSave:
    def test_save_returns_joinable_handle(self, tmp_path):
        state = {"x": jnp.arange(4.0)}
        h = ckpt_lib.save(str(tmp_path), 3, state, blocking=False)
        path = h.join()
        assert h.done()
        assert path == os.path.join(str(tmp_path), "step_00000003")
        assert os.fspath(h) == path  # str-compatible for old callers
        restored, step = ckpt_lib.restore(str(tmp_path))
        assert step == 3

    def test_blocking_save_handle_is_done(self, tmp_path):
        h = ckpt_lib.save(str(tmp_path), 1, {"x": jnp.zeros(2)})
        assert h.done()
        assert h.join() == os.fspath(h)

    def test_async_error_surfaces_on_join(self, tmp_path):
        from repro import obs

        blocker = tmp_path / "ckpts"
        blocker.write_text("not a directory")  # os.makedirs will fail
        with obs.session("save", enable_tracing=False) as s:
            h = ckpt_lib.save(str(blocker), 1, {"x": jnp.zeros(2)},
                              blocking=False)
            with pytest.raises(OSError):
                h.join()
            assert s.registry.value("ckpt.save.error") == 1
            assert s.registry.value("ckpt.save.ok") == 0

    def test_save_counters(self, tmp_path):
        from repro import obs

        with obs.session("save", enable_tracing=False) as s:
            ckpt_lib.save(str(tmp_path), 1, {"x": jnp.zeros(2)})
            ckpt_lib.save(str(tmp_path), 2, {"x": jnp.zeros(2)},
                          blocking=False).join()
            assert s.registry.value("ckpt.save.ok") == 2
            assert s.registry.value("ckpt.save.error") == 0


class TestReportAccounting:
    def _setup(self):
        def init_state():
            return {"w": jnp.zeros(()), "n": jnp.int32(0)}

        def train_step(state, batch):
            w = state["w"] + batch
            return {"w": w, "n": state["n"] + 1}, {"loss": float(w)}

        def batch_fn(step):
            return jnp.float32(step)

        return init_state, train_step, batch_fn

    def test_replayed_steps_not_double_counted(self, tmp_path):
        """Restarts replay the lost segment; losses/step_times must hold
        exactly one entry per step, not one per execution."""
        init_state, step_fn, batch_fn = self._setup()
        rcfg = ResilienceConfig(ckpt_dir=str(tmp_path), ckpt_every=5)
        _, report = run_resilient(init_state, step_fn, batch_fn, 20, rcfg,
                                  fail_at={7, 13})
        assert report.restarts == 2
        assert len(report.losses) == 20
        assert len(report.step_times) == 20
        # loss at step s is sum(0..s): the replayed entries were overwritten
        want = [float(sum(range(s + 1))) for s in range(20)]
        assert report.losses == want

    def test_retryable_is_configurable(self, tmp_path):
        """OSError is not retryable by default; widening rcfg.retryable
        turns it into a checkpoint/restart recovery."""
        init_state, step_fn, batch_fn = self._setup()
        tripped = []

        def flaky_step(state, batch):
            if not tripped and int(state["n"]) == 3:
                tripped.append(True)
                raise OSError("transient storage blip")
            return step_fn(state, batch)

        rcfg = ResilienceConfig(ckpt_dir=str(tmp_path / "a"), ckpt_every=2)
        with pytest.raises(OSError):
            run_resilient(init_state, flaky_step, batch_fn, 10, rcfg)

        tripped.clear()
        rcfg = ResilienceConfig(ckpt_dir=str(tmp_path / "b"), ckpt_every=2,
                                retryable=(InjectedFailure, OSError))
        state, report = run_resilient(init_state, flaky_step, batch_fn, 10,
                                      rcfg)
        assert report.restarts == 1
        assert float(state["w"]) == sum(range(10))

    def test_async_saves_drained_before_return(self, tmp_path):
        init_state, step_fn, batch_fn = self._setup()
        rcfg = ResilienceConfig(ckpt_dir=str(tmp_path), ckpt_every=5,
                                async_save=True)
        state, report = run_resilient(init_state, step_fn, batch_fn, 20, rcfg,
                                      fail_at={7})
        assert report.restarts == 1
        assert float(state["w"]) == sum(range(20))
        assert len(report.losses) == 20
        # the final save was joined before return: restore sees step 20
        assert ckpt_lib.latest_step(str(tmp_path)) == 20


class TestElastic:
    def test_remesh_plan(self):
        assert remesh_plan(256, 16) == (16, 16)
        assert remesh_plan(240, 16) == (15, 16)  # lost a host: dp shrinks
        with pytest.raises(ValueError):
            remesh_plan(8, 16)  # cannot keep the TP group

    def test_remesh_plan_multi_pod(self):
        # scattered survivors: each pod contributes count // tp groups,
        # so dp can be below the single-fabric n // tp
        assert remesh_plan(12, 4, multi_pod=True, pod_counts=(6, 6)) == (2, 4)
        assert remesh_plan(12, 4) == (3, 4)  # single fabric would give 3
        assert remesh_plan(16, 4, multi_pod=True,
                           pod_counts=(8, 8)) == (4, 4)
        assert remesh_plan(11, 4, multi_pod=True,
                           pod_counts=(8, 3)) == (2, 4)
        assert remesh_plan(7, 4, multi_pod=True, pod_counts=(0, 7)) == (1, 4)

    def test_remesh_plan_multi_pod_validation(self):
        with pytest.raises(ValueError, match="multi_pod"):
            remesh_plan(12, 4, pod_counts=(6, 6))  # unused knob must raise
        with pytest.raises(ValueError, match="pod_counts"):
            remesh_plan(12, 4, multi_pod=True)
        with pytest.raises(ValueError, match="sum"):
            remesh_plan(12, 4, multi_pod=True, pod_counts=(6, 4))
        with pytest.raises(ValueError, match="straddle"):
            # 6 survivors but no pod holds a full TP=4 group
            remesh_plan(6, 4, multi_pod=True, pod_counts=(3, 3))

    def test_make_elastic_mesh_validation(self):
        from repro.runtime.elastic import make_elastic_mesh

        with pytest.raises(ValueError, match="multi_pod"):
            make_elastic_mesh(jax.devices(), 1, pod_of=lambda d: 0)
        with pytest.raises(ValueError, match="pod_of"):
            make_elastic_mesh(jax.devices(), 1, multi_pod=True)

    def test_make_elastic_mesh_multi_pod_grouping(self, virtual_devices):
        out = virtual_devices("""
            import jax
            from repro.runtime.elastic import make_elastic_mesh

            devs = jax.devices()
            assert len(devs) == 8
            # pods of 3 + 5 with tp=2: stragglers (1 per pod) are dropped,
            # groups never straddle the boundary
            mesh = make_elastic_mesh(devs, 2, multi_pod=True,
                                     pod_of=lambda d: 0 if d.id < 3 else 1)
            assert dict(mesh.shape) == {"data": 3, "model": 2}
            ids = [d.id for d in mesh.devices.flat]
            assert ids == [0, 1, 3, 4, 5, 6]  # devices 2 and 7 idle
            for row in mesh.devices:
                pods = {0 if d.id < 3 else 1 for d in row}
                assert len(pods) == 1  # each TP group within one pod
            print("MESH_OK")
        """)
        assert "MESH_OK" in out

    def test_reshard_state_after_shrink(self, virtual_devices):
        out = virtual_devices("""
            import numpy as np, jax, jax.numpy as jnp
            from jax.sharding import PartitionSpec as P
            from repro.runtime.elastic import (make_elastic_mesh,
                                               remesh_plan, reshard_state)

            devs = jax.devices()
            old_mesh = make_elastic_mesh(devs, 2)          # (4, 2)
            state = {"w": jnp.arange(32.0).reshape(8, 4),
                     "b": jnp.ones((4,))}
            specs = {"w": P("data", "model"), "b": P()}
            dp, tp = remesh_plan(len(devs) // 2, 2)        # lost half: (2, 2)
            new_mesh = make_elastic_mesh(devs[: dp * tp], tp)
            moved = reshard_state(state, None, new_mesh, specs)
            assert moved["w"].sharding.mesh.devices.shape == (2, 2)
            np.testing.assert_array_equal(np.asarray(moved["w"]),
                                          np.asarray(state["w"]))
            np.testing.assert_array_equal(np.asarray(moved["b"]),
                                          np.asarray(state["b"]))
            print("RESHARD_OK")
        """)
        assert "RESHARD_OK" in out
