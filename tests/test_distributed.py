"""Distribution tests under 8 host devices (subprocess: jax locks the device
count at first init, so multi-device scenarios each run in a fresh process).
Covers: sharded train step on a (4,2) mesh, pipeline parallelism over a pod
axis, elastic checkpoint restore onto a different mesh, straggler monitor."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_with_devices(code: str, n: int = 8) -> str:
    env = dict(os.environ)
    # Host devices only: the child must never reach for a chip.
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    return out.stdout


def test_sharded_train_step_runs():
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_arch, reduced
        from repro.models import build_model
        from repro.launch import steps as steps_mod
        from repro.launch.mesh import make_test_mesh
        from repro.distributed import sharding as shd
        from repro.distributed.context import activation_sharding
        from repro.optim.adamw import AdamWConfig

        cfg = reduced(get_arch("stablelm-12b"))
        mesh = make_test_mesh(model=2)   # (4, 2) over 8 host devices
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        p_sh = shd.param_shardings(jax.eval_shape(lambda: params), cfg, mesh)
        params = jax.device_put(params, p_sh)
        opt = steps_mod.init_opt_state(params)
        o_sh = shd.opt_state_shardings(p_sh, mesh)
        opt = jax.device_put(opt, o_sh)
        step = steps_mod.make_train_step(model, AdamWConfig(warmup_steps=1, total_steps=4))
        jitted = jax.jit(step, in_shardings=(p_sh, o_sh, None),
                         out_shardings=(p_sh, o_sh, None), donate_argnums=(0, 1))
        batch = {"tokens": jnp.ones((8, 16), jnp.int32)}
        with activation_sharding(shd.activation_sharding(mesh, cfg)):
            params, opt, m = jitted(params, opt, batch)
            params, opt, m = jitted(params, opt, batch)
        assert bool(jnp.isfinite(m["loss"])), m
        # a TP-sharded leaf is genuinely distributed
        leaf = params["groups"]["0"]["attn"]["wq"]
        assert len(leaf.sharding.device_set) > 1
        print("LOSS", float(m["loss"]))
        print("OK")
    """)
    assert "OK" in out


def test_pipeline_parallel_matches_sequential():
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed.pipeline import pipeline_apply, bubble_fraction
        mesh = jax.make_mesh((4,), ("pod",))
        n_stages, d = 4, 16
        r = np.random.default_rng(0)
        ws = jnp.asarray(r.normal(size=(n_stages, d, d)) * 0.3, jnp.float32)
        x = jnp.asarray(r.normal(size=(8, d)), jnp.float32)
        def stage(w, h):
            return jnp.tanh(h @ w)
        y_pipe = pipeline_apply(stage, ws, x, mesh=mesh, axis="pod", n_microbatches=4)
        y_seq = x
        for i in range(n_stages):
            y_seq = stage(ws[i], y_seq)
        np.testing.assert_allclose(np.asarray(y_pipe), np.asarray(y_seq), rtol=1e-5, atol=1e-5)
        assert 0 < bubble_fraction(4, 4) < 1
        print("OK")
    """)
    assert "OK" in out


def test_elastic_restore_across_meshes(tmp_path):
    out = run_with_devices(f"""
        import jax, jax.numpy as jnp
        from repro.configs import get_arch, reduced
        from repro.models import build_model
        from repro.checkpoint import CheckpointManager
        from repro.distributed import sharding as shd
        from repro.distributed.fault import elastic_restore

        cfg = reduced(get_arch("minitron-4b"))
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        mesh8 = jax.make_mesh((4, 2), ("data", "model"))
        p8 = shd.param_shardings(jax.eval_shape(lambda: params), cfg, mesh8)
        params8 = jax.device_put(params, p8)
        m = CheckpointManager({str(tmp_path)!r})
        m.save(3, {{"params": params8}})

        # "failure": restore onto a smaller 4-device mesh (elastic downscale)
        mesh4 = jax.make_mesh((2, 2), ("data", "model"),
                              devices=jax.devices()[:4])
        step, restored = elastic_restore(m, {{"params": jax.eval_shape(lambda: params)}},
                                         cfg, mesh4)
        assert step == 3
        leaf = restored["params"]["groups"]["0"]["attn"]["wq"]
        assert leaf.sharding.device_set <= set(jax.devices()[:4])
        import numpy as np
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(leaf), np.float32),
            np.asarray(jax.device_get(params8["groups"]["0"]["attn"]["wq"]), np.float32))
        print("OK")
    """)
    assert "OK" in out


def test_multipod_mesh_constructs():
    out = run_with_devices("""
        from repro.launch.mesh import make_production_mesh
        mesh = make_production_mesh(multi_pod=True)
        assert mesh.shape == {"pod": 2, "data": 16, "model": 16}
        mesh1 = make_production_mesh()
        assert mesh1.shape == {"data": 16, "model": 16}
        print("OK")
    """, n=512)
    assert "OK" in out


def test_straggler_monitor():
    from repro.distributed import StragglerMonitor

    m = StragglerMonitor(threshold=2.0, warmup=2)
    for step in range(6):
        assert not m.record(step, 1.0)
    assert m.record(6, 5.0)          # flagged
    assert not m.record(7, 1.05)     # baseline not poisoned
    assert len(m.flagged) == 1 and m.flagged[0][0] == 6


def test_preemption_handler():
    from repro.distributed import PreemptionHandler

    h = PreemptionHandler(install_signal=False)
    assert not h.requested
    h.request()
    assert h.requested
