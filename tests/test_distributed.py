"""Multi-device behaviour via subprocesses (the parent process must keep the
real single-CPU device view; only the dry-run and these children force a
host-platform device count)."""

import os
import subprocess
import sys
import textwrap


_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(script: str, devices: int = 8, timeout: int = 420) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = _SRC
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(script)],
        env=env, capture_output=True, text=True, timeout=timeout,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_gpipe_pipeline_matches_sequential():
    _run("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import Mesh
        from repro.runtime.pipeline import gpipe_forward
        mesh = Mesh(np.asarray(jax.devices()[:4]), ("pod",))
        L, d = 8, 16
        Ws = 0.3 * jax.random.normal(jax.random.key(0), (L, d, d))
        def stage_fn(stage_W, x):
            return jax.lax.scan(lambda x, w: (jnp.tanh(x @ w), None), x, stage_W)[0]
        x = jax.random.normal(jax.random.key(1), (3, 4, d))
        out = jax.jit(gpipe_forward(stage_fn, mesh))(Ws, x)
        ref = x
        for i in range(L):
            ref = jnp.tanh(ref @ Ws[i])
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)
        print("OK")
    """)


def test_int8_error_feedback_compression():
    _run("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.optim.compression import ErrorFeedbackInt8
        mesh = Mesh(np.asarray(jax.devices()[:2]), ("pod",))
        comp = ErrorFeedbackInt8(axis="pod")
        g = jax.random.normal(jax.random.key(2), (2, 256))
        def f(gsh, esh):
            out, err = comp.reduce_mean({"w": gsh}, {"w": esh})
            return out["w"], err["w"]
        fm = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=(P("pod"), P("pod")),
                                   out_specs=(P(), P("pod")), check_vma=False))
        want = np.asarray(g).mean(0)
        # single shot: bounded quantization error (int8 against a shared
        # max-scale: ~scale/2 per element)
        red, err = fm(g, jnp.zeros((2, 256)))
        rel = np.abs(np.asarray(red).reshape(-1, 256)[0] - want).max() / np.abs(want).max()
        assert rel < 0.08, rel
        # error feedback: average of repeated reductions converges to exact
        e = jnp.zeros((2, 256)); acc = np.zeros(256)
        for i in range(16):
            red, e = fm(g, e)
            acc += np.asarray(red).reshape(-1, 256)[0]
        rel2 = np.abs(acc / 16 - want).max() / np.abs(want).max()
        # error feedback must drive the *time-averaged* estimate well below
        # the one-shot quantization error (measured ≈8× better)
        assert rel2 < rel / 2, (rel2, rel)
        print("OK", rel, rel2)
    """)


def test_production_sharding_on_mini_mesh():
    """The exact dry-run machinery at (2,2,2): train/prefill/decode of a
    smoke config compile AND execute with real sharded buffers."""
    _run("""
        import functools, numpy as np, jax, jax.numpy as jnp
        from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
        import dataclasses
        from repro.configs import get_smoke_config
        from repro.models import Model
        from repro.optim import AdamW
        from repro.optim.schedule import warmup_cosine
        from repro.runtime.sharding import (ShardingRules, batch_pspec,
            cache_pspecs, make_activation_sharder, param_pspecs)
        from repro.runtime.steps import make_train_step

        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                             axis_types=(AxisType.Auto,) * 3)
        for arch in ("granite-3-8b", "mixtral-8x22b", "jamba-1.5-large-398b", "xlstm-350m"):
            cfg = dataclasses.replace(get_smoke_config(arch), dtype="float32")
            rules = ShardingRules(mesh=mesh, data_axes=("pod", "data"), seq_shard=True)
            model = Model(cfg, shard_activation=make_activation_sharder(rules), remat=True)
            params = model.init(jax.random.key(0))
            p_sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                                param_pspecs(params, rules),
                                is_leaf=lambda x: isinstance(x, P))
            params = jax.device_put(params, p_sh)
            opt = AdamW()
            opt_state = opt.init(params)
            sched = functools.partial(warmup_cosine, peak_lr=1e-3, warmup_steps=1, total_steps=10)
            step = jax.jit(make_train_step(model, opt, sched), donate_argnums=(0, 1))
            B, T = 8, 16
            batch = {"tokens": jax.random.randint(jax.random.key(1), (B, T), 0, cfg.vocab),
                     "labels": jax.random.randint(jax.random.key(2), (B, T), 0, cfg.vocab)}
            params, opt_state, m = step(params, opt_state, batch)
            assert np.isfinite(float(m["loss"])), arch
            # decode under the same mesh
            cache = model.init_cache(B, 32)
            c_sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                                cache_pspecs(cache, rules),
                                is_leaf=lambda x: isinstance(x, P))
            cache = jax.device_put(cache, c_sh)
            dstep = jax.jit(model.decode_step)
            logits, cache = dstep(params, cache, batch["tokens"][:, 0], jnp.int32(0))
            assert np.all(np.isfinite(np.asarray(logits))), arch
            print(arch, "OK", float(m["loss"]))
    """, devices=8, timeout=560)


def test_elastic_restore_under_new_mesh():
    """Checkpoint under (4 data, 1 model) restores under (2 data, 1 model)."""
    _run("""
        import numpy as np, jax, jax.numpy as jnp, tempfile
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint import Checkpointer
        from repro.runtime.elastic import build_mesh, plan_remesh
        devs = jax.devices()
        m1 = build_mesh(devs, 4, 1)
        w = jax.device_put(jnp.arange(64.0).reshape(8, 8),
                           NamedSharding(m1, P("data", None)))
        with tempfile.TemporaryDirectory() as td:
            ck = Checkpointer(td)
            ck.save(1, {"w": w}, blocking=True)
            plan = plan_remesh((4, 1), 2)
            m2 = build_mesh(devs, plan.data, plan.model)
            tmpl = jax.device_put(jnp.zeros((8, 8)), NamedSharding(m2, P("data", None)))
            step, restored = ck.restore({"w": tmpl})
            np.testing.assert_array_equal(np.asarray(restored["w"]), np.asarray(w))
            assert restored["w"].sharding.mesh.shape["data"] == 2
            print("OK")
    """)
