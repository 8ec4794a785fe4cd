"""Elastic scaling: a checkpoint saved under one mesh restores onto a
different (smaller) mesh — the restart path after losing nodes.

Checkpoints store full logical arrays; shardings are re-derived from the
logical partition rules for whatever mesh the surviving devices form
(launch/mesh.py:make_mesh_for), so resharding is free at restore time.
"""
import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, devices: int = 8):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, cwd=REPO,
                       timeout=900)
    assert p.returncode == 0, f"STDOUT:\n{p.stdout}\nSTDERR:\n{p.stderr}"
    return p.stdout


def test_checkpoint_reshards_onto_smaller_mesh(tmp_path):
    out = _run(f"""
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from repro.checkpoint import checkpointer as ckpt
        from repro.configs import smoke_config
        from repro.distributed import context as dctx
        from repro.distributed.sharding import named_shardings
        from repro.launch.mesh import make_mesh, make_mesh_for
        from repro.models.model_zoo import make_model, synthetic_batch

        cfg = dataclasses.replace(smoke_config("qwen3-1.7b"),
                                  dtype=jnp.float32)
        model = make_model(cfg)
        params = model.init_params(jax.random.PRNGKey(0))

        # "before failure": 8 devices, (4 data, 2 model)
        mesh8 = make_mesh_for(8, model_parallel=2)
        p8 = jax.device_put(params, named_shardings(params, mesh8))
        ckpt.save({str(tmp_path)!r}, 7, {{"params": p8}})

        # "after failure": 4 surviving devices, (2 data, 2 model)
        mesh4 = make_mesh((2, 2), ("data", "model"),
                          devices=jax.devices()[:4])
        like = jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                              sharding=s),
            params, named_shardings(params, mesh4))
        restored, meta, step = ckpt.restore_latest(
            {str(tmp_path)!r}, {{"params": like}})
        assert step == 7
        # values identical, shardings re-derived for the new mesh
        for a, b in zip(jax.tree.leaves(params),
                        jax.tree.leaves(restored["params"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        devs = {{d for leaf in jax.tree.leaves(restored["params"])
                for d in leaf.sharding.device_set}}
        assert len(devs) <= 4
        # and the restored params still run a forward pass on the new mesh
        dctx.set_mesh(mesh4)
        batch = synthetic_batch(jax.random.PRNGKey(1), cfg, 64, 4)
        loss, _ = jax.jit(model.loss)(restored["params"], batch)
        assert bool(jnp.isfinite(loss))
        print("ELASTIC_OK", float(loss))
    """)
    assert "ELASTIC_OK" in out
