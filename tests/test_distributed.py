"""Distribution tests on 8 simulated devices (subprocess so the main test
process keeps its single-device jax).

Covers: EP-sharded MoE == single-device reference; sharded train step runs
and matches unsharded loss; dryrun lower/compile on a small mesh.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

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


def test_moe_ep_matches_single_device():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core.moe import (MoEConfig, init_moe_params, moe_apply,
                                    shard_moe_params)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = MoEConfig(num_experts=8, top_k=2, d_model=128, d_ff_expert=64,
                        num_shared_experts=1, capacity_factor=8.0)
        params = init_moe_params(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 32, 128))
        y_ref, _ = moe_apply(params, x.reshape(-1, 128), cfg)
        y_ref = y_ref.reshape(x.shape)
        ep = 4
        pspecs = shard_moe_params(params, cfg, ep)
        xspec = P("data", None, None)
        def local_fn(p, xl):
            rank = jax.lax.axis_index("model")
            b, s, d = xl.shape
            y, _ = moe_apply(p, xl.reshape(b*s, d), cfg, ep_rank=rank,
                             ep_size=ep, axis_name="model")
            return y.reshape(b, s, d)
        fn = jax.jit(jax.shard_map(local_fn, mesh=mesh,
                                   in_specs=(pspecs, xspec),
                                   out_specs=xspec, check_vma=False))
        y = fn(params, x)
        err = float(jnp.max(jnp.abs(y - y_ref)))
        assert err < 1e-3, err
        print("EP_OK", err)
    """)
    assert "EP_OK" in out


def test_moe_tp_fallback_matches_single_device():
    out = _run("""
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.core.moe import (MoEConfig, init_moe_params, moe_apply,
                                    shard_moe_params)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        # 6 experts % 4 != 0 -> TP-on-d_ff fallback (qwen2-moe regime)
        cfg = MoEConfig(num_experts=6, top_k=2, d_model=128, d_ff_expert=64,
                        num_shared_experts=1)
        params = init_moe_params(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, 128))
        y_ref, _ = moe_apply(params, x.reshape(-1, 128), cfg)
        pspecs = shard_moe_params(params, cfg, 1)
        xspec = P("data", None, None)
        def local_fn(p, xl):
            b, s, d = xl.shape
            y, _ = moe_apply(p, xl.reshape(b*s, d), cfg, ep_rank=0,
                             ep_size=1, axis_name="model")
            return y.reshape(b, s, d)
        fn = jax.jit(jax.shard_map(local_fn, mesh=mesh,
                                   in_specs=(pspecs, xspec),
                                   out_specs=xspec, check_vma=False))
        y = fn(params, x)
        err = float(jnp.max(jnp.abs(y.reshape(-1, 128) - y_ref)))
        assert err < 1e-3, err
        print("TP_OK", err)
    """)
    assert "TP_OK" in out


def test_sharded_train_step_matches_unsharded():
    out = _run("""
        import dataclasses, jax, jax.numpy as jnp
        from repro.configs import smoke_config
        from repro.distributed import context as dctx
        from repro.distributed.sharding import named_shardings
        from repro.models.model_zoo import make_model, synthetic_batch
        from repro.optim import adamw
        from repro.train.trainer import make_train_step

        cfg = dataclasses.replace(smoke_config("deepseek-moe-16b"),
                                  dtype=jnp.float32)
        model = make_model(cfg)
        params = model.init_params(jax.random.PRNGKey(0))
        batch = synthetic_batch(jax.random.PRNGKey(1), cfg, 64, 8)
        opt_cfg = adamw.OptConfig(use_master=False)
        opt = adamw.init_opt_state(params, opt_cfg)
        step = make_train_step(model.loss, opt_cfg, grad_accum=2)

        # unsharded reference
        _, _, m_ref = jax.jit(step)(params, opt, batch)

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        dctx.set_mesh(mesh)
        pshard = named_shardings(params, mesh, moe_mode="ep")
        params_s = jax.device_put(params, pshard)
        opt_s = adamw.init_opt_state(params_s, opt_cfg)
        _, _, m = jax.jit(step)(params_s, opt_s, batch)
        a, b = float(m_ref["loss"]), float(m["loss"])
        assert abs(a - b) / abs(a) < 2e-2, (a, b)
        print("TRAIN_OK", a, b)
    """)
    assert "TRAIN_OK" in out


def test_dryrun_lowers_on_small_mesh():
    """The dryrun machinery itself (specs, shardings, analyzer) on an
    8-device mesh with a reduced arch — fast end-to-end coverage."""
    out = _run("""
        import jax
        import repro.launch.dryrun as d
        from repro.configs import smoke_config
        import repro.launch.mesh as mesh_mod
        # shrink the production mesh for the test
        mesh_mod.make_production_mesh = \\
            lambda multi_pod=False: mesh_mod.make_mesh(
                (2, 2, 2) if multi_pod else (4, 2),
                ("pod", "data", "model") if multi_pod else ("data", "model"))
        d.make_production_mesh = mesh_mod.make_production_mesh
        import repro.configs as C
        real_get = C.get_config
        import repro.launch.dryrun as dd
        dd.get_config = lambda a: smoke_config(a)
        dd.SHAPES = {k: v for k, v in d.SHAPES.items()}
        import dataclasses
        dd.SHAPES["train_4k"] = dataclasses.replace(
            d.SHAPES["train_4k"], seq_len=128, global_batch=8)
        dd.SHAPES["decode_32k"] = dataclasses.replace(
            d.SHAPES["decode_32k"], seq_len=256, global_batch=8)
        for arch in ("deepseek-moe-16b", "recurrentgemma-2b"):
            for shape in ("train_4k", "decode_32k"):
                rec = dd.lower_cell(arch, shape, multi_pod=False)
                assert rec["ok"], rec
                assert rec["cost"]["flops_per_device"] > 0
        rec = dd.lower_cell("qwen3-1.7b", "train_4k", multi_pod=True)
        assert rec["ok"]
        print("DRYRUN_OK")
    """, devices=8)
    assert "DRYRUN_OK" in out
