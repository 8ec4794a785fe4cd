"""Plan-aware serving decode path.

The Engine resolves a decode-specialized ``block_m<=16`` KernelConfig
exactly ONCE at construction (the decode pool, ``op="decode"``), pins
separate prefill/decode configs over one param tree, and a full generate
builds plan metadata exactly once per phase — the decode loop's traced
plan is replayed for every step.
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.analysis import events
from repro.configs import smoke_config
from repro.core.grouped_gemm import _quant_weights, grouped_linear
from repro.core.moe import (MoEConfig, init_moe_params,
                            quantize_serving_weights)
from repro.core.quantization import QuantizedWeight, quantize_weight
from repro.kernels import plan as plan_mod
from repro.kernels.plan import DECODE_POOL, KernelConfig
from repro.models import model_zoo
from repro.models.model_zoo import make_model, synthetic_batch
from repro.models.transformer import quantize_serving_params
from repro.serve.engine import Engine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def moe_model():
    cfg = dataclasses.replace(smoke_config("qwen2-moe-a2.7b"),
                              precision="fp8",
                              gemm_backend="pallas_interpret")
    model = make_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    return model, params


def test_decode_config_resolved_once_per_engine(moe_model, monkeypatch,
                                                tmp_path):
    model, params = moe_model
    monkeypatch.setenv("REPRO_TILEPLAN_CACHE", str(tmp_path / "c.json"))
    selections = []
    real = plan_mod.decode_config
    monkeypatch.setattr(plan_mod, "decode_config",
                        lambda *a, **kw: selections.append(a) or
                        real(*a, **kw))
    engine = Engine(model, params, max_new_tokens=6, decode_batch_size=2)
    assert len(selections) == 1, "one decode selection per engine"
    assert engine.decode_config is not None
    assert engine.decode_config.block_m <= 16
    # prefill keeps its own (non-decode) geometry
    pf = engine.prefill_config
    assert pf is None or pf.block_m > 16
    # ...and a second generate-sized workload does not re-select
    batch = synthetic_batch(jax.random.PRNGKey(1), model.cfg, 16, 2)
    engine.generate(batch, key=jax.random.PRNGKey(2))
    assert len(selections) == 1


def test_generate_builds_one_plan_per_phase(moe_model, monkeypatch,
                                            tmp_path):
    """prefill + >=4 decode steps = exactly FOUR metadata builds: per
    phase trace, one for the routed experts and one for the shared-expert
    FFN's G=1 plan (the shared experts run fp8 since the precision
    bugfix, with their own plan-once group structure); the decode loop's
    scanned body replays its pair on every step without rebuilding."""
    model, params = moe_model
    monkeypatch.setenv("REPRO_TILEPLAN_CACHE", str(tmp_path / "c.json"))
    engine = Engine(model, params, max_new_tokens=6, decode_batch_size=2)
    builds = []
    inner = plan_mod.make_group_metadata
    monkeypatch.setattr(plan_mod, "make_group_metadata",
                        lambda *a, **kw: builds.append(a) or inner(*a, **kw))
    batch = synthetic_batch(jax.random.PRNGKey(1), model.cfg, 16, 2)
    res = engine.generate(batch, key=jax.random.PRNGKey(42))
    assert res.tokens.shape == (2, 6)            # 1 prefill + 5 decode
    assert len(builds) == 4, \
        f"two plan builds per phase (routed + shared), saw {len(builds)}"
    # per phase: one routed build (G=num_experts) + one shared G=1 build
    assert [b[3] for b in builds] == [model.cfg.moe.num_experts, 1,
                                      model.cfg.moe.num_experts, 1]
    # the decode phase's routed build runs under the decode-specialized
    # tiling
    assert int(builds[2][2]) == engine.decode_config.block_m


def test_explicit_decode_config_skips_selection(moe_model, monkeypatch):
    model, params = moe_model
    monkeypatch.setattr(plan_mod, "decode_config",
                        lambda *a, **kw: pytest.fail("selection ran"))
    pinned = KernelConfig(block_m=16, backend="pallas_interpret")
    engine = Engine(model, params, decode_kernel_config=pinned)
    assert engine.decode_config == pinned


def test_non_moe_model_has_no_decode_config(monkeypatch):
    monkeypatch.setattr(plan_mod, "decode_config",
                        lambda *a, **kw: pytest.fail("selection ran"))
    cfg = smoke_config("qwen3-1.7b")
    model = make_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    engine = Engine(model, params, max_new_tokens=2)
    assert engine.decode_config is None
    assert engine._decode_model is engine.model
    batch = synthetic_batch(jax.random.PRNGKey(1), cfg, 8, 1)
    assert engine.generate(batch).tokens.shape == (1, 2)


def test_decode_config_inherits_run_config_fields(moe_model, tmp_path,
                                                  monkeypatch):
    """The decode selection replaces tile geometry ONLY — backend,
    out_dtype, and wgrad_precision of a pinned run config survive."""
    model, params = moe_model
    monkeypatch.setenv("REPRO_TILEPLAN_CACHE", str(tmp_path / "c.json"))
    pinned = KernelConfig(block_m=256, backend="pallas_interpret",
                          out_dtype=jnp.float32)
    engine = Engine(model, params, kernel_config=pinned,
                    decode_batch_size=2)
    dc = engine.decode_config
    assert dc.block_m <= 16
    assert dc.backend == "pallas_interpret"
    assert dc.out_dtype == jnp.float32
    assert engine.prefill_config == pinned


def test_decode_autotune_uses_decode_pool_and_key(tmp_path):
    cache = str(tmp_path / "c.json")
    cfg = plan_mod.decode_config(16, 128, 128, 4,
                                 backend="pallas_interpret",
                                 cache_path=cache)
    assert cfg.block_m in {c.block_m for c in DECODE_POOL}
    entries = plan_mod.load_cache(cache)
    key = plan_mod.cache_key(plan_mod._device_kind(), "pallas_interpret",
                             16, 128, 128, 4, op="decode")
    assert key in entries and entries[key]["op"] == "decode"
    # distinct from a generic gemm tune of the same shape class
    plan_mod.autotune(16, 128, 128, 4, backend="pallas_interpret",
                      cache_path=cache, measure=False)
    assert len(plan_mod.load_cache(cache)) == 2


def test_decode_entries_never_rank_at_training_shapes():
    """The MXU-occupancy cost term confines block_m=8/16 to tiny M: at a
    training shape the ranked-first candidate keeps a full tile."""
    spec = plan_mod.device_spec("cpu")
    cands = plan_mod.candidate_pool(512, 512)
    best = min(cands, key=lambda c: plan_mod.estimate_cost_s(
        8192, 512, 512, 16, c, spec))
    assert best.block_m >= 64, best
    tiny = min(cands, key=lambda c: plan_mod.estimate_cost_s(
        8, 512, 512, 4, c, spec))
    assert tiny.block_m <= 16, tiny


def test_with_kernel_config_is_noop_on_match(moe_model):
    model, _ = moe_model
    assert model_zoo.with_kernel_config(model, model.cfg.kernel_config) \
        is model
    pinned = KernelConfig(block_m=16)
    rebuilt = model_zoo.with_kernel_config(model, pinned)
    assert rebuilt is not model
    assert rebuilt.cfg.kernel_config == pinned


def test_decode_output_matches_default_tiling(moe_model, tmp_path,
                                              monkeypatch):
    """Decode-specialized tiles are pure scheduling: greedy decode
    produces the same tokens as an engine pinned to the training
    geometry (same kernel arithmetic, different tile walk)."""
    model, params = moe_model
    monkeypatch.setenv("REPRO_TILEPLAN_CACHE", str(tmp_path / "c.json"))
    batch = synthetic_batch(jax.random.PRNGKey(1), model.cfg, 16, 2)
    fast = Engine(model, params, max_new_tokens=4, decode_batch_size=2)
    ref = Engine(model, params, max_new_tokens=4,
                 decode_kernel_config=KernelConfig(
                     backend="pallas_interpret"))
    t_fast = fast.generate(batch, key=jax.random.PRNGKey(7)).tokens
    t_ref = ref.generate(batch, key=jax.random.PRNGKey(7)).tokens
    np.testing.assert_array_equal(np.asarray(t_fast), np.asarray(t_ref))


# ---------------------------------------------------------------------------
# Weights quantized once at construction
# ---------------------------------------------------------------------------

def _fp8_records(tree):
    return [v for v in jax.tree.leaves(
        tree, is_leaf=lambda v: isinstance(v, QuantizedWeight))
        if isinstance(v, QuantizedWeight)]


def test_quantize_weight_is_the_gemms_own_weight_quantization():
    """The record holds bitwise what the fp8 GEMMs compute from the raw
    weight, stacked leading axes included."""
    w = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 256, 384),
                          jnp.bfloat16)
    rec = quantize_weight(w, backend="pallas_interpret")
    assert rec.shape == w.shape
    assert rec.q.dtype == jnp.float8_e4m3fn
    assert rec.scale.shape == (2, 3, 2, 3)
    cfg = KernelConfig(backend="pallas_interpret")
    for layer in range(2):
        q8, s = jax.jit(lambda v: _quant_weights(v, cfg))(w[layer])
        np.testing.assert_array_equal(np.asarray(rec.q[layer], np.float32),
                                      np.asarray(q8, np.float32))
        np.testing.assert_array_equal(np.asarray(rec.scale[layer]),
                                      np.asarray(s))


@pytest.mark.parametrize("fuse_producer", [False, True])
def test_prequantized_params_give_bitwise_logits(moe_model, fuse_producer):
    """prefill and decode_step over the pre-quantized tree equal those
    over the raw params bitwise, on the unfused path and the
    producer-fused FFN (routed and shared experts both fp8)."""
    model, params = moe_model
    model = model_zoo.with_kernel_config(model, KernelConfig(
        backend="pallas_interpret", fuse_producer=fuse_producer))
    served = quantize_serving_params(params, model.cfg)
    assert len(_fp8_records(served)) == 6        # 3 routed + 3 shared
    batch = synthetic_batch(jax.random.PRNGKey(1), model.cfg, 16, 2)
    prefill = jax.jit(model.prefill, static_argnames=("cache_capacity",))
    decode = jax.jit(model.decode_step)
    logits_raw, cache_raw = prefill(params, batch, cache_capacity=20)
    logits_q, cache_q = prefill(served, batch, cache_capacity=20)
    np.testing.assert_array_equal(np.asarray(logits_raw),
                                  np.asarray(logits_q))
    tok = jnp.argmax(logits_raw[:, -1:], -1).astype(jnp.int32)
    step_raw, _ = decode(params, tok, cache_raw)
    step_q, _ = decode(served, tok, cache_q)
    np.testing.assert_array_equal(np.asarray(step_raw), np.asarray(step_q))


def test_generate_matches_hand_rolled_decode_over_raw_params(
        moe_model, tmp_path, monkeypatch):
    model, params = moe_model
    monkeypatch.setenv("REPRO_TILEPLAN_CACHE", str(tmp_path / "c.json"))
    engine = Engine(model, params, max_new_tokens=4, decode_batch_size=2)
    assert engine.quantized_weights == 6
    batch = synthetic_batch(jax.random.PRNGKey(1), model.cfg, 16, 2)
    got = engine.generate(batch, key=jax.random.PRNGKey(5)).tokens
    logits, cache = jax.jit(engine.model.prefill,
                            static_argnames=("cache_capacity",))(
        params, batch, cache_capacity=20)
    decode = jax.jit(engine._decode_model.decode_step)
    tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
    want = [tok]
    for _ in range(3):
        logits, cache = decode(params, tok[:, None], cache)
        tok = jnp.argmax(logits[:, 0], -1).astype(jnp.int32)
        want.append(tok)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(jnp.stack(want, 1)))


def test_served_programs_trace_no_weight_quantize(moe_model, tmp_path,
                                                  monkeypatch):
    """The Engine's prefill and decode loop trace zero weight quantizes;
    the same model over raw params traces six a step (3 routed + 3
    shared), as training does every step."""
    model, params = moe_model
    monkeypatch.setenv("REPRO_TILEPLAN_CACHE", str(tmp_path / "c.json"))
    engine = Engine(model, params, max_new_tokens=3, decode_batch_size=2)
    batch = synthetic_batch(jax.random.PRNGKey(1), model.cfg, 16, 2)
    with events.capture() as seen:
        engine.generate(batch)
    assert events.count(seen, "quantize_blockwise") == 0
    assert sum(e.data["family"] == "gemm" for e in events.of_kind(
        seen, "backend_resolved")) > 0       # the GEMMs did trace
    _, cache = jax.eval_shape(
        lambda p: model.prefill(p, batch, cache_capacity=19), params)
    tok = jax.ShapeDtypeStruct((2, 1), jnp.int32)
    with events.capture() as seen:
        jax.eval_shape(model.decode_step, params, tok, cache)
    assert events.count(seen, "quantize_blockwise") == 6


def test_raw_weights_quantize_once_per_gemm():
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 128), jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 128, 256),
                          jnp.bfloat16)
    gs = jnp.array([20, 0, 40], jnp.int32)
    cfg = KernelConfig(backend="pallas_interpret")
    with events.capture() as seen:
        jax.eval_shape(lambda x, w: grouped_linear(
            x, w, gs, precision="fp8", config=cfg), x, w)
    assert [e.data["shape"] for e in events.of_kind(
        seen, "quantize_blockwise")] == [(3, 128, 256)]
    rec = quantize_weight(w, backend="pallas_interpret")
    with events.capture() as seen:
        y = grouped_linear(x, rec, gs, precision="fp8", config=cfg)
    assert events.count(seen, "quantize_blockwise") == 0
    np.testing.assert_array_equal(
        np.asarray(y, np.float32),
        np.asarray(grouped_linear(x, w, gs, precision="fp8", config=cfg),
                   np.float32))


def test_grad_through_quantized_weight_raises():
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 128), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 128, 128), jnp.float32)
    gs = jnp.array([20, 0, 40], jnp.int32)
    rec = quantize_weight(w, backend="pallas_interpret")
    cfg = KernelConfig(backend="pallas_interpret")

    def loss(x):
        y = grouped_linear(x, rec, gs, precision="fp8", config=cfg)
        return jnp.sum(y.astype(jnp.float32) ** 2)
    with pytest.raises(ValueError, match="serving-only"):
        jax.grad(loss)(x)


def test_weights_off_the_fp8_paths_stay_raw():
    """Only what moe_apply quantizes becomes a record: shared experts
    whose width is no multiple of 128 and the dense dispatch's experts
    run bf16 and stay raw; a bf16 layer is returned as it is."""
    cfg = MoEConfig(num_experts=4, top_k=2, d_model=128, d_ff_expert=96,
                    num_shared_experts=1, precision="fp8",
                    backend="pallas_interpret")
    params = init_moe_params(jax.random.PRNGKey(0), cfg)
    served = quantize_serving_weights(params, cfg)
    assert sorted(k for k, v in served.items()
                  if isinstance(v, QuantizedWeight)) == [
        "w_down", "w_gate", "w_up"]
    assert all(served[k] is params[k]
               for k in ("router", "shared_gate", "shared_up",
                         "shared_down"))
    dense = dataclasses.replace(cfg, dispatch="dense")
    assert _fp8_records(quantize_serving_weights(params, dense)) == []
    bf16 = dataclasses.replace(cfg, precision="bf16")
    assert quantize_serving_weights(params, bf16) is params


def test_bf16_engine_serves_the_input_tree(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TILEPLAN_CACHE", str(tmp_path / "c.json"))
    cfg = dataclasses.replace(smoke_config("qwen2-moe-a2.7b"),
                              precision="bf16")
    model = make_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    engine = Engine(model, params, max_new_tokens=2, decode_batch_size=2)
    assert engine.params is params
    assert engine.quantized_weights == 0


def test_sharded_engine_serves_the_input_tree():
    """Under a mesh with a model axis over 1 the MoE layer shards raw
    weights, so the Engine keeps the input tree."""
    code = textwrap.dedent("""
        import dataclasses, os, tempfile
        import jax
        from repro.configs import smoke_config
        from repro.distributed import context as dctx
        from repro.launch.mesh import make_mesh
        from repro.models.model_zoo import make_model
        from repro.serve.engine import Engine
        os.environ["REPRO_TILEPLAN_CACHE"] = os.path.join(
            tempfile.mkdtemp(), "c.json")
        cfg = dataclasses.replace(smoke_config("qwen2-moe-a2.7b"),
                                  precision="fp8",
                                  gemm_backend="pallas_interpret")
        model = make_model(cfg)
        params = model.init_params(jax.random.PRNGKey(0))
        dctx.set_mesh(make_mesh((1, 2), ("data", "model")))
        engine = Engine(model, params, max_new_tokens=2,
                        decode_batch_size=2)
        assert engine.params is params, "sharded engine re-quantized"
        assert engine.quantized_weights == 0
        dctx.set_mesh(None)
        assert Engine(model, params, max_new_tokens=2,
                      decode_batch_size=2).quantized_weights == 6
        print("SHARDED_OK")
    """)
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=os.path.join(REPO, "src"))
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, cwd=REPO, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    assert "SHARDED_OK" in p.stdout
