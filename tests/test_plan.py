"""TilePlan subsystem: KernelConfig validation, plan-once/run-many reuse,
the block-shape pool autotuner + its persistent cache, and the empty-group
edge cases of the metadata schedule.

The two load-bearing pins:

  * ``test_moe_fwd_bwd_builds_metadata_exactly_once`` — one MoE
    forward+backward builds group metadata ONCE (counting monkeypatch),
    i.e. the plan is genuinely shared across gate/up/down + dgrads;
  * ``test_moe_fp8_bitwise_golden`` — outputs/grads on
    ``pallas_interpret`` agree with the same layer on the ``xla_exact``
    oracles.
"""
import dataclasses
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.moe import MoEConfig, _capacity, init_moe_params, moe_apply
from repro.kernels import dispatch, ref
from repro.kernels import plan as plan_mod
from repro.kernels.grouped_gemm_kernel import gmm_pallas
from repro.kernels.plan import (CONFIG_POOL, KernelConfig, autotune,
                                candidate_pool, estimate_cost_s,
                                make_group_metadata, make_tile_plan)


# ---------------------------------------------------------------------------
# KernelConfig
# ---------------------------------------------------------------------------

def test_kernel_config_static_validation():
    with pytest.raises(ValueError):
        KernelConfig(block_n=64)          # lane width
    with pytest.raises(ValueError):
        KernelConfig(block_k=100)         # quant tile
    with pytest.raises(ValueError):
        KernelConfig(block_m=12)          # sublane


def test_kernel_config_shape_validation():
    cfg = KernelConfig()
    with pytest.raises(ValueError):
        cfg.validate(100, 100, 128)       # K % block_k
    with pytest.raises(ValueError):
        cfg.validate(100, 128, 100)       # N % block_n
    assert cfg.validate(100, 128, 128) is cfg
    assert cfg.compatible(256, 256) and not cfg.compatible(100, 128)


def test_kernel_config_roundtrip_and_default():
    cfg = KernelConfig(block_m=256, backend="pallas_interpret",
                       out_dtype=jnp.float32)
    assert KernelConfig.from_dict(cfg.to_dict()) == cfg
    # per-device defaults always produce a legal config
    for kind in ("cpu", "TPU v5e", "TPU v4", "weird-accelerator"):
        KernelConfig.default(kind).validate(64, 256, 256)


def test_default_config_seam():
    pinned = KernelConfig(block_m=512)
    with plan_mod.default_config(pinned):
        assert plan_mod.get_default_config() == pinned
        assert plan_mod.resolve_config(None).block_m == 512
        # explicit config and per-call overrides win over the default
        assert plan_mod.resolve_config(KernelConfig()).block_m == 128
        assert plan_mod.resolve_config(
            None, backend="xla_exact").backend == "xla_exact"
    assert plan_mod.get_default_config().block_m != 512


# ---------------------------------------------------------------------------
# TilePlan construction + reuse
# ---------------------------------------------------------------------------

def _quantized(sizes, k, n, seed=0):
    rng = np.random.default_rng(seed)
    m = int(np.sum(sizes))
    a8, sa = ref.quantize_tilewise_ref(
        jnp.asarray(rng.standard_normal((m, k)), jnp.float32))
    b8, sb = jax.vmap(ref.quantize_blockwise_ref)(
        jnp.asarray(rng.standard_normal((len(sizes), k, n)), jnp.float32))
    return a8, sa, b8, sb, jnp.asarray(sizes, jnp.int32)


def test_tile_plan_matches_inline_metadata():
    gs = jnp.asarray([100, 0, 37, 163], jnp.int32)
    plan = make_tile_plan(gs, 300, block_m=128)
    offs, gids, tids = make_group_metadata(gs, 300, 128, 4)
    np.testing.assert_array_equal(np.asarray(plan.group_offsets),
                                  np.asarray(offs))
    np.testing.assert_array_equal(np.asarray(plan.group_ids),
                                  np.asarray(gids))
    np.testing.assert_array_equal(np.asarray(plan.m_tile_ids),
                                  np.asarray(tids))
    assert plan.num_tiles == 3 and plan.max_visits == 6
    assert int(plan.total_rows()) == 300


def test_tile_plan_is_pytree():
    gs = jnp.asarray([8, 8], jnp.int32)
    plan = make_tile_plan(gs, 16, block_m=8)
    leaves, treedef = jax.tree_util.tree_flatten(plan)
    assert len(leaves) == 3
    rebuilt = jax.tree_util.tree_unflatten(treedef, leaves)
    assert rebuilt.block_m == 8 and rebuilt.m == 16


def test_plan_mismatch_rejected():
    gs = jnp.asarray([64, 64], jnp.int32)
    a8, sa, b8, sb, gs = _quantized([64, 64], 128, 128)
    plan = make_tile_plan(gs, 128, block_m=64)
    with pytest.raises(ValueError, match="TilePlan built for"):
        gmm_pallas(a8, sa, b8, sb, gs, out_dtype=jnp.float32,
                   interpret=True, plan=plan)   # kernel block_m=128


@pytest.mark.parametrize("sizes", [[100, 0, 37, 163], [1, 1, 1, 1],
                                   [0, 0, 512], [5, 250, 3, 127, 129]])
def test_precomputed_plan_bitwise_equals_plan_free(sizes):
    a8, sa, b8, sb, gs = _quantized(sizes, 256, 128, seed=sum(sizes))
    plan = make_tile_plan(gs, int(np.sum(sizes)), block_m=128)
    free = dispatch.grouped_gemm_fp8(a8, sa, b8, sb, gs,
                                     backend="pallas_interpret",
                                     out_dtype=jnp.float32)
    planned = dispatch.grouped_gemm_fp8(a8, sa, b8, sb, gs,
                                        backend="pallas_interpret",
                                        out_dtype=jnp.float32, plan=plan)
    np.testing.assert_array_equal(np.asarray(free), np.asarray(planned))


# ---------------------------------------------------------------------------
# Empty-group edge cases (satellite: num_real == 0)
# ---------------------------------------------------------------------------

def test_metadata_all_groups_empty_is_safe():
    gs = jnp.zeros((4,), jnp.int32)
    offs, gids, tids = make_group_metadata(gs, 256, 128, 4)
    assert np.asarray(offs).tolist() == [0] * 5
    # zero real visits: every visit is a padding visit pinned to group 0
    # (whose row range is empty) sweeping the tail tiles so the kernel
    # zero-fills the whole buffer; nothing negative / out of range
    assert np.all(np.asarray(gids) == 0)
    tids = np.asarray(tids)
    assert np.all((tids >= 0) & (tids < 2))
    # the sweep covers every tile (both tiles of the 256-row buffer)
    assert set(tids.tolist()) == {0, 1}


def test_metadata_padding_visits_sweep_tail_tiles():
    """sum(group_sizes) < M: the padding visits walk the tiles beyond the
    last owned row (so the kernel's store zero-fills them) instead of
    replicating the last real visit."""
    gs = jnp.asarray([60, 30], jnp.int32)         # total=90, 2 tiles of 128
    offs, gids, tids = make_group_metadata(gs, 256, 128, 2)
    real = [(int(g), int(t)) for g, t in zip(gids, tids)]
    # real visits: both groups in tile 0; the one padding visit covers
    # tail tile 1 (keeping the last real group id — empty range there)
    assert real == [(0, 0), (1, 0), (1, 1)]


def test_metadata_m_zero_is_safe():
    gs = jnp.zeros((3,), jnp.int32)
    offs, gids, tids = make_group_metadata(gs, 0, 128, 3)
    assert np.all(np.asarray(gids) >= 0) and np.all(np.asarray(tids) >= 0)


def test_gmm_all_zero_group_sizes_returns_zeros():
    a8, sa, b8, sb, _ = _quantized([128, 128], 128, 128)
    gs0 = jnp.zeros((2,), jnp.int32)
    out = gmm_pallas(a8, sa, b8, sb, gs0, out_dtype=jnp.float32,
                     interpret=True)
    assert out.shape == (256, 128)
    assert np.all(np.asarray(out) == 0.0)


def test_gmm_m_zero_returns_empty():
    a8, sa, b8, sb, _ = _quantized([128], 128, 128)
    out = gmm_pallas(a8[:0], sa[:0], b8, sb, jnp.zeros((1,), jnp.int32),
                     out_dtype=jnp.float32, interpret=True)
    assert out.shape == (0, 128)


# ---------------------------------------------------------------------------
# MoE: plan-once/run-many + bitwise golden vs pre-refactor
# ---------------------------------------------------------------------------

def _moe_fixture():
    cfg = MoEConfig(num_experts=4, top_k=2, d_model=128, d_ff_expert=128,
                    num_shared_experts=1, precision="fp8",
                    backend="pallas_interpret")
    params = init_moe_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (64, cfg.d_model))
    return cfg, params, x


def _moe_loss(cfg):
    def loss(p, x):
        y, _ = moe_apply(p, x, cfg)
        return jnp.sum(y * jnp.cos(jnp.arange(y.size).reshape(y.shape))), y
    return loss


def test_moe_fwd_bwd_builds_metadata_exactly_once(monkeypatch):
    """One moe_apply forward+backward = ONE group-metadata build per
    group structure: the routed TilePlan is constructed per routing
    decision and shared by the gate/up/down forward GEMMs and both
    dgrads in the custom VJP; the shared-expert FFN (fp8 since the
    precision bugfix) adds exactly one G=1 plan of its own."""
    cfg, params, x = _moe_fixture()
    calls = []
    inner = plan_mod.make_group_metadata

    def counting(*a, **kw):
        calls.append(a)
        return inner(*a, **kw)

    monkeypatch.setattr(plan_mod, "make_group_metadata", counting)
    loss = _moe_loss(cfg)
    jax.grad(lambda p: loss(p, x)[0])(params)   # fresh fwd+bwd trace
    assert len(calls) == 2, \
        f"expected one routed + one shared metadata build, saw {len(calls)}"
    assert [c[3] for c in calls] == [cfg.num_experts, 1]


@pytest.mark.slow
def test_moe_fp8_bitwise_golden():
    """The fp8 MoE fwd+bwd on the kernel path agrees with the same layer
    on the ``xla_exact`` oracles (per-128-K-block f32 GEMMs, dense f32
    wgrad, reference quantizers).  The oracle shares the routing and the
    quantization recipe, so what is left is f32 reassociation: the
    forward and loss agree to ~1e-6 of their scale, the grads to ~1e-4."""
    cfg, params, x = _moe_fixture()
    (l, y), g = jax.value_and_grad(_moe_loss(cfg), has_aux=True)(params, x)
    oracle = dataclasses.replace(cfg, backend="xla_exact")
    (l_o, y_o), g_o = jax.value_and_grad(_moe_loss(oracle),
                                         has_aux=True)(params, x)

    def rel(a, b):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        return float(np.abs(a - b).max() / np.abs(b).max())

    assert rel(y, y_o) < 1e-5
    assert abs(float(l) - float(l_o)) < 1e-5 * abs(float(l_o))
    assert set(g) == set(g_o)
    for name in g:
        assert rel(g[name], g_o[name]) < 1e-3, name


@pytest.mark.parametrize("kind,want", [("TPU v5 lite", "tpu v5e"),
                                       ("TPU v4", "tpu"), ("cpu", "cpu")])
def test_device_spec_matches_known_kinds(kind, want):
    assert plan_mod.device_spec(kind) is plan_mod.DEVICE_SPECS[want]


def test_device_spec_raises_on_unknown_kind():
    """An unknown accelerator gets no borrowed CPU numbers."""
    with pytest.raises(ValueError, match="unknown accelerator"):
        plan_mod.device_spec("unknown accelerator")


def test_capacity_respects_block_m_alignment():
    # non-default tile heights must drive the capacity round-up
    assert _capacity(49152, 16, 2.0) == 6144            # 128-aligned default
    assert _capacity(49152, 16, 2.0, align=256) == 6144  # already aligned
    assert _capacity(1000, 4, 2.0, align=64) % 64 == 0
    assert _capacity(1000, 4, 2.0, align=512) == 512
    # the clamp itself is aligned now: tiny decode shapes round up to one
    # tile instead of returning the unaligned slot count
    assert _capacity(48, 16, 2.0, align=256) == 256


def test_moe_with_nondefault_kernel_config_runs():
    cfg, params, x = _moe_fixture()
    import dataclasses
    cfg = dataclasses.replace(
        cfg, kernel_config=KernelConfig(block_m=64,
                                        backend="pallas_interpret"))
    y, aux = moe_apply(params, x, cfg)
    assert bool(jnp.isfinite(y).all())


# ---------------------------------------------------------------------------
# Pool + cost model + autotuner cache
# ---------------------------------------------------------------------------

def test_candidate_pool_filters_legality():
    cands = candidate_pool(256, 128)
    assert cands and all(c.compatible(256, 128) for c in cands)
    assert all(c.block_n == 128 for c in cands)     # N=128 excludes bn=256
    # the pool spans the training tile heights AND the decode-specialized
    # tiny-M entries (block_m=8/16, serving's per-step grouped GEMM)
    assert {c.block_m for c in candidate_pool(512, 512)} == \
        {8, 16, 64, 128, 256, 512}
    assert {c.block_m for c in plan_mod.DECODE_POOL} == {8, 16}


def test_candidate_pool_requires_transposed_legality():
    """The fp8 VJP dgrad runs the transposed GEMM under the same config:
    a (K=128, N=256)-forward-legal block_n=256 entry would crash every
    backward (N'=128 % 256 != 0) and must not be selectable."""
    for c in candidate_pool(128, 256):
        assert c.compatible(256, 128), c            # transposed orientation
    assert any(c.block_n == 256 for c in CONFIG_POOL
               if c.compatible(128, 256))           # ...though fwd-legal
    # and the full train path holds for an autotuned rectangular shape
    from repro.core.grouped_gemm import grouped_linear
    cfg = candidate_pool(128, 256)[0].with_(backend="pallas_interpret")
    x = jnp.ones((32, 128), jnp.float32)
    w = jnp.ones((2, 128, 256), jnp.float32)
    gs = jnp.asarray([20, 12], jnp.int32)
    jax.grad(lambda x_: jnp.sum(grouped_linear(
        x_, w, gs, precision="fp8", config=cfg)))(x)   # must not raise


def test_cost_model_prefers_fewer_boundary_tiles():
    # many tiny groups -> small block_m wins (fewer inflated visits);
    # one huge group -> visit counts equalize and taller tiles never lose
    small = estimate_cost_s(4096, 512, 512, 64, KernelConfig(block_m=64))
    big = estimate_cost_s(4096, 512, 512, 64, KernelConfig(block_m=512))
    assert small < big


def test_autotune_persists_and_reloads_identically(tmp_path, monkeypatch):
    """Satellite: write -> load -> identical selection, without
    re-measuring on the cache hit."""
    cache = str(tmp_path / "tileplan_cache.json")
    measured = []
    real = plan_mod._measure_candidate

    def counting(*a, **kw):
        measured.append(a)
        return real(*a, iters=1, warmup=0, **{k: v for k, v in kw.items()
                                              if k not in ("iters", "warmup")})

    monkeypatch.setattr(plan_mod, "_measure_candidate", counting)
    first = autotune(256, 128, 128, 4, backend="pallas_interpret",
                     cache_path=cache, max_candidates=2)
    assert os.path.exists(cache)
    assert measured, "live-backend measurement should have run"

    n_measured = len(measured)
    plan_mod.clear_cache_memo()            # force a re-read from disk
    second = autotune(256, 128, 128, 4, backend="pallas_interpret",
                      cache_path=cache, max_candidates=2)
    assert second == first
    assert len(measured) == n_measured, "cache hit must not re-measure"


def test_autotune_cost_model_only_on_tile_free_backend(tmp_path,
                                                       monkeypatch):
    """xla backends ignore tile shapes -> pure cost-model selection, no
    measurement, still cached."""
    if not dispatch.availability("xla_ragged")[0]:
        pytest.skip("no ragged_dot in this jax")
    cache = str(tmp_path / "c.json")
    monkeypatch.setattr(plan_mod, "_measure_candidate",
                        lambda *a, **kw: pytest.fail("measured a "
                                                     "tile-free backend"))
    cfg = autotune(1024, 256, 256, 8, backend="xla_ragged",
                   cache_path=cache)
    assert cfg.backend == "xla_ragged"
    entries = plan_mod.load_cache(cache)
    (entry,) = entries.values()
    assert entry["source"] == "cost_model"


def test_pinned_out_dtype_honoured_everywhere():
    """A config with a pinned out_dtype must produce that dtype from every
    entry point; with out_dtype=None (the default) grouped_linear keeps
    its historical x.dtype behaviour."""
    from repro.core.grouped_gemm import grouped_linear
    a8, sa, b8, sb, gs = _quantized([40, 24], 128, 128)
    x = jnp.ones((64, 128), jnp.bfloat16)
    w = jnp.ones((2, 128, 128), jnp.bfloat16)
    pinned = KernelConfig(backend="pallas_interpret",
                          out_dtype=jnp.float32)
    assert dispatch.grouped_gemm_fp8(
        a8, sa, b8, sb, gs, config=pinned).dtype == jnp.float32
    assert grouped_linear(x, w, gs, precision="fp8",
                          config=pinned).dtype == jnp.float32
    default = KernelConfig(backend="pallas_interpret")
    assert grouped_linear(x, w, gs, precision="fp8",
                          config=default).dtype == jnp.bfloat16
    assert dispatch.grouped_gemm_fp8(
        a8, sa, b8, sb, gs, config=default).dtype == jnp.bfloat16
    # explicit per-call override beats the pin
    assert grouped_linear(x, w, gs, precision="fp8", config=pinned,
                          out_dtype=jnp.bfloat16).dtype == jnp.bfloat16
    # the bf16 path honours the pin too (and keeps x.dtype without one)
    assert grouped_linear(x, w, gs, precision="bf16",
                          config=pinned).dtype == jnp.float32
    assert grouped_linear(x, w, gs, precision="bf16").dtype == jnp.bfloat16


def test_save_cache_merges_concurrent_writers(tmp_path):
    """Read-modify-write across processes: a save must not drop entries
    another writer persisted since our load."""
    cache = str(tmp_path / "c.json")
    plan_mod.save_cache({"a": {"config": KernelConfig().to_dict()}}, cache)
    # simulate a second process: bypass this process's memoized view
    plan_mod.clear_cache_memo()
    plan_mod.save_cache({"b": {"config": KernelConfig().to_dict()}}, cache)
    plan_mod.clear_cache_memo()
    assert set(plan_mod.load_cache(cache)) == {"a", "b"}


def test_autotune_m_bucketing_shares_entries(tmp_path):
    cache = str(tmp_path / "c.json")
    a = autotune(513, 128, 128, 4, backend="pallas_interpret",
                 cache_path=cache, measure=False)
    b = autotune(1024, 128, 128, 4, backend="pallas_interpret",
                 cache_path=cache, measure=False)
    assert a == b
    assert len(plan_mod.load_cache(cache)) == 1


# ---------------------------------------------------------------------------
# Satellite: quantize_tilewise never refused for a pure-quantization call
# ---------------------------------------------------------------------------

def test_quantize_tilewise_falls_back_to_ref(monkeypatch):
    from repro import compat
    monkeypatch.setattr(compat, "has_tpu", lambda: False)
    dispatch.set_default_backend("pallas")     # unavailable here
    try:
        x = jnp.ones((8, 128), jnp.float32)
        q8, s = dispatch.quantize_tilewise(x)   # must not raise
        qr, sr = ref.quantize_tilewise_ref(x)
        np.testing.assert_array_equal(np.asarray(q8, np.float32),
                                      np.asarray(qr, np.float32))
    finally:
        dispatch.set_default_backend(None)


def test_quantize_tilewise_explicit_unavailable_still_raises(monkeypatch):
    """The ref fallback serves auto-resolution failures only — an
    explicitly requested kernel backend must not be silently stood in."""
    from repro import compat
    monkeypatch.setattr(compat, "has_tpu", lambda: False)
    with pytest.raises(dispatch.BackendUnavailableError):
        dispatch.quantize_tilewise(jnp.ones((8, 128)), backend="pallas")


def test_quantize_blockwise_batched_routes_through_dispatch(monkeypatch):
    """Satellite: the batched (per-expert) weight quantization goes
    through the registry seam like the unbatched form — a future quant
    kernel covers both — with the same refusal semantics."""
    from repro import compat
    from repro.core import quantization as q
    w = jnp.ones((2, 128, 128), jnp.float32)
    q8, s = q.quantize_blockwise_batched(w)
    qr, sr = jax.vmap(ref.quantize_blockwise_ref)(w)
    np.testing.assert_array_equal(np.asarray(q8, np.float32),
                                  np.asarray(qr, np.float32))
    np.testing.assert_array_equal(np.asarray(s), np.asarray(sr))
    monkeypatch.setattr(compat, "has_tpu", lambda: False)
    with pytest.raises(dispatch.BackendUnavailableError):
        q.quantize_blockwise_batched(w, backend="pallas")
    # auto-resolution failure still serves ref (never refuses pure quant)
    dispatch.set_default_backend("pallas")
    try:
        q.quantize_blockwise_batched(w)        # must not raise
    finally:
        dispatch.set_default_backend(None)


def test_explicit_auto_escapes_pinned_backend(monkeypatch):
    """backend='auto' at a call site must re-enter auto-resolution even
    when the installed default pins a concrete (unavailable) backend."""
    from repro import compat
    monkeypatch.setattr(compat, "has_tpu", lambda: False)
    with plan_mod.default_config(KernelConfig(backend="pallas")):
        cfg = plan_mod.resolve_config(None, backend="auto")
        assert cfg.backend is None
        dispatch.resolve_backend(cfg.backend)   # must not raise


def test_autotune_measured_request_upgrades_cost_model_entry(tmp_path,
                                                             monkeypatch):
    cache = str(tmp_path / "c.json")
    seeded = autotune(256, 128, 128, 4, backend="pallas_interpret",
                      cache_path=cache, measure=False)
    assert plan_mod.load_cache(cache)[plan_mod.cache_key(
        plan_mod._device_kind(), "pallas_interpret", 256, 128, 128, 4
    )]["source"] == "cost_model"
    monkeypatch.setattr(plan_mod, "_measure_candidate",
                        lambda c, *a, **kw: 0.0 if c == seeded else 1.0)
    upgraded = autotune(256, 128, 128, 4, backend="pallas_interpret",
                        cache_path=cache, measure=True, max_candidates=2)
    entries = plan_mod.load_cache(cache)
    (entry,) = entries.values()
    assert entry["source"] == "measured"
    # and a further measured request is now a pure cache hit
    monkeypatch.setattr(plan_mod, "_measure_candidate",
                        lambda *a, **kw: pytest.fail("re-measured"))
    again = autotune(256, 128, 128, 4, backend="pallas_interpret",
                     cache_path=cache, measure=True)
    assert again == upgraded
