"""The unified (family, precision) operator registry.

Covers the ISSUE-5 acceptance surface:

  * ``dispatch.py`` hosts exactly ONE registry dict (``_OPERATORS``,
    keyed by :class:`~repro.kernels.dispatch.OpKey`) and ONE resolution
    function (:func:`~repro.kernels.dispatch.resolve`); the per-family
    registry copies (``_REGISTRY`` / ``_WGRAD_REGISTRY``) are gone;
  * registry parity: every ``(family, precision, backend)`` combination
    that resolved before the refactor still resolves through the aliases,
    with bitwise-identical outputs (golden-checked against the PR 4 test
    fixtures' shapes and the oracle backends);
  * the quantize family is a first-class OpKey — including the
    ``op="quantize"`` autotune satellite (pool ranking + persistent
    cache + config-routed tile height);
  * the padded baseline's block-aligned plan comes from the PlanCache:
    two calls with the same static shape build exactly one plan
    (regression for the historical per-call re-planning).
"""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import compat
from repro.core import padding_baseline as pb
from repro.kernels import dispatch, ref
from repro.kernels import plan as plan_mod
from repro.kernels.dispatch import OpKey
from repro.kernels.plan import KernelConfig


# PR 4 fixture shape: ragged, an empty group, sum < M would be the wgrad
# tests' variant — the registry-parity goldens reuse the same generator
SIZES = [100, 0, 37, 163]
K, N = 256, 128


@pytest.fixture(scope="module")
def fixtures():
    rng = np.random.default_rng(3)
    m = sum(SIZES)
    a = jnp.asarray(rng.standard_normal((m, K)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((len(SIZES), K, N)), jnp.float32)
    dy = jnp.asarray(rng.standard_normal((m, N)), jnp.float32)
    a8, sa = ref.quantize_tilewise_ref(a)
    b8, sb = jax.vmap(ref.quantize_blockwise_ref)(b)
    d8, sd = ref.quantize_tilewise_ref(dy)
    return dict(a=a, b=b, dy=dy, a8=a8, sa=sa, b8=b8, sb=sb, d8=d8, sd=sd,
                gs=jnp.asarray(SIZES, jnp.int32))


# ---------------------------------------------------------------------------
# Structure: one registry dict, one resolution function
# ---------------------------------------------------------------------------

def test_single_registry_dict_and_resolver():
    assert isinstance(dispatch._OPERATORS, dict)
    assert all(isinstance(k, OpKey) for k in dispatch._OPERATORS)
    # the per-family copies are gone — aliases are views over _OPERATORS
    for legacy in ("_REGISTRY", "_WGRAD_REGISTRY"):
        assert not hasattr(dispatch, legacy), legacy
    assert callable(dispatch.resolve)


def test_registered_op_keys():
    keys = set(dispatch.op_keys())
    assert {OpKey("gemm", "fp8"), OpKey("gemm", "bf16"),
            OpKey("wgrad", "bf16"), OpKey("wgrad", "fp8"),
            OpKey("quantize", "fp8")} <= keys


def test_op_key_validation():
    with pytest.raises(ValueError, match="op family"):
        OpKey("dgrad", "fp8")
    with pytest.raises(ValueError, match="precision"):
        OpKey("gemm", "int4")
    with pytest.raises(ValueError, match="no operator registered"):
        dispatch.resolve(("quantize", "bf16"))


def test_plan_and_tile_membership_is_registry_derived():
    assert dispatch.op_uses_plan(("gemm", "fp8"), "pallas_interpret")
    assert not dispatch.op_uses_plan(("gemm", "fp8"), "xla_exact")
    assert dispatch.op_ignores_tiles(("gemm", "fp8"), "xla_ragged")
    assert not dispatch.op_ignores_tiles(("gemm", "fp8"), "padded_baseline")
    assert dispatch.op_uses_plan(("wgrad", "fp8"), "pallas_interpret")
    # the derived back-compat frozensets keep their historical contents
    assert dispatch.PLAN_BACKENDS == frozenset(
        {"pallas", "pallas_interpret", "pallas_fp8",
         "pallas_interpret_fp8"})
    assert dispatch.TILE_FREE_BACKENDS == frozenset(
        {"xla_ragged", "xla_exact", "xla_ragged_fp8", "xla_exact_fp8"})


# ---------------------------------------------------------------------------
# Registry parity: every pre-refactor combination still resolves
# ---------------------------------------------------------------------------

def test_every_prerefactor_combination_resolves():
    # (alias call, requested names) exactly as PRs 1-4 published them
    for name in ("pallas_interpret", "xla_ragged", "xla_exact",
                 "padded_baseline", "xla", "auto", None):
        assert dispatch.resolve_backend(name) in dispatch.backend_names()
    for precision in ("bf16", "fp8"):
        suffix = "_fp8" if precision == "fp8" else ""
        for name in ("pallas_interpret", "xla_ragged", "xla_exact"):
            got = dispatch.resolve_wgrad_backend(name, precision=precision)
            assert got == name + suffix
            # the suffixed historical spelling resolves to the same entry
            assert dispatch.resolve_wgrad_backend(
                name + "_fp8", precision=precision) == got
    for name in ("pallas_interpret", "xla_ragged", "padded_baseline",
                 "ref", None):
        q, s = dispatch.quantize_tilewise(jnp.ones((8, 128)), backend=name)
        assert q.shape == (8, 128) and s.shape == (8, 1)


def test_resolve_is_what_the_aliases_call(monkeypatch):
    monkeypatch.setattr(compat, "has_tpu", lambda: True)
    assert dispatch.resolve(("gemm", "fp8"), "auto") == \
        dispatch.resolve_backend("auto") == "pallas"
    assert dispatch.resolve(("wgrad", "fp8"), "auto") == "pallas"
    assert dispatch.resolve_wgrad_backend("auto", precision="fp8") == \
        "pallas_fp8"


def test_gemm_alias_output_bitwise_vs_direct_registry_run(fixtures):
    f = fixtures
    cfg = KernelConfig(backend="pallas_interpret", out_dtype=jnp.float32)
    via_alias = dispatch.grouped_gemm_fp8(f["a8"], f["sa"], f["b8"],
                                          f["sb"], f["gs"], config=cfg)
    key = OpKey("gemm", "fp8")
    direct = dispatch._OPERATORS[key]["pallas_interpret"].run(
        f["a8"], f["sa"], f["b8"], f["sb"], f["gs"],
        num_groups=len(SIZES), config=cfg, plan=None)
    np.testing.assert_array_equal(np.asarray(via_alias),
                                  np.asarray(direct))


@pytest.mark.parametrize("backend", ["pallas_interpret", "xla_exact"])
def test_wgrad_alias_outputs_bitwise_both_precisions(fixtures, backend):
    f = fixtures
    x16 = f["a"].astype(jnp.bfloat16)
    dy16 = f["dy"].astype(jnp.bfloat16)
    via_alias = dispatch.grouped_gemm_wgrad(x16, dy16, f["gs"],
                                            backend=backend)
    direct = dispatch._OPERATORS[OpKey("wgrad", "bf16")][backend].run(
        x16, dy16, f["gs"], num_groups=len(SIZES),
        config=KernelConfig(out_dtype=jnp.float32), plan=None)
    np.testing.assert_array_equal(np.asarray(via_alias), np.asarray(direct))
    via_alias8 = dispatch.grouped_gemm_wgrad_fp8(
        f["a8"], f["sa"], f["d8"], f["sd"], f["gs"], backend=backend)
    direct8 = dispatch._OPERATORS[OpKey("wgrad", "fp8")][backend].run(
        f["a8"], f["sa"], f["d8"], f["sd"], f["gs"], num_groups=len(SIZES),
        config=KernelConfig(out_dtype=jnp.float32), plan=None)
    np.testing.assert_array_equal(np.asarray(via_alias8),
                                  np.asarray(direct8))


def test_bf16_gemm_family_matches_ragged_dot(fixtures):
    """The bf16 baseline is now a registry citizen; its output must be
    bitwise what a direct jax.lax.ragged_dot produces."""
    f = fixtures
    x16 = f["a"].astype(jnp.bfloat16)
    w16 = f["b"].astype(jnp.bfloat16)
    got = dispatch.grouped_gemm_bf16(x16, w16, f["gs"],
                                     out_dtype=jnp.float32)
    want = jax.lax.ragged_dot(x16, w16, f["gs"],
                              preferred_element_type=jnp.float32)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_quantize_family_entries_and_explicit_semantics(monkeypatch):
    x = jnp.ones((8, 128), jnp.float32)
    qr, sr = ref.quantize_tilewise_ref(x)
    # kernel entries are bitwise vs ref on this input; xla/ref entries ARE ref
    for name in ("pallas_interpret", "xla_ragged", "ref"):
        q, s = dispatch.quantize_tilewise(x, backend=name)
        np.testing.assert_array_equal(np.asarray(q, np.float32),
                                      np.asarray(qr, np.float32))
        np.testing.assert_array_equal(np.asarray(s), np.asarray(sr))
    # explicitly requested unavailable entries still refuse (parity with
    # the pre-refactor resolve-through-gemm behaviour)
    monkeypatch.setattr(compat, "has_tpu", lambda: False)
    with pytest.raises(dispatch.BackendUnavailableError):
        dispatch.quantize_tilewise(x, backend="pallas")
    table = dispatch._OPERATORS[OpKey("quantize", "fp8")]
    monkeypatch.setitem(table, "xla_ragged", dataclasses.replace(
        table["xla_ragged"], available=lambda: (False, "refused here")))
    with pytest.raises(dispatch.BackendUnavailableError):
        dispatch.quantize_tilewise(x, backend="xla_ragged")


def test_quantize_config_routes_tile_height_bitwise(fixtures):
    """An autotuned quantizer tile height is pure scheduling: any
    block_m produces the identical (q, s) pair."""
    f = fixtures
    base = dispatch.quantize_tilewise(f["a"], backend="pallas_interpret")
    for bm in (8, 64, 512):
        q, s = dispatch.quantize_tilewise(
            f["a"], backend="pallas_interpret",
            config=KernelConfig(block_m=bm))
        np.testing.assert_array_equal(np.asarray(q, np.float32),
                                      np.asarray(base[0], np.float32))
        np.testing.assert_array_equal(np.asarray(s), np.asarray(base[1]))


def test_register_operator_plugs_into_unified_table():
    key = OpKey("gemm", "fp8")
    try:
        dispatch.register_operator(
            key, "test_backend", description="unit-test entry",
            available=lambda: (True, ""),
            run=lambda *a, **kw: jnp.zeros(()))
        assert "test_backend" in dispatch.backend_names()
        assert dispatch.resolve(key, "test_backend") == "test_backend"
    finally:
        del dispatch._OPERATORS[key]["test_backend"]


def test_backend_matrix_all_covers_every_operator():
    full = dispatch.backend_matrix("all")
    assert set(full) == {f"{k.family}/{k.precision}"
                         for k in dispatch.op_keys()}
    assert full["wgrad/fp8"]["pallas_interpret"]["available"]
    table = dispatch.format_backend_matrix()
    for label in ("`gemm` | `fp8`", "`wgrad` | `fp8`", "`quantize` | `fp8`",
                  "`gemm` | `bf16`", "`pallas_interpret_fp8`"):
        assert label in table, label


def test_tile_fallback_owned_by_resolve():
    cfg = KernelConfig(block_n=256)         # N=128 not divisible
    # auto: falls to a tile-free entry of the same op
    name = dispatch.resolve(("wgrad", "bf16"), None,
                            tile=(cfg, 64, 128, 128))
    assert name in ("xla_ragged", "xla_exact")
    # explicit: raises via KernelConfig.validate
    with pytest.raises(ValueError, match="block_n"):
        dispatch.resolve(("wgrad", "bf16"), "pallas_interpret",
                         tile=(cfg.with_(backend="pallas_interpret"),
                               64, 128, 128))


# ---------------------------------------------------------------------------
# Satellite: op="quantize" autotune family
# ---------------------------------------------------------------------------

def test_autotune_quantize_caches_under_distinct_key(tmp_path):
    cache = str(tmp_path / "c.json")
    cfg_q = plan_mod.autotune(512, 256, 0, 0, backend="pallas_interpret",
                              cache_path=cache, measure=False,
                              op="quantize")
    assert cfg_q.backend == "pallas_interpret"
    key_q = plan_mod.cache_key(plan_mod._device_kind(), "pallas_interpret",
                               512, 256, 0, 0, op="quantize")
    entries = plan_mod.load_cache(cache)
    assert key_q in entries and entries[key_q]["op"] == "quantize"
    plan_mod.clear_cache_memo()
    again = plan_mod.autotune(512, 256, 0, 0, backend="pallas_interpret",
                              cache_path=cache, measure=False,
                              op="quantize")
    assert again == cfg_q


def test_autotune_quantize_measures_the_quantize_dispatch(tmp_path,
                                                         monkeypatch):
    cache = str(tmp_path / "c.json")
    seen = []
    real = plan_mod._measure_candidate

    def spying(*a, **kw):
        seen.append(kw.get("op", "gemm"))
        return real(*a, iters=1, warmup=0,
                    **{k: v for k, v in kw.items()
                       if k not in ("iters", "warmup")})

    monkeypatch.setattr(plan_mod, "_measure_candidate", spying)
    plan_mod.autotune(256, 128, 0, 0, backend="pallas_interpret",
                      cache_path=cache, max_candidates=2, op="quantize")
    assert seen and all(op == "quantize" for op in seen)


def test_autotune_quantize_dedupes_tile_heights(tmp_path):
    """Pool entries differing only in (block_n, block_k) are one
    candidate for the quantizer — the cost model must rank tile heights,
    not duplicates."""
    cache = str(tmp_path / "c.json")
    plan_mod.autotune(256, 128, 0, 0, backend="pallas_interpret",
                      cache_path=cache, measure=False, op="quantize")
    entries = plan_mod.load_cache(cache)
    (entry,) = entries.values()
    pool_heights = {c.block_m for c in plan_mod.CONFIG_POOL}
    assert entry["pool_size"] == len(pool_heights)


# ---------------------------------------------------------------------------
# Satellite/bugfix: padded_baseline plans once per static shape
# ---------------------------------------------------------------------------

def _padded_inputs(sizes, k, n, seed=0):
    rng = np.random.default_rng(seed)
    m = sum(sizes)
    a8, sa = ref.quantize_tilewise_ref(
        jnp.asarray(rng.standard_normal((m, k)), jnp.float32))
    b8, sb = jax.vmap(ref.quantize_blockwise_ref)(
        jnp.asarray(rng.standard_normal((len(sizes), k, n)), jnp.float32))
    return a8, sa, b8, sb, jnp.asarray(sizes, jnp.int32)


def test_padded_baseline_plans_once_per_static_shape(monkeypatch):
    """REGRESSION: the baseline re-planned its block-aligned schedule on
    every call.  Two calls with the same static shape must build exactly
    one plan (the PlanCache replays the compiled builder); a different
    static shape builds a second one."""
    a8, sa, b8, sb, gs = _padded_inputs([60, 30, 40], 128, 128, seed=1)
    plan_mod.PLAN_CACHE.clear()
    calls = []
    inner = plan_mod.make_group_metadata
    monkeypatch.setattr(plan_mod, "make_group_metadata",
                        lambda *a, **kw: calls.append(a) or inner(*a, **kw))
    cfg = KernelConfig(backend="pallas_interpret", out_dtype=jnp.float32)
    out1 = pb.grouped_gemm_fp8_padded(a8, sa, b8, sb, gs, config=cfg)
    assert len(calls) == 1, f"first call must build the plan: {len(calls)}"
    # same static shape, different group sizes: replay, not re-plan
    gs2 = jnp.asarray([20, 70, 40], jnp.int32)
    out2 = pb.grouped_gemm_fp8_padded(a8, sa, b8, sb, gs2, config=cfg)
    assert len(calls) == 1, \
        f"same static shape must not re-plan: {len(calls)}"
    assert plan_mod.PLAN_CACHE.builds == 1
    # a different block_m is a different static plan shape
    pb.grouped_gemm_fp8_padded(a8, sa, b8, sb, gs,
                               config=cfg.with_(block_m=64))
    assert len(calls) == 2 and plan_mod.PLAN_CACHE.builds == 2
    assert out1.shape == out2.shape == (130, 128)


def test_padded_baseline_cached_plan_is_bitwise_neutral(fixtures):
    """The cached plan must not change the baseline's output — the
    paper's bitwise pad->GEMM->unpad equivalence still holds through the
    dispatch entry (which routes through the PlanCache)."""
    f = fixtures
    ours = dispatch.grouped_gemm_fp8(f["a8"], f["sa"], f["b8"], f["sb"],
                                     f["gs"], backend="pallas_interpret",
                                     out_dtype=jnp.bfloat16)
    for _ in range(2):                      # second call hits the cache
        base = dispatch.grouped_gemm_fp8(f["a8"], f["sa"], f["b8"],
                                         f["sb"], f["gs"],
                                         backend="padded_baseline",
                                         out_dtype=jnp.bfloat16)
        assert np.array_equal(np.asarray(ours, np.float32),
                              np.asarray(base, np.float32))


def test_plan_cache_key_includes_dtype_and_shape():
    plan_mod.PLAN_CACHE.clear()
    gs32 = jnp.asarray([8, 8], jnp.int32)
    p1 = plan_mod.shared_plan(gs32, 16, block_m=8)
    p2 = plan_mod.shared_plan(jnp.asarray([4, 12], jnp.int32), 16,
                              block_m=8)
    assert plan_mod.PLAN_CACHE.builds == 1          # same static key
    plan_mod.shared_plan(gs32.astype(jnp.int16), 16, block_m=8)
    assert plan_mod.PLAN_CACHE.builds == 2          # dtype is part of key
    plan_mod.shared_plan(gs32, 32, block_m=8)
    assert plan_mod.PLAN_CACHE.builds == 3          # m is part of key
    # and the cached builder's output equals a fresh make_tile_plan
    fresh = plan_mod.make_tile_plan(jnp.asarray([4, 12], jnp.int32), 16,
                                    block_m=8)
    np.testing.assert_array_equal(np.asarray(p2.group_ids),
                                  np.asarray(fresh.group_ids))
    np.testing.assert_array_equal(np.asarray(p2.m_tile_ids),
                                  np.asarray(fresh.m_tile_ids))
    assert p1.block_m == 8 and p1.m == 16
