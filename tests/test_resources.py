"""Tests for the static kernel-resource model (kernels/resources.py) and
its plan.py integrations: family-aware KernelConfig.validate, autotune's
static pool pruning, skipped-with-reason measurement, and the
resource-model-versioned cache key."""
import json
import os

import pytest

from repro.kernels import plan as plan_mod
from repro.kernels import resources as res
from repro.kernels.plan import KernelConfig


# ---------------------------------------------------------------------------
# tile arithmetic + footprints
# ---------------------------------------------------------------------------

def test_tile_bytes_rounds_to_lane_and_sublane():
    # cols pad to 128 lanes; rows to the dtype's sublane granularity
    assert res.tile_bytes(8, 128, 4) == 8 * 128 * 4
    assert res.tile_bytes(8, 100, 4) == 8 * 128 * 4
    assert res.tile_bytes(5, 128, 4) == 8 * 128 * 4      # f32: 8 rows
    assert res.tile_bytes(5, 128, 2) == 16 * 128 * 2     # bf16: 16 rows
    assert res.tile_bytes(5, 128, 1) == 32 * 128 * 1     # fp8: 32 rows


def test_gemm_footprint_matches_hand_arithmetic():
    # bm=128, bn=128, bk=128 at K=N=4096: kb=nb=32
    fp = res.footprint("gemm", {"block_m": 128, "block_n": 128,
                                "block_k": 128}, m=8192, k=4096, n=4096)
    a = 128 * 128 * 1
    s_a = 128 * 128 * 4          # 32 cols pad to 128 lanes
    b = 128 * 128 * 1
    s_b = 32 * 128 * 4           # rows 32 (f32 sublane 8), cols pad
    out = 128 * 128 * 2
    acc = 128 * 128 * 4
    assert fp["total_single"] == a + s_a + b + s_b + out + acc
    assert fp["total"] == 2 * (a + s_a + b + s_b + out) + acc


def test_gemm_quant_footprint_swaps_wide_output_for_payload_and_scales():
    kw = dict(m=8192, k=4096, n=4096)
    cfg = {"block_m": 128, "block_n": 128, "block_k": 128}
    plain = res.footprint("gemm", cfg, **kw)
    quant = res.footprint("gemm_quant", cfg, **kw)
    assert "out_payload" in quant["buffers"]
    assert "out_scales" in quant["buffers"]
    assert "out_tile" not in quant["buffers"]
    # the payload halves the bf16 output write, but the (bm, 1) f32 scale
    # tile lane-pads to 128 columns — the model must charge that padding
    assert quant["buffers"]["out_payload"] < plain["buffers"]["out_tile"]
    assert quant["buffers"]["out_scales"] == 2 * 128 * 128 * 4


def test_wgrad_fp8_footprint_adds_scale_rows():
    kw = dict(m=8192, k=4096, n=4096)
    cfg = {"block_m": 128, "block_n": 128, "block_k": 128}
    bf16 = res.footprint("wgrad", cfg, wgrad_precision="bf16", **kw)
    fp8 = res.footprint("wgrad", cfg, wgrad_precision="fp8", **kw)
    assert "s_x_row" in fp8["buffers"] and "s_x_row" not in bf16["buffers"]


def test_quantize_footprint_applies_the_kernel_tile_clamp():
    # the quantize kernel clamps block_m to max(8, m)
    tall = res.footprint("quantize", {"block_m": 512, "block_n": 128,
                                      "block_k": 128}, m=16, k=2048, n=0)
    short = res.footprint("quantize", {"block_m": 16, "block_n": 128,
                                       "block_k": 128}, m=16, k=2048, n=0)
    assert tall["total"] == short["total"]


def test_act_quant_models_the_extra_producer_input():
    kw = dict(m=8192, k=2048, n=2048)
    cfg = {"block_m": 128, "block_n": 128, "block_k": 128}
    one = res.footprint("quantize", cfg, **kw)
    two = res.footprint("act_quant", cfg, **kw)
    # two bf16 inputs equal one f32 input in bytes; totals match here but
    # the buffer breakdown must show the fused pass reads two operands
    assert two["buffers"]["in_rows"] == 2 * 128 * 2048 * 2 * 2
    assert one["buffers"]["in_rows"] == 128 * 2048 * 4 * 2


def test_vmem_budget_prefix_matching():
    assert res.vmem_budget("TPU v5 lite") == 16 * 2**20
    assert res.vmem_budget("tpu v5e") == 16 * 2**20
    assert res.vmem_budget("tpu v4") == 32 * 2**20
    assert res.vmem_budget("cpu") == 16 * 2**20
    with pytest.raises(ValueError, match="unknown accelerator"):
        res.vmem_budget("unknown accelerator")


def test_infeasible_reason_cases():
    shape = dict(m=8192, k=4096, n=4096)
    budget = res.vmem_budget("tpu v5e")
    ok = res.infeasible_reason(
        "gemm", {"block_m": 128, "block_n": 128, "block_k": 128},
        vmem_bytes=budget, **shape)
    assert ok is None
    misaligned = res.infeasible_reason(
        "gemm", {"block_m": 128, "block_n": 96, "block_k": 128},
        vmem_bytes=budget, **shape)
    assert "misaligned" in misaligned
    degenerate = res.infeasible_reason(
        "gemm", {"block_m": 512, "block_n": 128, "block_k": 128},
        vmem_bytes=budget, m=256, k=4096, n=4096)
    assert "degenerate" in degenerate
    over = res.infeasible_reason(
        "gemm", {"block_m": 8192, "block_n": 128, "block_k": 128},
        vmem_bytes=budget, m=16384, k=4096, n=4096)
    assert "VMEM" in over


def test_degeneracy_keeps_the_smallest_decode_tile_at_m1():
    # bm=8 must survive m=1 (the smallest pool tile IS the selection);
    # bm=16 is prunable (half the fetch does the same work)
    assert res.degeneracy_issues({"block_m": 8, "block_n": 128,
                                  "block_k": 128}, m=1, k=256, n=256) == []
    assert res.degeneracy_issues({"block_m": 16, "block_n": 128,
                                  "block_k": 128}, m=1, k=256, n=256)


# ---------------------------------------------------------------------------
# KernelConfig.validate budget check
# ---------------------------------------------------------------------------

def test_validate_raises_with_computed_footprint_for_infeasible_config():
    cfg = KernelConfig(block_m=8192, block_n=512, block_k=512)
    with pytest.raises(ValueError, match="VMEM"):
        cfg.validate(16384, 4096, 4096)


def test_validate_passes_pool_configs_at_training_shapes():
    for cfg in plan_mod.CONFIG_POOL:
        assert cfg.validate(8192, 4096, 4096) is cfg
    for cfg in plan_mod.CONFIG_POOL:
        assert cfg.validate(8192, 4096, 4096, family="gemm_quant") is cfg


# ---------------------------------------------------------------------------
# autotune static pruning + skipped-with-reason measurement
# ---------------------------------------------------------------------------

def _tmp_cache(tmp_path):
    return str(tmp_path / "tileplan_cache.json")


def test_autotune_statically_prunes_degenerate_pool_entry(tmp_path):
    # acceptance pin: at the CI smoke shape (M=256) the bm=512 pool entry
    # is statically infeasible and must never be ranked or measured
    plan_mod.clear_cache_memo()
    plan_mod.reset_prune_stats()
    cfg = plan_mod.autotune(256, 128, 128, 4, backend="xla_ragged",
                            measure=False, cache_path=_tmp_cache(tmp_path))
    rep = plan_mod.last_autotune_report()
    assert cfg.block_m < 512
    assert len(rep["pruned"]) >= 1
    assert any(c["block_m"] == 512 for c, _ in rep["pruned"])
    assert all("degenerate" in r or "VMEM" in r for _, r in rep["pruned"])
    assert plan_mod.prune_stats().get("gemm", 0) >= 1


def test_autotune_pruned_config_never_reaches_measurement(tmp_path,
                                                          monkeypatch):
    measured = []
    real = plan_mod._measure_candidate

    def spy(config, *a, **kw):
        measured.append(config.block_m)
        return real(config, *a, **kw)

    monkeypatch.setattr(plan_mod, "_measure_candidate", spy)
    plan_mod.clear_cache_memo()
    plan_mod.autotune(256, 128, 128, 4, backend="pallas_interpret",
                      measure=True, cache_path=_tmp_cache(tmp_path))
    assert measured, "interpret path must actually measure"
    assert 512 not in measured


def test_autotune_measurement_failure_is_skipped_not_fatal(tmp_path,
                                                           monkeypatch):
    real = plan_mod._measure_candidate

    def flaky(config, *a, **kw):
        if config.block_m == 128:
            raise RuntimeError("synthetic compile failure")
        return real(config, *a, **kw)

    monkeypatch.setattr(plan_mod, "_measure_candidate", flaky)
    plan_mod.clear_cache_memo()
    cache = _tmp_cache(tmp_path)
    cfg = plan_mod.autotune(256, 128, 128, 4, backend="pallas_interpret",
                            measure=True, cache_path=cache)
    assert cfg.block_m != 128
    rep = plan_mod.last_autotune_report()
    assert any("synthetic compile failure" in r for _, r in rep["skipped"])
    # the skip reason persists in the cache entry
    with open(cache) as f:
        entries = json.load(f)["entries"]
    (entry,) = [e for e in entries.values() if e["op"] == "gemm"]
    assert entry["skipped"] and entry["source"] == "measured"


def test_autotune_all_measurements_failing_falls_back_to_cost_model(
        tmp_path, monkeypatch):
    def always_fail(config, *a, **kw):
        raise RuntimeError("no backend")

    monkeypatch.setattr(plan_mod, "_measure_candidate", always_fail)
    plan_mod.clear_cache_memo()
    cfg = plan_mod.autotune(256, 128, 128, 4, backend="pallas_interpret",
                            measure=True, cache_path=_tmp_cache(tmp_path))
    assert cfg is not None
    assert plan_mod.last_autotune_report()["source"] == "cost_model"


# ---------------------------------------------------------------------------
# cache-key versioning (satellite bugfix)
# ---------------------------------------------------------------------------

def test_cache_key_is_namespaced_by_resource_model_version():
    key = plan_mod.cache_key("cpu", "xla_ragged", 256, 128, 128, 4)
    assert key.endswith(f"|rm{res.RESOURCE_MODEL_VERSION}")
    key_wgrad = plan_mod.cache_key("cpu", "xla_ragged", 256, 128, 128, 4,
                                   op="wgrad")
    assert f"|wgrad|rm{res.RESOURCE_MODEL_VERSION}" in key_wgrad


def test_old_format_cache_entries_are_ignored_not_crashed_on(tmp_path):
    # a cache written before the resource-model namespace: its key has no
    # |rm suffix, so it can never be served — autotune re-tunes and the
    # old entry survives the merge untouched
    cache = _tmp_cache(tmp_path)
    stale_key = "cpu|xla_ragged|M256|K128|N128|G4"
    stale = {"version": 1, "entries": {stale_key: {
        "config": {"block_m": 512, "block_n": 128, "block_k": 128,
                   "backend": "xla_ragged", "out_dtype": None},
        "seconds": 1.0, "source": "measured", "pool_size": 6,
        "op": "gemm"}}}
    with open(cache, "w") as f:
        json.dump(stale, f)
    plan_mod.clear_cache_memo()
    cfg = plan_mod.autotune(256, 128, 128, 4, backend="xla_ragged",
                            measure=False, cache_path=cache)
    # the stale (now statically-infeasible) selection must NOT be served
    assert cfg.block_m != 512
    with open(cache) as f:
        entries = json.load(f)["entries"]
    assert stale_key in entries            # preserved, not clobbered
    new_key = plan_mod.cache_key("cpu", "xla_ragged", 256, 128, 128, 4)
    assert new_key in entries


def test_prune_stats_reset():
    plan_mod.reset_prune_stats()
    assert plan_mod.prune_stats() == {}
