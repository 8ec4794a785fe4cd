"""The harness's refusals: a per-layer metric that reads nothing or a
share of a peak above 105 %, a cell on more chips than a runner drives,
a configuration whose balance-loss weight the program does not apply,
and a number without a limit; and the committed limits."""
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

from bench import check, run, spec  # noqa: E402
from bench.cells import train  # noqa: E402


def _cell(metric):
    return types.SimpleNamespace(name="dsmoe-train", per_layer=(
        {"name": metric, "unit": "%"},))


def test_metric_that_reads_nothing_fails():
    ctx = types.SimpleNamespace(kind="serve", trace=None)
    with pytest.raises(RuntimeError, match="found nothing to read"):
        run.per_layer(_cell("idle.train"), ctx)


def test_share_of_peak_over_105_fails():
    sizes = spec.sizes(spec.model_config(
        spec.cell("dsmoe-train").config, "xla"))
    trace = types.SimpleNamespace(window_s=1.0)
    ctx = types.SimpleNamespace(
        kind="train", trace=trace, tokens=10 ** 6, chips=1, sizes=sizes,
        traffic={"seq": 4096}, peak={"bf16_flops": 197e12})
    with pytest.raises(RuntimeError, match="of its peak"):
        run.per_layer(_cell("mfu.train"), ctx)
    ctx.tokens = 1000
    assert 0 < run.per_layer(_cell("mfu.train"), ctx)["mfu.train"][
        "value"] < 100


def test_cell_on_four_chips_is_refused(monkeypatch):
    bench = spec.benchmark()
    for w in bench["workloads"]:
        w["chips"] = 4
    monkeypatch.setattr(spec, "benchmark", lambda: bench)
    with pytest.raises(RuntimeError, match="no runner for 4 chips"):
        run.run_cell("dsmoe-train", 1, 1.0, False, True)


@pytest.mark.parametrize("weight, ok", [(0.01, True), (0.001, False)])
def test_aux_weight_must_be_the_programs(weight, ok):
    config = {"name": "c", "aux_loss_weight": weight}
    if ok:
        train._check_aux_weight(config)
    else:
        with pytest.raises(ValueError, match="aux_loss_weight"):
            train._check_aux_weight(config)


def test_number_without_limit_fails_unless_left_out():
    numbers = {"a": 0.5, "b": 99.0}
    ok, checks = check.verdict(numbers, {"a": {"limit": 1.0},
                                         "b": {"limit": None}})
    assert not ok
    ok, checks = check.verdict(numbers, {"a": {"limit": 1.0}})
    assert not ok
    ok, checks = check.verdict(numbers, {"a": {"limit": 1.0}, "b": {
        "compared": False, "limit": None}})
    assert ok and set(checks) == {"a"}
    ok, _ = check.verdict({"a": 1.5, "b": 0.0}, {"a": {"limit": 1.0}, "b": {
        "compared": False, "limit": None}})
    assert not ok


@pytest.mark.parametrize(
    "workload", [w["name"] for w in spec.benchmark()["workloads"]])
def test_every_cell_has_limits_between_its_readings(workload):
    limits = spec.cell(workload).limits
    compared = {n: v for n, v in limits.items()
                if v.get("compared", True)}
    assert compared, f"{workload}: no compared number"
    for name, v in compared.items():
        assert v["lower"] < v["limit"] < v["upper"], (name, v)
