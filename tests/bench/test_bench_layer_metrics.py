"""The scoped layer metrics as a traced run reads them: the harness's own
trace reduction (``module_ops``) and its readers' context
(``run.layer_context``: ``ctx.scopes`` from the optimized HLO), through
``run.per_layer``, on a hand-made trace with shared-expert ops
(``bench/fixtures/handmade_trace_shared.pbtxt``, numbers worked out by
hand below)."""
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

from bench import peaks, run, scopes, spec, trace_reduce  # noqa: E402

FIXTURE = os.path.join(ROOT, "bench", "fixtures",
                       "handmade_trace_shared.pbtxt")
NS = 1e-9


def _meta(path):
    return f'metadata={{op_name="{path}"}}'


STEP_HLO = f"""HloModule jit_train_step, is_scheduled=true

ENTRY %main.1 (p: f32[8]) -> f32[8] {{
  %p = f32[8]{{0}} parameter(0)
  %fusion.1 = f32[8]{{0}} fusion(%p), kind=kLoop, calls=%f, {_meta("jit(train_step)/transpose(jvp(attn))/dot_general")}
  %fusion.2 = f32[8]{{0}} fusion(%p), kind=kLoop, calls=%f, {_meta("jit(train_step)/jvp(moe.experts)/mul")}
  %fusion.3 = f32[8]{{0}} fusion(%p), kind=kLoop, calls=%f, {_meta("jit(train_step)/transpose(jvp(moe.shared))/dot_general")}
  %fusion.4 = f32[8]{{0}} fusion(%p), kind=kLoop, calls=%f, {_meta("jit(train_step)/moe.shared/quant.weights/convert_element_type")}
  %copy.6 = f32[8]{{0}} copy(%p)
  ROOT %fusion.7 = f32[8]{{0}} fusion(%p), kind=kLoop, calls=%f, {_meta("jit(train_step)/moe.route/top_k")}
}}
"""

DECODE_HLO = f"""HloModule jit__decode_loop_impl, is_scheduled=true

%body.2 (arg: f32[8]) -> f32[8] {{
  %arg = f32[8]{{0}} parameter(0)
  %fusion.1 = f32[8]{{0}} fusion(%arg), kind=kLoop, calls=%f, {_meta("jit(_decode_loop_impl)/while/body/attn/dot_general")}
  %fusion.2 = f32[8]{{0}} fusion(%fusion.1), kind=kLoop, calls=%f, {_meta("jit(_decode_loop_impl)/while/body/moe.shared/dot_general")}
  %fusion.3 = f32[8]{{0}} fusion(%fusion.2), kind=kLoop, calls=%f, {_meta("jit(_decode_loop_impl)/while/body/moe.experts/dot_general")}
  ROOT %copy.4 = f32[8]{{0}} copy(%fusion.3)
}}

ENTRY %main.3 (p: f32[8]) -> f32[8] {{
  %p = f32[8]{{0}} parameter(0)
  ROOT %while.1 = f32[8]{{0}} while(%p), condition=%cond.2, body=%body.2, {_meta("jit(_decode_loop_impl)/while")}
}}
"""

PREFILL_HLO = f"""HloModule jit__prefill_impl, is_scheduled=true

ENTRY %main.1 (p: f32[8]) -> f32[8] {{
  %p = f32[8]{{0}} parameter(0)
  %fusion.1 = f32[8]{{0}} fusion(%p), kind=kLoop, calls=%f, {_meta("jit(_prefill_impl)/attn/dot_general")}
  ROOT %fusion.2 = f32[8]{{0}} fusion(%fusion.1), kind=kLoop, calls=%f, {_meta("jit(_prefill_impl)/moe.shared/dot_general")}
}}
"""

SCOPED = {"train": ("moe_ms.train", "attn_ms.train", "shared_ms.train"),
          "serve": ("moe_ms.serve", "attn_ms.serve", "shared_ms.serve")}
CELLS = {"train": "dsmoe-train", "serve": "dsmoe-decode"}


@pytest.fixture(scope="module")
def reduced(tmp_path_factory):
    from jax.profiler import ProfileData
    with open(FIXTURE) as f:
        text = "".join(ln for ln in f if not ln.startswith("#"))
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return trace_reduce.reduce(str(path), host_spans=scopes.host_spans())


def _context(kind, reduced):
    cell = spec.cell(CELLS[kind])
    cfg = spec.model_config(cell.config, "xla")
    if kind == "train":
        hlo = {"step": STEP_HLO}
        counts = dict(steps=2, tokens=2 * 4096, calls=0)
    else:
        hlo = {"prefill": PREFILL_HLO, "decode_loop": DECODE_HLO}
        counts = dict(steps=0, tokens=5, calls=1)
    r = {"prog_cfg": cfg, "traffic": {**cell.traffic, "new": 5},
         "hlo": hlo, "counts": counts}
    return cell, run.layer_context(kind, r, 1, reduced,
                                   peaks.peak("TPU v5 lite"))


def test_module_ops_in_the_one_reduction(reduced):
    # the per-op table and the (module, op) table come from one pass: the
    # fusion.1 of the three modules sum to the op table's fusion.1
    assert reduced.ops["fusion.1"][0] == 4
    assert reduced.ops["fusion.1"][1] == pytest.approx(4_000 * NS)
    mod = {m: v for (m, op), v in reduced.module_ops.items()
           if op == "fusion.1"}
    assert {m: (n, pytest.approx(s / NS)) for m, (n, s) in mod.items()} == {
        "jit_train_step(5)": (1, 1_000),
        "jit__decode_loop_impl(9)": (2, 2_000),
        "jit__prefill_impl(8)": (1, 1_000)}
    assert reduced.module_ops[("jit__decode_loop_impl(9)", "while.1")][
        1] == pytest.approx(15_000 * NS)


def test_context_carries_the_chains(reduced):
    _, ctx = _context("train", reduced)
    assert ctx.programs == {"step": "jit_train_step"}
    assert ctx.scopes["step"]["fusion.4"] == ("moe.shared", "quant.weights")
    assert ctx.scopes["step"]["copy.6"] == ()
    _, ctx = _context("serve", reduced)
    assert ctx.containers["decode_loop"] == {"while.1"}
    assert ctx.scopes["decode_loop"]["fusion.2"] == ("moe.shared",)


@pytest.mark.parametrize("kind, want", [
    # train, 2 steps: moe = fusion.2 3000 + fusion.3 2 x 2000 + fusion.4
    # 500 + fusion.7 1000 = 8500 ns; attn = fusion.1 1000; shared =
    # fusion.3 4000 + fusion.4 500 (its quant.weights under moe.shared)
    ("train", {"moe_ms.train": 8_500e-6 / 2, "attn_ms.train": 1_000e-6 / 2,
               "shared_ms.train": 4_500e-6 / 2}),
    # serve, one call of new=5: 4 decode steps of the loop alone (the
    # prefill's attn and moe.shared ops, the while and the unscoped copy
    # do not count): moe = fusion.2 4000 + fusion.3 8000; attn = fusion.1
    # 2000; shared = fusion.2 4000
    ("serve", {"moe_ms.serve": 12_000e-6 / 4, "attn_ms.serve": 2_000e-6 / 4,
               "shared_ms.serve": 4_000e-6 / 4}),
])
def test_scoped_metrics_through_per_layer(reduced, kind, want):
    cell, ctx = _context(kind, reduced)
    listed = [m for m in cell.per_layer if m["name"] in SCOPED[kind]]
    assert [m["name"] for m in listed] == list(SCOPED[kind])
    assert {m["unit"] for m in listed} == {"ms"}
    got = run.per_layer(types.SimpleNamespace(name=cell.name,
                                              per_layer=tuple(listed)), ctx)
    assert set(got) == set(want)
    for name, value in want.items():
        assert got[name] == {"value": pytest.approx(value), "unit": "ms"}


def test_unscoped_share_from_the_context(reduced):
    _, ctx = _context("serve", reduced)
    att = scopes.attribution(reduced, ctx)
    # busy: 1000-11000, 12000-27000, 28000-30000 = 27000 ns; scoped leaf
    # ops of the two serve programs: loop 14000 + prefill 2000; the train
    # step's module is no program of this context
    assert att["unscoped_share"] == pytest.approx(
        100 * (1 - 16_000 / 27_000))
    assert att["table"][("jit__decode_loop_impl(9)", "(container)")][
        1] == pytest.approx(15_000 * NS)
    assert att["table"][("jit_train_step(5)", "(no program)")][0] == 7
