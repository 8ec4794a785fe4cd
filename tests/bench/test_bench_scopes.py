"""Layer attribution (``bench/scopes.py``): scope chains read from HLO
``op_name`` paths, op time per (module, op) on a hand-made trace
(``bench/fixtures/handmade_trace_scoped.pbtxt``, numbers worked out by
hand below), the layer metric readers, and a CPU compile of the
rehearsal-size programs in which every layer name of the program's
vocabulary reaches a leaf op."""
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import scopes, spec, trace_reduce  # noqa: E402
from repro import scopes as program_scopes  # noqa: E402

FIXTURE = os.path.join(ROOT, "bench", "fixtures",
                       "handmade_trace_scoped.pbtxt")
NS = 1e-9
VOCAB = program_scopes.VOCABULARY


def test_bench_reads_the_program_vocabulary():
    assert scopes.vocabulary() == VOCAB
    assert len(set(VOCAB)) == len(VOCAB) == 12
    assert scopes.host_spans() == ("engine.prefill", "engine.sample",
                                   "engine.decode")
    with pytest.raises(ValueError, match="not a layer scope"):
        program_scopes.scope("moe")
    with pytest.raises(ValueError, match="not a host span"):
        program_scopes.span("bench.window")


@pytest.mark.parametrize("op_name, want", [
    # backward ops: JAX wraps a scope in its transforms
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/moe.experts/quant.weights/mul",
     ("moe.experts", "quant.weights")),
    ("jit(train_step)/jvp(lm_head)/mul", ("lm_head",)),
    ("jit(step)/transpose(jvp(attn))/dot_general", ("attn",)),
    # a jitted kernel inside a scope, the scope repeated below it
    ("jit(train_step)/jvp(dense_ffn)/jit(gmm_pallas)/cond/jit(train_step)/"
     "jvp(dense_ffn)/quant.weights/pallas_call",
     ("dense_ffn", "quant.weights")),
    ("jit(train_step)/optimizer/sub", ("optimizer",)),
    # no vocabulary name on the path; a name only inside another word
    ("jit(train_step)/while/body/add", ()),
    ("jit(attn_like)/moe.experts_x/add", ()),
])
def test_chain_of_an_op_name(op_name, want):
    assert scopes.chain(op_name, VOCAB) == want


PREFILL_HLO = """HloModule jit__prefill_impl, is_scheduled=true

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %dot.1 = f32[8]{0} multiply(%param_0, %param_0), metadata={op_name="jit(_prefill_impl)/attn/dot_general"}
}

ENTRY %main.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(_prefill_impl)/attn/dot_general"}
  ROOT %fusion.2 = f32[8]{0:T(8,128)} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(_prefill_impl)/moe.experts/mul"}
}
"""

DECODE_HLO = """HloModule jit__decode_loop_impl, is_scheduled=true

%fused_computation.9 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  ROOT %convert.2 = f32[8]{0} convert(%param_0.1), metadata={op_name="jit(_decode_loop_impl)/while/body/attn/convert_element_type"}
}

%body.2 (arg: (s32[], f32[8])) -> (s32[], f32[8]) {
  %arg = (s32[], f32[8]{0}) parameter(0)
  %gte.1 = f32[8]{0} get-tuple-element(%arg), index=1
  %fusion.1 = f32[8]{0} fusion(%gte.1), kind=kLoop, calls=%fused_computation.9, metadata={op_name="jit(_decode_loop_impl)/while/body/moe.experts/quant.weights/convert_element_type"}
  %fusion.4 = bf16[8]{0:T(1024)(128)(2,1)} fusion(%fusion.1), kind=kOutput, calls=%fused_computation.9, metadata={op_name="jit(_decode_loop_impl)/while/body/moe.experts/dot_general"}
  %copy.7 = f32[8]{0} copy(%fusion.1)
  ROOT %tuple.1 = (s32[], f32[8]{0}) tuple(%gte.0, %copy.7)
}

ENTRY %main.3 (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]{0}) parameter(0)
  ROOT %while.3 = (s32[], f32[8]{0}) while(%p), condition=%cond.2, body=%body.2, metadata={op_name="jit(_decode_loop_impl)/while"}
  %cond.5 = (f32[8]{0}, /*index=1*/s32[]) conditional(%c, %p, %p), branch_computations={%b1, %b2}, metadata={op_name="jit(_decode_loop_impl)/attn/cond"}
}
"""

PROGRAMS = {"prefill": "jit__prefill_impl",
            "decode_loop": "jit__decode_loop_impl"}
HLO = {"prefill": PREFILL_HLO, "decode_loop": DECODE_HLO}


def test_hlo_chains_of_leaf_ops():
    p = scopes.hlo_chains(DECODE_HLO, VOCAB)
    # containers get no chain; ops inside a fused computation are not ops
    # of their own (their fusion is)
    assert p.containers == {"while.3", "cond.5"}
    assert "convert.2" not in p.chains and "param_0.1" not in p.chains
    assert p.chains["fusion.1"] == ("moe.experts", "quant.weights")
    assert p.chains["fusion.4"] == ("moe.experts",)
    assert p.chains["copy.7"] == ()
    assert p.chains["tuple.1"] == ()
    q = scopes.hlo_chains(PREFILL_HLO, VOCAB)
    assert q.chains == {"p": (), "fusion.1": ("attn",),
                        "fusion.2": ("moe.experts",)}
    # a program that predates the vocabulary has no chains
    assert set(scopes.hlo_chains(DECODE_HLO, ()).chains.values()) == {()}


@pytest.fixture(scope="module")
def layered(tmp_path_factory):
    from jax.profiler import ProfileData
    with open(FIXTURE) as f:
        text = "".join(ln for ln in f if not ln.startswith("#"))
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return trace_reduce.reduce(str(path), host_spans=scopes.host_spans())


def test_module_ops_by_containment(layered):
    # the two fusion.1 ops stay apart: each goes to the module event that
    # holds it (prefill 1000-3000 ns, decode loop 4000-18000 ns)
    pre, loop = "jit__prefill_impl(11)", "jit__decode_loop_impl(22)"
    want = {(pre, "fusion.1"): (1, 1_000), (pre, "fusion.2"): (1, 1_000),
            (loop, "while.3"): (1, 14_000), (loop, "fusion.1"): (2, 4_000),
            (loop, "fusion.4"): (2, 8_000), (loop, "copy.7"): (1, 2_000)}
    assert set(layered.module_ops) == set(want)
    for key, (count, ns) in want.items():
        assert layered.module_ops[key][0] == count
        assert layered.module_ops[key][1] == pytest.approx(ns * NS)
    # window 200-20000; busy is the union 1000-3000 and 4000-18000: the
    # while and its body overlap
    assert layered.window_s == pytest.approx(19_800 * NS)
    assert layered.busy_s == pytest.approx(16_000 * NS)


def test_idle_gaps_take_the_innermost_span(layered):
    # 18000-20000: bench.wait (9000-19000) covers 1000 ns, no span the
    # rest.  3000-4000: engine.sample (2500-4500) and bench.generate
    # (500-9000) both cover all of it, engine.sample is inside.  200-1000:
    # bench.generate alone is open 500-700, engine.prefill (700-2500)
    # inside it 700-1000, so engine.prefill holds more of the gap, though
    # bench.generate covers more of it.
    assert layered.idle_gaps == [
        ("host: bench.wait", pytest.approx(2_000 * NS)),
        ("host: engine.sample", pytest.approx(1_000 * NS)),
        ("host: engine.prefill", pytest.approx(800 * NS))]


def _ctx(layered, kind, **counts):
    parsed = {k: scopes.hlo_chains(v, VOCAB) for k, v in HLO.items()}
    return types.SimpleNamespace(
        kind=kind, traffic={"new": 5}, trace=layered,
        scopes={k: p.chains for k, p in parsed.items()},
        containers={k: p.containers for k, p in parsed.items()},
        programs=dict(PROGRAMS), **counts)


def test_a_while_and_its_body_count_once(layered):
    ctx = _ctx(layered, "serve", calls=1)
    # every scoped leaf op of the loop: fusion.1 4000 + fusion.4 8000; the
    # while (14000) and the unscoped copy are not counted
    assert scopes.seconds_under(ctx, bool, "decode_loop") == pytest.approx(
        12_000 * NS)
    assert scopes.seconds_under(ctx, bool) == pytest.approx(14_000 * NS)


def test_layer_metric_readers(layered):
    serve = _ctx(layered, "serve", calls=1, steps=0)
    read = {n: spec.metric_reader(n) for n in (
        "moe_ms.serve", "attn_ms.serve", "weight_quant_ms.serve",
        "moe_ms.train", "attn_ms.train")}
    # one call of new=5 tokens: 4 decode steps
    assert read["moe_ms.serve"](serve) == pytest.approx(12_000e-6 / 4)
    assert read["weight_quant_ms.serve"](serve) == pytest.approx(
        4_000e-6 / 4)
    # attention ran in the prefill module only: the loop's reader finds
    # nothing and says so
    assert read["attn_ms.serve"](serve) is None
    assert read["moe_ms.train"](serve) is None
    train = _ctx(layered, "train", calls=0, steps=2)
    train.programs = {"step": PROGRAMS["decode_loop"]}
    train.scopes = {"step": train.scopes["decode_loop"]}
    assert read["moe_ms.train"](train) == pytest.approx(12_000e-6 / 2)
    assert read["attn_ms.train"](train) is None
    # a program without the vocabulary, or a trace without module_ops
    bare = _ctx(layered, "serve", calls=1)
    bare.scopes = {k: scopes.hlo_chains(v, ()).chains for k, v in HLO.items()}
    assert read["moe_ms.serve"](bare) is None
    serve.trace = types.SimpleNamespace()
    assert read["moe_ms.serve"](serve) is None


def test_attribution_table_and_unscoped_share(layered):
    att = scopes.attribution(layered, _ctx(layered, "serve"))
    loop = "jit__decode_loop_impl(22)"
    tab = {k: (n, pytest.approx(s / NS)) for k, (n, s) in
           att["table"].items()}
    assert tab == {("jit__prefill_impl(11)", "attn"): (1, 1_000),
                   ("jit__prefill_impl(11)", "moe.experts"): (1, 1_000),
                   (loop, "(container)"): (1, 14_000),
                   (loop, "moe.experts/quant.weights"): (2, 4_000),
                   (loop, "moe.experts"): (2, 8_000),
                   (loop, "-"): (1, 2_000)}
    # scoped 14000 of 16000 busy
    assert att["unscoped_share"] == pytest.approx(12.5)
    assert att["unscoped_top"] == [[loop, "copy.7", pytest.approx(2e-6)]]


# --- the program's own compile: every layer name reaches a leaf op -----

@pytest.fixture(scope="module")
def rehearsal_hlo():
    """Optimized CPU HLO of the rehearsal-size train step (the train
    cell's program) and decode loop (the serve cell's)."""
    import jax
    import jax.numpy as jnp
    from bench.cells import serve, train
    cell = spec.cell("dsmoe-train")
    step = train.compile_program(cell.config, cell.traffic,
                                 "pallas_interpret", True).compiled
    cell = spec.cell("dsmoe-decode")
    t = spec.traffic_sizes(cell.traffic, True)
    eng = serve.build(cell.config, "pallas_interpret", True, 1, t).engine
    batch = {"tokens": jnp.zeros((t["batch"], t["prompt"]), jnp.int32)}
    pre = eng._prefill.lower(eng.params, batch,
                             cache_capacity=t["prompt"] + t["new"])
    _, cache = pre.out_info
    first = jax.ShapeDtypeStruct((t["batch"],), jnp.int32)
    loop = eng._decode_loop.lower(eng.params, first, cache,
                                  jax.random.PRNGKey(0)).compile()
    return {"step": step.as_text(), "decode_loop": loop.as_text(),
            "prefill_module": pre.as_text()}


def _names_on_leaf_ops(text):
    return {n for ch in scopes.hlo_chains(text, VOCAB).chains.values()
            for n in ch}


def test_every_layer_name_reaches_a_leaf_op(rehearsal_hlo):
    assert _names_on_leaf_ops(rehearsal_hlo["step"]) == set(VOCAB)
    assert _names_on_leaf_ops(rehearsal_hlo["decode_loop"]) == \
        set(VOCAB) - {"optimizer"}


def test_the_prefill_program_is_named(rehearsal_hlo):
    assert "jit__prefill_impl" in rehearsal_hlo["prefill_module"]
