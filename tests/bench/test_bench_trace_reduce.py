"""The trace reduction, on intervals and on a trace laid out as a TPU
profile is (``bench/fixtures/handmade_trace.pbtxt``), whose numbers are
worked out by hand below."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import kernels, trace_reduce  # noqa: E402

FIXTURE = os.path.join(ROOT, "bench", "fixtures", "handmade_trace.pbtxt")
NS = 1e-9


def test_union_length():
    assert trace_reduce.union_length([]) == 0
    assert trace_reduce.union_length([(0, 2), (1, 3), (5, 6)]) == 4
    assert trace_reduce.union_length([(5, 6), (0, 10), (2, 3)]) == 10


def test_gaps():
    assert trace_reduce._gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4),
                                                         (5, 6)]
    assert trace_reduce._gaps([(0, 6)], 0, 6) == []
    assert trace_reduce._gaps([(0, 3), (1, 2)], 0, 4) == [(3, 4)]


@pytest.fixture(scope="module")
def reduced(tmp_path_factory):
    from jax.profiler import ProfileData
    with open(FIXTURE) as f:
        text = "".join(ln for ln in f if not ln.startswith("#"))
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    assert trace_reduce.find_xplane(str(path.parent)) == str(path)
    return trace_reduce.reduce(str(path))


def test_window_and_busy(reduced):
    # window 1000-11000 ns; ops 500-2000, 2000-4000, 3000-5000,
    # 7000-9000, 10500-12000 clip to a union of 1000-5000, 7000-9000 and
    # 10500-11000
    assert reduced.window_s == pytest.approx(10_000 * NS)
    assert reduced.busy_s == pytest.approx(6_500 * NS)
    assert reduced.devices == 1


def test_ops_and_modules(reduced):
    want = {"fusion.1": (2, 1_500), "gmm_fp8_kernel": (1, 2_000),
            "fusion.2": (1, 2_000), "wgrad_kernel": (1, 2_000)}
    assert set(reduced.ops) == set(want)
    for name, (count, ns) in want.items():
        assert reduced.ops[name][0] == count
        assert reduced.ops[name][1] == pytest.approx(ns * NS)
    assert reduced.module("jit_train_step") == pytest.approx(
        (2, (4_000 + 2_200) * NS))
    assert reduced.module("decode") is None
    gemm = frozenset({"gmm_fp8_kernel", "wgrad_kernel"})
    assert kernels.expert_kernel_seconds(reduced, gemm) == pytest.approx(
        4_000 * NS)
    assert kernels.expert_kernel_seconds(reduced, frozenset()) == 0
    assert trace_reduce.top_ops(reduced, 2)[0][1] == pytest.approx(2_000 * NS)


def test_idle_gaps_are_labelled(reduced):
    # 5000-7000: bench.wait covers 1000 ns of it, bench.dispatch 500;
    # 9000-10500: bench.wait
    assert reduced.idle_gaps == [("host: bench.wait", pytest.approx(2e-6)),
                                 ("host: bench.wait", pytest.approx(1.5e-6))]


def test_no_window_is_an_error(tmp_path):
    from jax.profiler import ProfileData
    text = 'planes { id: 1 name: "/device:TPU:0" }'
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    with pytest.raises(ValueError, match="bench.window"):
        trace_reduce.reduce(str(path))


# three Pallas custom calls and a fusion as the TPU compiler prints them
# (operands and most attributes cut)
HLO = """
  %branch_1_fun.52 = bf16[4096,2816]{1,0} custom-call(%a, %b), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/while/body/checkpoint/jit(gmm_pallas)/cond/branch_1_fun/pallas_call"}
  %gmm_pallas_wgrad.12 = f32[64,2048,2816]{2,1,0} custom-call(%c), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/checkpoint/jit(gmm_pallas_wgrad)/pallas_call"}
  ROOT %quantize_tilewise_pallas.33 = f8e4m3fn[4096,2048]{1,0} custom-call(%d), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/checkpoint/jit(quantize_tilewise_pallas)/pallas_call"}
  %fusion.7 = f32[8]{0} fusion(%e), kind=kLoop, metadata={op_name="jit(train_step)/jit(gmm_pallas)/mul"}
"""


def test_expert_gemm_ops_from_hlo():
    assert kernels.expert_gemm_ops([HLO]) == {"branch_1_fun.52",
                                              "gmm_pallas_wgrad.12"}
    assert kernels.expert_gemm_ops([]) == frozenset()


def test_op_name_of_a_tpu_trace_event():
    # a TPU trace names each op event by its whole HLO line
    line = ("%branch_1_fun.59 = bf16[24576,2048]{1,0:T(8,128)(2,1)} "
            "custom-call(s32[65]{0:T(128)S(1)} %copy-done.397)")
    assert trace_reduce.op_name(line) == "branch_1_fun.59"
    assert trace_reduce.op_name("fusion.1") == "fusion.1"


def test_module_name_from_hlo():
    text = ("HloModule jit__unknown, is_scheduled=true, "
            "entry_computation_layout={(s32[4]{0})->s32[4]{0}}\n\nENTRY %m {}")
    assert kernels.module_name(text) == "jit__unknown"
