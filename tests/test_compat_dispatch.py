"""Compat layer + grouped-GEMM dispatch registry.

Covers:
  * the TPU probe is monkeypatchable and drives backend selection — each
    backend is selected (auto) or refused (explicit request) per the
    probed platform, with a reasoned error instead of AttributeError;
  * the ``ragged_dot_general`` wgrad fallback agrees with a dense one-hot
    oracle;
  * every CPU-runnable backend produces matching outputs on the
    equivalence fixtures, including a dispatch-level re-run of the paper's
    bitwise padded-baseline equivalence claim.
"""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro import compat
from repro.kernels import dispatch, ref
from repro.kernels.dispatch import OpKey


# ---------------------------------------------------------------------------
# platform probe
# ---------------------------------------------------------------------------

def test_probes_return_bool():
    assert isinstance(compat.has_tpu(), bool)


def test_tpu_compiler_params_constructs():
    """The kernels build the installed JAX's ``pltpu.CompilerParams``
    directly; the spelling they use must construct."""
    p = pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))
    assert tuple(p.dimension_semantics) == ("parallel", "arbitrary")


# ---------------------------------------------------------------------------
# wgrad fallback
# ---------------------------------------------------------------------------

def _wgrad_oracle(x, dy, sizes):
    g = len(sizes)
    dw = np.zeros((g, x.shape[1], dy.shape[1]), np.float32)
    off = 0
    for i, n in enumerate(sizes):
        dw[i] = np.asarray(x[off:off + n], np.float32).T @ \
            np.asarray(dy[off:off + n], np.float32)
        off += n
    return dw


@pytest.mark.parametrize("sizes", [(5, 7, 4), (40, 0, 57), (0, 0, 16)])
def test_ragged_wgrad_matches_dense_oracle(sizes):
    rng = np.random.default_rng(sum(sizes))
    m = sum(sizes)
    x = jnp.asarray(rng.standard_normal((m, 16)), jnp.float32)
    dy = jnp.asarray(rng.standard_normal((m, 8)), jnp.float32)
    gs = jnp.asarray(sizes, jnp.int32)
    dw = dispatch.wgrad_xla_ragged(x, dy, gs, num_groups=len(sizes))
    np.testing.assert_allclose(np.asarray(dw), _wgrad_oracle(x, dy, sizes),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# backend selection / refusal
# ---------------------------------------------------------------------------

def test_registry_exposes_expected_backends():
    names = dispatch.backend_names()
    for required in ("pallas", "pallas_interpret", "xla_ragged",
                     "xla_exact", "padded_baseline"):
        assert required in names


def test_auto_prefers_pallas_on_tpu(monkeypatch):
    monkeypatch.setattr(compat, "has_tpu", lambda: True)
    assert dispatch.resolve_backend("auto") == "pallas"


def test_auto_prefers_xla_ragged_on_cpu(monkeypatch):
    monkeypatch.setattr(compat, "has_tpu", lambda: False)
    assert dispatch.resolve_backend("auto") == "xla_ragged"


def test_auto_falls_back_to_interpret(monkeypatch):
    monkeypatch.setattr(compat, "has_tpu", lambda: False)
    table = dispatch._OPERATORS[OpKey("gemm", "fp8")]
    monkeypatch.setitem(table, "xla_ragged", dataclasses.replace(
        table["xla_ragged"], available=lambda: (False, "refused here")))
    assert dispatch.resolve_backend("auto") == "pallas_interpret"


def test_none_backend_means_auto(monkeypatch):
    monkeypatch.setattr(compat, "has_tpu", lambda: False)
    assert dispatch.resolve_backend(None) == dispatch.resolve_backend("auto")


def test_pallas_refused_without_tpu(monkeypatch):
    monkeypatch.setattr(compat, "has_tpu", lambda: False)
    with pytest.raises(dispatch.BackendUnavailableError) as ei:
        dispatch.resolve_backend("pallas")
    assert "TPU" in str(ei.value)
    assert ei.value.backend == "pallas"


def test_interpret_refused_on_tpu(monkeypatch):
    monkeypatch.setattr(compat, "has_tpu", lambda: True)
    with pytest.raises(dispatch.BackendUnavailableError, match="interpret"):
        dispatch.resolve_backend("pallas_interpret")


def test_tile_fallback_warns_once_per_shape_on_tpu(monkeypatch):
    from repro.kernels.plan import KernelConfig
    monkeypatch.setattr(compat, "has_tpu", lambda: True)
    monkeypatch.setattr(dispatch, "_FALLBACK_WARNED", set())
    tile = (KernelConfig(block_k=256), 128, 128, 128)    # K=128 % 256 != 0
    with pytest.warns(UserWarning, match="xla_ragged"):
        assert dispatch.resolve(("wgrad", "bf16"), "auto",
                                tile=tile) == "xla_ragged"
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert dispatch.resolve(("wgrad", "bf16"), "auto",
                                tile=tile) == "xla_ragged"


def test_unknown_backend_raises_valueerror():
    with pytest.raises(ValueError, match="unknown backend"):
        dispatch.resolve_backend("cuda")


def test_xla_alias_resolves_to_xla_ragged():
    assert dispatch.resolve_backend("xla") == "xla_ragged"


def test_default_backend_override_roundtrip():
    try:
        dispatch.set_default_backend("pallas_interpret")
        assert dispatch.resolve_backend("auto") == "pallas_interpret"
    finally:
        dispatch.set_default_backend(None)


def test_backend_matrix_reports_reasons():
    matrix = dispatch.backend_matrix()
    assert matrix["pallas_interpret"]["available"]
    for row in matrix.values():
        assert isinstance(row["available"], bool)
        if not row["available"]:
            assert row["reason"]


# ---------------------------------------------------------------------------
# cross-backend equivalence fixtures
# ---------------------------------------------------------------------------

SIZES = [100, 0, 37, 163, 129]
K, N = 256, 128


@pytest.fixture(scope="module")
def quantized_inputs():
    rng = np.random.default_rng(3)
    m = sum(SIZES)
    a = jnp.asarray(rng.standard_normal((m, K)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((len(SIZES), K, N)), jnp.float32)
    a8, sa = ref.quantize_tilewise_ref(a)
    b8, sb = jax.vmap(ref.quantize_blockwise_ref)(b)
    return a, b, a8, sa, b8, sb, jnp.asarray(SIZES, jnp.int32)


def test_padded_baseline_bitwise_vs_interpret(quantized_inputs):
    """ISSUE-1: interpret-mode dispatch re-run of the paper's central
    claim — padding-free output is bitwise identical to
    pad -> aligned GEMM -> unpad."""
    _, _, a8, sa, b8, sb, gs = quantized_inputs
    ours = dispatch.grouped_gemm_fp8(a8, sa, b8, sb, gs,
                                     backend="pallas_interpret",
                                     out_dtype=jnp.bfloat16)
    base = dispatch.grouped_gemm_fp8(a8, sa, b8, sb, gs,
                                     backend="padded_baseline",
                                     out_dtype=jnp.bfloat16)
    assert np.array_equal(np.asarray(ours, np.float32),
                          np.asarray(base, np.float32))


def test_all_cpu_backends_match(quantized_inputs):
    _, _, a8, sa, b8, sb, gs = quantized_inputs
    outs = {
        name: np.asarray(dispatch.grouped_gemm_fp8(
            a8, sa, b8, sb, gs, backend=name, out_dtype=jnp.float32))
        for name in ("pallas_interpret", "xla_ragged", "xla_exact",
                     "padded_baseline", "auto")
    }
    anchor = outs["xla_exact"]
    # exact-accumulation backends agree tightly; the bf16-dequantized
    # xla_ragged path carries fp8->bf16 input rounding over K=256
    for name in ("pallas_interpret", "padded_baseline"):
        np.testing.assert_allclose(outs[name], anchor, rtol=1e-5, atol=1e-4,
                                   err_msg=name)
    np.testing.assert_allclose(outs["xla_ragged"], anchor, rtol=5e-2,
                               atol=0.35)
    # "auto" is exactly whatever concrete backend it resolves to
    np.testing.assert_array_equal(outs["auto"],
                                  outs[dispatch.resolve_backend("auto")])


def test_highlevel_grouped_gemm_entry(quantized_inputs):
    a, b, a8, sa, b8, sb, gs = quantized_inputs
    y = dispatch.grouped_gemm(a, b, gs, backend="pallas_interpret",
                              out_dtype=jnp.float32)
    y_ref = dispatch.grouped_gemm_fp8(a8, sa, b8, sb, gs,
                                      backend="pallas_interpret",
                                      out_dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_ref))


def test_run_with_unavailable_backend_is_reasoned(monkeypatch,
                                                  quantized_inputs):
    _, _, a8, sa, b8, sb, gs = quantized_inputs
    monkeypatch.setattr(compat, "has_tpu", lambda: False)
    with pytest.raises(dispatch.BackendUnavailableError):
        dispatch.grouped_gemm_fp8(a8, sa, b8, sb, gs, backend="pallas")
