"""Differentiable grouped-linear: the fp8 custom VJP through the Pallas
kernel (interpret mode) — forward, dgrad AND wgrad all run padding-free
kernels through the dispatch registries.  Cross-checked against the
xla_exact path and finite-difference structure."""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.grouped_gemm import grouped_linear
from repro.kernels import dispatch


def _setup(sizes=(40, 0, 57), k=128, n=128, seed=0):
    rng = np.random.default_rng(seed)
    m = sum(sizes)
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((len(sizes), k, n)), jnp.float32)
    gs = jnp.asarray(sizes, jnp.int32)
    return x, w, gs


def test_fp8_pallas_fwd_matches_xla_exact():
    x, w, gs = _setup()
    y_pal = grouped_linear(x, w, gs, precision="fp8",
                           backend="pallas_interpret")
    y_ref = grouped_linear(x, w, gs, precision="fp8", backend="xla_exact")
    np.testing.assert_allclose(np.asarray(y_pal, np.float32),
                               np.asarray(y_ref, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_fp8_pallas_grads_match_xla_exact():
    x, w, gs = _setup()

    def loss(x, w, backend):
        y = grouped_linear(x, w, gs, precision="fp8", backend=backend)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    gx_p, gw_p = jax.grad(loss, argnums=(0, 1))(x, w, "pallas_interpret")
    gx_r, gw_r = jax.grad(loss, argnums=(0, 1))(x, w, "xla_exact")
    assert bool(jnp.isfinite(gx_p).all()) and bool(jnp.isfinite(gw_p).all())
    np.testing.assert_allclose(np.asarray(gx_p), np.asarray(gx_r),
                               rtol=5e-2, atol=5e-1)
    np.testing.assert_allclose(np.asarray(gw_p), np.asarray(gw_r),
                               rtol=5e-2, atol=5e-1)


def test_bf16_grouped_linear_grad_structure():
    """Gradients respect the group structure: dW[g] only sees rows of
    group g (zero-size group -> exactly zero gradient)."""
    x, w, gs = _setup(sizes=(40, 0, 57))

    def loss(w):
        y = grouped_linear(x, w, gs, precision="bf16")
        return jnp.sum(y.astype(jnp.float32) ** 2)

    gw = jax.grad(loss)(w)
    assert float(jnp.abs(gw[1]).max()) == 0.0      # empty group
    assert float(jnp.abs(gw[0]).max()) > 0.0
    assert float(jnp.abs(gw[2]).max()) > 0.0


def _grad_backends():
    """Every grouped-GEMM backend the fp8 VJP can run here (the gemm
    family drives the forward/dgrad; wgrad resolves the same name)."""
    names = []
    for name in ("pallas", "pallas_interpret", "xla_ragged", "xla_exact"):
        if dispatch.availability(name)[0]:
            names.append(name)
    return names


@pytest.mark.parametrize("backend", _grad_backends())
def test_fp8_tail_dx_rows_exactly_zero(backend):
    """REGRESSION (unowned-row gradient corruption): with
    sum(group_sizes) < M — the normal capacity-buffer case — jax.grad
    through grouped_linear(precision='fp8') must produce EXACTLY zero dx
    for rows beyond the last group on every backend.  Pre-fix, the
    kernel's masked store left those rows uninitialized (NaN in interpret
    mode) and moe_apply's take-VJP scatter-added them into real token
    gradients."""
    rng = np.random.default_rng(29)
    m_buf, k, n = 256, 128, 128
    sizes = (60, 0, 30)                         # sum=90 < 256
    total = sum(sizes)
    x = jnp.asarray(rng.standard_normal((m_buf, k)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((len(sizes), k, n)), jnp.float32)
    gs = jnp.asarray(sizes, jnp.int32)

    def loss(x, w):
        y = grouped_linear(x, w, gs, precision="fp8", backend=backend)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    gx, gw = jax.grad(loss, argnums=(0, 1))(x, w)
    tail = np.asarray(gx[total:])
    assert np.all(tail == 0.0), \
        (f"{backend}: tail dx rows must be exactly zero, got "
         f"{tail[np.nonzero(tail)][:4]} (nan count "
         f"{int(np.isnan(tail).sum())})")
    assert np.all(np.isfinite(np.asarray(gx[:total])))
    assert np.all(np.isfinite(np.asarray(gw)))
    assert float(jnp.abs(gw[1]).max()) == 0.0   # empty group's wgrad


def test_fp8_bwd_wgrad_runs_through_registry(monkeypatch):
    """The fp8 backward's dw goes through dispatch.grouped_gemm_wgrad —
    ragged_dot_general is only the registry's fallback entry now."""
    x, w, gs = _setup()
    calls = []
    real = dispatch.grouped_gemm_wgrad

    def spying(*a, **kw):
        calls.append(kw.get("plan") is not None)
        return real(*a, **kw)

    monkeypatch.setattr(dispatch, "grouped_gemm_wgrad", spying)

    def loss(w):
        y = grouped_linear(x, w, gs, precision="fp8",
                           backend="pallas_interpret")
        return jnp.sum(y.astype(jnp.float32) ** 2)

    jax.grad(loss)(w)
    assert calls == [True], \
        "wgrad must route through the registry with the forward's plan"


def test_bf16_backend_kwarg_warns_instead_of_silent_drop():
    x, w, gs = _setup(sizes=(16, 16), k=128, n=128)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        grouped_linear(x, w, gs, precision="bf16", backend="pallas")
    assert any("ignores backend" in str(c.message) for c in caught)
    # backend='auto' and backend=None stay silent
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        grouped_linear(x, w, gs, precision="bf16", backend="auto")
        grouped_linear(x, w, gs, precision="bf16")
    assert not [c for c in caught if "ignores backend" in str(c.message)]
