"""The true-Pallas ``(gemm, bf16)`` registry entry: bitwise parity with
its accumulation-order oracle over ragged shapes, tolerance agreement
with the ``ragged_dot`` baseline, exact zero-fill contracts, and the
same registration/tile-fallback semantics as every other plan consumer.

Why the oracle, not ``ragged_dot``, carries the bitwise claim: XLA's
``ragged_dot`` lowering splits the K reduction differently per output-row
segment, so its f32 sums differ from per-tile MXU dots in the last ulp
(~1e-4 of output bits flip even after the bf16 cast).  ``gmm_bf16_
xla_exact`` replays the kernel's exact reduction order — one dense f32
dot per (group, 128-wide K block) — and dense-dot M-tiling is
bitwise-stable, so kernel-vs-oracle equality is exact while
kernel-vs-ragged_dot is a (tight) tolerance check."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import dispatch
from repro.kernels import plan as plan_mod
from repro.kernels.dispatch import gmm_bf16_xla_exact
from repro.kernels.grouped_gemm_kernel import gmm_pallas_bf16
from repro.kernels.plan import KernelConfig

# ragged: balanced, empty group + sum<M capacity tail, all-empty,
# single group, multi-M-tile block_m=256 walk
CASES = [
    ([128, 128, 128, 128], 512, 256, 256, 128),
    ([200, 0, 150, 100], 512, 256, 256, 128),
    ([0, 0, 0], 256, 128, 128, 128),
    ([300], 384, 128, 256, 128),
    ([100, 300, 50], 512, 384, 256, 256),
]


def _inputs(sizes, m, k, n, seed=0):
    g = len(sizes)
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((g, k, n)), jnp.float32)
    return x, w, jnp.asarray(sizes, jnp.int32)


@pytest.mark.parametrize("sizes,m,k,n,bm", CASES)
def test_bitwise_matches_exact_oracle(sizes, m, k, n, bm):
    x, w, gs = _inputs(sizes, m, k, n)
    out = gmm_pallas_bf16(x, w, gs, num_groups=len(sizes), block_m=bm,
                          interpret=True)
    ref = gmm_bf16_xla_exact(x, w, gs)
    assert out.dtype == ref.dtype == jnp.bfloat16
    assert np.array_equal(np.asarray(out).view(np.uint16),
                          np.asarray(ref).view(np.uint16)), \
        "bf16 Pallas kernel diverged bitwise from its reduction-order oracle"


@pytest.mark.parametrize("sizes,m,k,n,bm", CASES)
def test_close_to_ragged_dot_baseline(sizes, m, k, n, bm):
    x, w, gs = _inputs(sizes, m, k, n)
    out = gmm_pallas_bf16(x, w, gs, num_groups=len(sizes), block_m=bm,
                          interpret=True).astype(jnp.float32)
    rd = jax.lax.ragged_dot(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                            gs, preferred_element_type=jnp.float32
                            ).astype(jnp.bfloat16).astype(jnp.float32)
    total = int(sum(sizes))
    np.testing.assert_allclose(np.asarray(out[:total]),
                               np.asarray(rd[:total]),
                               rtol=2e-2, atol=2e-2)


def test_tail_rows_exact_zero():
    x, w, gs = _inputs([60, 30], 256, 128, 128)   # sum=90 << m
    out = gmm_pallas_bf16(x, w, gs, num_groups=2, interpret=True)
    assert np.all(np.asarray(out[90:], np.float32) == 0.0)


def test_m_zero_short_circuit():
    x, w, gs = _inputs([0, 0], 0, 128, 128)
    out = gmm_pallas_bf16(x, w, gs, num_groups=2, interpret=True)
    assert out.shape == (0, 128) and out.dtype == jnp.bfloat16


def test_k_mismatch_raises():
    x, w, gs = _inputs([128, 128], 256, 128, 128)
    with pytest.raises(ValueError, match="disagree on K"):
        gmm_pallas_bf16(x, w[:, :64, :], gs, num_groups=2, interpret=True)


def test_registry_entries():
    names = dispatch.op_backend_names(("gemm", "bf16"))
    assert {"pallas", "pallas_interpret", "xla_ragged",
            "xla_exact"} <= set(names)
    table = dispatch._OPERATORS[dispatch.OpKey("gemm", "bf16")]
    for name in ("pallas", "pallas_interpret"):
        assert table[name].uses_plan and table[name].uses_tiles
    # interpret + oracle are runnable everywhere (CPU CI)
    assert dispatch.op_availability(("gemm", "bf16"),
                                    "pallas_interpret")[0]
    assert dispatch.op_availability(("gemm", "bf16"), "xla_exact")[0]


def test_dispatch_pallas_interpret_matches_oracle_backend():
    x, w, gs = _inputs([200, 0, 150, 100], 512, 256, 256)
    out = dispatch.grouped_gemm_bf16(x, w, gs, backend="pallas_interpret",
                                     out_dtype=jnp.bfloat16)
    ref = dispatch.grouped_gemm_bf16(x, w, gs, backend="xla_exact",
                                     out_dtype=jnp.bfloat16)
    assert np.array_equal(np.asarray(out).view(np.uint16),
                          np.asarray(ref).view(np.uint16))


def test_tile_fallback_semantics():
    """Auto-resolved kernels whose tiles don't divide (K, N) fall back to
    a tile-free entry; explicit requests raise — the same policy as every
    other registry citizen."""
    cfg = KernelConfig(block_n=128, block_k=128)
    # N=192 indivisible: auto falls back
    name = dispatch.resolve(("gemm", "bf16"), None, tile=(cfg, 256, 128, 192))
    assert name in ("xla_ragged", "xla_exact")
    with pytest.raises(ValueError):
        dispatch.resolve(("gemm", "bf16"), "pallas_interpret",
                         tile=(cfg.with_(backend="pallas_interpret"),
                               256, 128, 192))


def test_autotune_gemm_bf16_op(tmp_path):
    cache = str(tmp_path / "cache.json")
    cfg = plan_mod.autotune(256, 128, 128, 4, measure=True, op="gemm_bf16",
                            backend="pallas_interpret", cache_path=cache)
    assert (cfg.n_span, cfg.k_span) == (1, 1)
    assert cfg.backend == "pallas_interpret"
    rep = plan_mod.last_autotune_report()
    assert rep["op"] == "gemm_bf16" and rep["source"] == "measured"


def test_contract_facts_cover_bf16_gemm():
    facts = dispatch.op_contract_facts()
    f = facts[dispatch.OpKey("gemm", "bf16")]
    assert f["entry_point"] == "grouped_gemm_bf16"
    assert f["padding_free"] is True
