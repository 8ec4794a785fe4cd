"""FP8 quantization with straight-through gradients.

1x128 per-tile activation quant + 128x128 per-block weight quant — the
paper's (= DeepSeek-V3's) scheme.  ``quantize_*_ste`` are the autodiff-safe
entry points used by the training path.

:class:`QuantizedActivation` is the quantize-once record: one
``quantize_tilewise`` of a shared activation buffer, carried alongside the
:class:`~repro.kernels.plan.TilePlan` through ``grouped_linear`` so every
GEMM consuming the same buffer (the MoE gate and up projections, and —
under ``wgrad_precision="fp8"`` — the backward's wgrad via the VJP
residual) amortizes the quantization like the schedule metadata.

:class:`QuantizedWeight` is its serving counterpart for weights: the
128x128-block fp8 form of a weight that does not change, built once by
:func:`quantize_weight` (the serving engine does so at construction) and
passed to the fp8 GEMMs in place of the raw weight.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.analysis import events as _events
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.scopes import QUANT_WEIGHTS, scope

QUANT_BLOCK = kref.QUANT_BLOCK
FP8_MAX = kref.FP8_MAX


@dataclasses.dataclass(frozen=True)
class QuantizedActivation:
    """1x128-tile fp8 representation of one activation buffer.

    ``q``: [M, K] fp8 e4m3; ``scale``: [M, ceil(K/128)] f32 with
    ``x ≈ q * repeat(scale, 128, axis=1)``.  A registered pytree, so it
    rides through ``jit``/``shard_map`` and custom_vjp arguments next to
    the TilePlan.

    CONTRACT: a record is only valid for the exact buffer it was built
    from — passing it to ``grouped_linear(x, ...)`` with a *different*
    ``x`` produces silently wrong output (the forward consumes ``(q,
    scale)`` wholesale and only uses ``x`` for dtype/VJP bookkeeping).
    Build it with :func:`quantize_activation` at the point the buffer is
    produced, never cache it across routing decisions.
    """
    q: jax.Array       # [M, K] fp8 e4m3
    scale: jax.Array   # [M, ceil(K/128)] f32


jax.tree_util.register_pytree_node(
    QuantizedActivation,
    lambda qa: ((qa.q, qa.scale), None),
    lambda _, children: QuantizedActivation(*children))


@dataclasses.dataclass(frozen=True)
class QuantizedWeight:
    """128x128-block fp8 representation of one constant weight.

    ``q``: [..., K, N] fp8 e4m3; ``scale``: [..., ceil(K/128),
    ceil(N/128)] f32 — exactly what the fp8 GEMMs' own weight quantization
    computes from the raw weight.  Leading axes (experts, stacked layers)
    index both leaves alike; a registered pytree, so a ``scan`` over
    stacked layers slices it like any other leaf.  Forward only: the
    backward needs the raw weight, so a gradient through a record raises.
    """
    q: jax.Array       # [..., K, N] fp8 e4m3
    scale: jax.Array   # [..., ceil(K/128), ceil(N/128)] f32

    @property
    def shape(self):
        """The raw weight's shape (the payload's)."""
        return self.q.shape


jax.tree_util.register_pytree_node(
    QuantizedWeight,
    lambda qw: ((qw.q, qw.scale), None),
    lambda _, children: QuantizedWeight(*children))


@functools.partial(jax.jit, static_argnames=("backend",))
def quantize_weight(w, *, backend=None) -> QuantizedWeight:
    """ONE 128x128-block quantization of ``w`` [..., K, N] into a
    :class:`QuantizedWeight`: the f32 upcast and
    :func:`quantize_blockwise_batched` the fp8 GEMMs apply to a raw
    weight, so the record holds the same values bitwise.  One jitted call
    per weight keeps a single f32 upcast live at a time."""
    with scope(QUANT_WEIGHTS):
        lead, (k, n) = w.shape[:-2], w.shape[-2:]
        q8, s = quantize_blockwise_batched(
            w.reshape(-1, k, n).astype(jnp.float32), backend=backend)
        return QuantizedWeight(q8.reshape(*lead, *q8.shape[1:]),
                               s.reshape(*lead, *s.shape[1:]))


def quantize_activation(x, *, backend=None, config=None) -> QuantizedActivation:
    """ONE ``quantize_tilewise`` call producing the shareable record.

    The input is ``stop_gradient``-ed: gradients flow to the activation
    through ``grouped_linear``'s custom VJP (which returns a zero
    cotangent for the record itself), not through the quantization graph.
    ``config`` (optional) routes an autotuned quantizer tile height
    (``op="quantize"``) into the kernel; the record is tile-height
    independent either way.
    """
    q8, s = quantize_tilewise(
        jax.lax.stop_gradient(x).astype(jnp.float32), backend=backend,
        config=config)
    return QuantizedActivation(q8, s)


def fused_act_quantize(g, u=None, *, act="silu_mul", backend=None,
                       config=None) -> QuantizedActivation:
    """Fused producer: activation + ONE tilewise quantization, no bf16
    intermediate.

    Routes ``silu(g)*u`` (or unary ``gelu(g)``) through the
    ``(act_quant, fp8)`` operator and wraps the result as a
    :class:`QuantizedActivation` — the same record
    :func:`quantize_activation` builds, minus the HBM round-trip of the
    activation buffer.  Inputs are ``stop_gradient``-ed: gradients reach
    ``g``/``u`` through the fused ``grouped_linear`` VJP's activation
    recompute, not through the quantization graph.  ``config`` routes an
    autotuned tile height (``op="act_quant"``); the record is
    tile-height independent.
    """
    gq = jax.lax.stop_gradient(g).astype(jnp.float32)
    uq = None if u is None else jax.lax.stop_gradient(u).astype(jnp.float32)
    q8, s = kops.act_quantize(gq, uq, act=act, backend=backend,
                              config=config)
    return QuantizedActivation(q8, s)


def fused_act_quantize_fp8(g8, s_g, u8=None, s_u=None, *, act="silu_mul",
                           backend=None, config=None) -> QuantizedActivation:
    """Fused producer epilogue on *fp8* operands.

    The fused-producer GEMM (``grouped_gemm_quant``) emits gate/up as fp8
    payloads + 1x128 scales; this routes them through the ``(act_quant,
    fp8)`` operator's dequant-on-load mode, so the bf16 g/u buffers never
    exist anywhere.  Payloads and scales are already detached (they come
    out of a non-differentiable producer), so no ``stop_gradient`` is
    needed; gradients reach the FFN inputs through the fused VJP's
    activation recompute.
    """
    q8, s = kops.act_quantize(g8, u8, act=act, backend=backend,
                              config=config, s_g=s_g, s_u=s_u)
    return QuantizedActivation(q8, s)


@jax.custom_vjp
def quantize_dequantize_tilewise(x):
    """fake-quant (quant->dequant) with straight-through gradient; used to
    inject fp8 noise into reference paths when validating training."""
    q, s = kref.quantize_tilewise_ref(x)
    return kref.dequantize_tilewise_ref(q, s).astype(x.dtype)


def _qdq_fwd(x):
    return quantize_dequantize_tilewise(x), None


def _qdq_bwd(_, g):
    return (g,)


quantize_dequantize_tilewise.defvjp(_qdq_fwd, _qdq_bwd)


def quantize_tilewise(x, *, backend=None, config=None):
    """[M, K] -> (fp8[M, K], f32[M, K/128]).  Not differentiable — use
    inside custom_vjp boundaries (see core.grouped_gemm).  ``config``
    optionally carries an autotuned quantizer tile height (the output is
    tile-height independent)."""
    # one event per STANDALONE tilewise quantization — the quantize-once
    # contracts (REPRO-C01) count these; fused epilogues (act_quantize,
    # grouped_gemm_quant) quantize in-kernel and do not pass through here
    _events.emit("quantize_tilewise", shape=tuple(x.shape))
    return kops.quantize_tilewise(x, backend=backend, config=config)


def quantize_blockwise(w, *, backend=None):
    """[K, N] -> (fp8[K, N], f32[K/128, N/128])."""
    return kops.quantize_blockwise(w, backend=backend)


def quantize_blockwise_batched(w, *, backend=None):
    """[G, K, N] -> (fp8[G, K, N], f32[G, K/128, N/128]).  Routes through
    the dispatch registry like the unbatched form, so a future quant
    kernel covers both paths."""
    return kops.quantize_blockwise_batched(w, backend=backend)
