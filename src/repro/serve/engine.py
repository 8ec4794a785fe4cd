"""Batched serving engine: prefill + greedy/temperature decode loop with a
static KV-cache capacity (continuous-batching-lite: per-sequence stop with
a done mask; finished rows keep decoding into padding, standard for
static-shape TPU serving).

Plan-aware decode: a decode step's MoE grouped GEMM sees *tiny, constant*
M (batch x top_k routed rows in total), where the training-shaped 128-row
tiles waste almost every fetched A row.  The engine therefore resolves a
decode-specialized :class:`~repro.kernels.plan.KernelConfig` (the
``block_m<=16`` pool entries, ``op="decode"`` in the autotuner) ONCE at
construction and rebuilds the decode-phase model closures over it —
prefill keeps the caller's (or default) config, so the two phases pin
separate tuned tile geometries while sharing one param tree.  Inside the
jitted decode loop the TilePlan schedule is then traced once and replayed
every step — one plan build per phase, the serving analogue of the
paper's configure-once/select-cheaply descriptor pool.

Weights are quantized once, too: the engine replaces every fp8 MoE
weight by its 128x128-block fp8 payload and scales at construction
(``transformer.quantize_serving_params``), as the paper configures its
descriptor pool once, so the compiled prefill and decode programs take
fp8 weights as inputs and quantize none.  The engine keeps only the
quantized tree; the raw expert weights are the caller's to drop.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.quantization import QuantizedWeight
from repro.kernels import dispatch
from repro.kernels import plan as plan_mod
from repro.kernels.plan import KernelConfig
from repro.models import model_zoo
from repro.models.model_zoo import Model
from repro.models.transformer import quantize_serving_params
from repro.scopes import ENGINE_DECODE, ENGINE_PREFILL, ENGINE_SAMPLE, span


@dataclasses.dataclass
class GenerationResult:
    tokens: jax.Array          # [B, max_new]
    num_generated: jax.Array   # [B]


class Engine:
    """``kernel_config`` pins the *prefill* phase's tile shapes (and is
    inherited as the base of the decode selection); ``decode_kernel_config``
    pins the decode phase explicitly, skipping the pool selection.
    ``decode_batch_size`` is the M-bucket hint for that selection — the
    engine stays correct for any actual batch (plans are traced per
    shape), the hint only steers which pool entry is pinned."""

    def __init__(self, model: Model, params, *, max_new_tokens: int = 32,
                 eos_id: int = -1, temperature: float = 0.0,
                 kernel_config: Optional[KernelConfig] = None,
                 decode_kernel_config: Optional[KernelConfig] = None,
                 decode_batch_size: int = 8):
        if kernel_config is not None:
            # pin tuned tile shapes for every GEMM the prefill traces by
            # rebuilding the model closures over a config carrying them
            model = model_zoo.with_kernel_config(model, kernel_config)
        self.model = model
        self.prefill_config = model.cfg.resolved_kernel_config
        # decode-specialized plan: resolved exactly ONCE per engine
        self.decode_config = (decode_kernel_config
                              if decode_kernel_config is not None
                              else self._select_decode_config(
                                  model.cfg, decode_batch_size))
        self._decode_model = (
            model_zoo.with_kernel_config(model, self.decode_config)
            if self.decode_config is not None else model)
        self.params = quantize_serving_params(params, model.cfg)
        # weights served pre-quantized (0 for bf16 or non-MoE models)
        self.quantized_weights = sum(
            isinstance(v, QuantizedWeight) for v in jax.tree.leaves(
                self.params, is_leaf=lambda v: isinstance(v, QuantizedWeight)))
        self.max_new = max_new_tokens
        self.eos_id = eos_id
        self.temperature = temperature
        self._prefill = jax.jit(self._prefill_impl,
                                static_argnames=("cache_capacity",))
        self._decode_loop = jax.jit(self._decode_loop_impl)

    @staticmethod
    def _select_decode_config(cfg, batch_hint: int) -> Optional[KernelConfig]:
        """One-time decode pool selection (cost-model ranked, cached
        beside the measured autotune entries).  ``None`` when the model
        has no grouped GEMM to specialize (non-MoE families) or the
        decode pool has no legal entry for its dims."""
        if cfg.moe is None:
            return None
        m = max(batch_hint, 1) * cfg.moe.top_k
        k, n, g = cfg.d_model, cfg.moe.d_ff_expert, cfg.moe.num_experts
        try:
            sel = plan_mod.decode_config(m, k, n, g,
                                         backend=cfg.gemm_backend)
        except (ValueError, dispatch.BackendUnavailableError):
            return None
        base = cfg.resolved_kernel_config
        if base is not None:
            # keep the run config's backend/out_dtype/wgrad choices; only
            # the tile geometry is decode-specialized
            sel = base.with_(block_m=sel.block_m, block_n=sel.block_n,
                             block_k=sel.block_k)
        return sel

    def _prefill_impl(self, params, batch, cache_capacity):
        logits, cache = self.model.prefill(params, batch,
                                           cache_capacity=cache_capacity)
        return logits[:, -1], cache

    def _sample(self, logits, key):
        if self.temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(
            key, logits.astype(jnp.float32) / self.temperature
        ).astype(jnp.int32)

    def _decode_loop_impl(self, params, first_token, cache, key):
        def step(carry, _):
            tok, cache, done, key = carry
            key, sub = jax.random.split(key)
            logits, cache = self._decode_model.decode_step(
                params, tok[:, None], cache)
            nxt = self._sample(logits[:, 0], sub)
            nxt = jnp.where(done, 0, nxt)
            done = done | (nxt == self.eos_id)
            return (nxt, cache, done, key), nxt

        b = first_token.shape[0]
        done0 = jnp.zeros((b,), bool)
        (_, cache, done, _), toks = jax.lax.scan(
            step, (first_token, cache, done0, key), None,
            length=self.max_new - 1)
        return toks.swapaxes(0, 1), done  # [B, max_new-1]

    def generate(self, batch, *, key=None) -> GenerationResult:
        key = key if key is not None else jax.random.PRNGKey(0)
        prompt_len = batch["tokens"].shape[1]
        extra = (self.model.cfg.num_patches
                 if self.model.cfg.family == "vlm" else 0)
        cap = prompt_len + extra + self.max_new
        with span(ENGINE_PREFILL):
            last_logits, cache = self._prefill(self.params, batch,
                                               cache_capacity=cap)
        with span(ENGINE_SAMPLE):
            key, sub = jax.random.split(key)
            first = self._sample(last_logits, sub)
        with span(ENGINE_DECODE):
            rest, done = self._decode_loop(self.params, first, cache, key)
            tokens = jnp.concatenate([first[:, None], rest], axis=1)
            num = jnp.full((tokens.shape[0],), tokens.shape[1], jnp.int32)
        return GenerationResult(tokens=tokens, num_generated=num)


# ---------------------------------------------------------------------------
# Kernel contracts (repro.analysis layer 1)
# ---------------------------------------------------------------------------
# Decode plan discipline, checked by a REAL smoke generate (mode="run" —
# jit with concrete args executes; same cost as the serving CI gate this
# replaced): one decode-config pool selection per Engine, block_m<=16,
# and exactly one plan build per phase per expert group (routed + shared
# x prefill + decode = 4), with the decode-phase build using the decode
# config's tile height.

from repro.analysis.contracts import register_contract as _register_contract


def _build_engine_contract():
    import os
    import tempfile

    from repro.configs import smoke_config
    from repro.models.model_zoo import make_model, synthetic_batch

    cfg = dataclasses.replace(smoke_config("qwen2-moe-a2.7b"),
                              precision="fp8",
                              gemm_backend="pallas_interpret")
    model = make_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    batch = synthetic_batch(jax.random.PRNGKey(1), cfg, 16, 2)

    def fn():
        # the decode selection autotunes through the JSON plan cache —
        # route the write to a throwaway path, never the user's cache
        prev = os.environ.get("REPRO_TILEPLAN_CACHE")
        os.environ["REPRO_TILEPLAN_CACHE"] = os.path.join(
            tempfile.mkdtemp(), "tileplan_cache.json")
        try:
            engine = Engine(model, params, max_new_tokens=6,
                            decode_batch_size=2)
        finally:
            if prev is None:
                os.environ.pop("REPRO_TILEPLAN_CACHE", None)
            else:
                os.environ["REPRO_TILEPLAN_CACHE"] = prev
        res = engine.generate(batch, key=jax.random.PRNGKey(42))
        return engine, res
    return fn, ()


def _check_engine_contract(result, events):
    engine, res = result
    msgs = []
    dc = engine.decode_config
    if dc is None:
        msgs.append("engine resolved no decode config for an MoE model")
    elif dc.block_m > 16:
        msgs.append(f"decode config block_m={dc.block_m} > 16 — not a "
                    f"decode-pool entry")
    if tuple(res.tokens.shape) != (2, 6):
        msgs.append(f"generate returned tokens of shape "
                    f"{tuple(res.tokens.shape)}, expected (2, 6)")
    builds = [e for e in events if e.kind == "plan_build"]
    # build order: prefill routed, prefill shared, decode routed, decode
    # shared — the decode-phase builds must use the decode tile height
    if dc is not None and len(builds) == 4 \
            and builds[2].data["block_m"] != dc.block_m:
        msgs.append(f"decode-phase plan build used "
                    f"block_m={builds[2].data['block_m']}, not the "
                    f"decode config's {dc.block_m}")
    return msgs


_register_contract(
    "engine.generate.decode_plan",
    description="one decode-config selection per Engine; a full generate "
                "(prefill + >=4 decode steps) builds plan metadata once "
                "per phase per expert group; decode tiles block_m<=16",
    build=_build_engine_contract,
    mode="run",
    decode_selects=1, plan_builds=4,
    extra=_check_engine_contract)


# ---------------------------------------------------------------------------
# Compile contracts (repro.analysis layer 5: REPRO-T02)
# ---------------------------------------------------------------------------
# Engine.generate compiles exactly once per phase: the first generate
# traces the prefill step and the decode loop once each, and a second
# generate over a same-shaped batch hits both jit caches.  The Engine is
# constructed inside the contract's trace window (it jits in __init__),
# so its entry points are the observed ones.

from repro.analysis.retrace import \
    register_compile_contract as _register_compile_contract


def _build_engine_retrace():
    import os
    import tempfile

    from repro.configs import smoke_config
    from repro.models.model_zoo import make_model, synthetic_batch

    cfg = dataclasses.replace(smoke_config("qwen2-moe-a2.7b"),
                              precision="fp8",
                              gemm_backend="pallas_interpret")
    model = make_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    batch = synthetic_batch(jax.random.PRNGKey(1), cfg, 16, 2)

    prev = os.environ.get("REPRO_TILEPLAN_CACHE")
    os.environ["REPRO_TILEPLAN_CACHE"] = os.path.join(
        tempfile.mkdtemp(), "tileplan_cache.json")
    try:
        engine = Engine(model, params, max_new_tokens=6,
                        decode_batch_size=2)
    finally:
        if prev is None:
            os.environ.pop("REPRO_TILEPLAN_CACHE", None)
        else:
            os.environ["REPRO_TILEPLAN_CACHE"] = prev

    def generate(key):
        return engine.generate(batch, key=key)
    calls = [(jax.random.PRNGKey(42),), (jax.random.PRNGKey(43),)]
    return generate, calls


_register_compile_contract(
    "engine.generate.retrace",
    description="two same-shape generates compile the prefill step and "
                "the decode loop exactly once each",
    build=_build_engine_retrace,
    expected={"_prefill_impl": 1, "_decode_loop_impl": 1},
    rule="REPRO-T02")
