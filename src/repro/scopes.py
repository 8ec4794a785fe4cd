"""The names of the model's layers inside the compiled program.

Every op traced under ``scope(name)`` carries ``name`` in its HLO
``op_name`` metadata (``jax.named_scope``), forward and backward alike:
JAX wraps backward ops as ``transpose(jvp(...))`` and keeps the scope,
and ops that ``jax.checkpoint`` recomputes keep theirs.  Scopes nest, so
an op's layer is the chain of these names on its ``op_name`` path, outer
to inner (the weight quantization of an expert GEMM reads
``moe.experts/quant.weights``).  The vocabulary is closed: a profile
reader maps every device op to these names and nothing else.

The ``engine.*`` names are host spans (``jax.profiler.TraceAnnotation``)
around the phases of ``serve.engine.Engine.generate``; with no profiler
session open they cost one check each.
"""
from __future__ import annotations

import jax

EMBED = "embed"                  # token embedding (and patch projection)
ATTN = "attn"                    # attention sub-block: QKV, cache, out proj
DENSE_FFN = "dense_ffn"          # MLP of a dense layer
MOE_ROUTE = "moe.route"          # router, top-k, balance losses
MOE_PACK = "moe.pack"            # sort, group sizes, tile plan, xs gather
MOE_EXPERTS = "moe.experts"      # routed expert FFN
MOE_COMBINE = "moe.combine"      # weighting, scatter-add, cross-shard sum
MOE_SHARED = "moe.shared"        # shared-expert FFN
QUANT_ACT = "quant.act"          # standalone 1x128 activation quantize
QUANT_WEIGHTS = "quant.weights"  # f32 upcast + 128x128 weight quantize
LM_HEAD = "lm_head"              # final norm, unembedding, loss
OPTIMIZER = "optimizer"          # AdamW update

VOCABULARY = (EMBED, ATTN, DENSE_FFN, MOE_ROUTE, MOE_PACK, MOE_EXPERTS,
              MOE_COMBINE, MOE_SHARED, QUANT_ACT, QUANT_WEIGHTS, LM_HEAD,
              OPTIMIZER)

ENGINE_PREFILL = "engine.prefill"  # prefill dispatch
ENGINE_SAMPLE = "engine.sample"    # key split, first-token sample
ENGINE_DECODE = "engine.decode"    # decode-loop dispatch, result assembly

HOST_SPANS = (ENGINE_PREFILL, ENGINE_SAMPLE, ENGINE_DECODE)


def scope(name: str):
    """``jax.named_scope(name)`` for a name of the vocabulary; usable as a
    context manager or a decorator."""
    if name not in VOCABULARY:
        raise ValueError(f"{name!r} is not a layer scope; "
                         f"known: {VOCABULARY}")
    return jax.named_scope(name)


def span(name: str):
    """A host span of the profiler trace for a name of ``HOST_SPANS``."""
    if name not in HOST_SPANS:
        raise ValueError(f"{name!r} is not a host span; known: {HOST_SPANS}")
    return jax.profiler.TraceAnnotation(name)
