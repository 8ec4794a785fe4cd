"""TMA-Adaptive FP8 Grouped GEMM — Pallas TPU kernel.

This is the TPU-native re-derivation of the paper's padding-free grouped
GEMM (see DESIGN.md §2 for the Hopper→TPU mapping).  The paper's problem:

  * groups have dynamic row counts ``M^g`` (MoE routing), but the bulk-copy
    engine (Hopper TMA there, Pallas ``BlockSpec`` pipelining here) only
    moves statically-shaped, aligned blocks;
  * padding every group to ``block_m`` wastes memory + bandwidth + flops.

The paper's fix is a pool of ``log2(block_m)`` TMA descriptors plus a
two-phase *overlapping, idempotent* store for each residual block.  The TPU
equivalent implemented here:

  * the grid walks **globally block-aligned tiles of the unpadded,
    concatenated token buffer** — every HBM→VMEM copy is aligned by
    construction (the analogue of TMA's static-descriptor compliance);
  * a tile that straddles a group boundary is *visited once per group that
    intersects it* (scalar-prefetched ``group_ids``/``m_tile_ids`` schedule);
  * each visit computes the full tile against its group's ``B^g`` and
    performs a **masked read-modify-write** of the output tile in VMEM —
    rows owned by other groups are preserved.  Same-tile visits are adjacent
    in the grid, so Pallas keeps the output block resident in VMEM between
    them and flushes it to HBM exactly once (the "safe overlapping write"
    of paper §2.2, with the identical cost profile: ≤2 visits per boundary
    tile, independent of the residual size).

Alignment bookkeeping (paper §2.3) maps to:
  * ``block_n % 128 == 0``  (lane width / MXU tile; paper: ``block_N % 64``)
  * ``K % block_k == 0`` and ``block_k % 128 == 0`` (quant-tile alignment)
  * scale rows ``S_A`` travel on the same global M-tiles as ``A`` — the
    whole per-row scale vector is over-fetched once per tile (padded to the
    128-lane VMEM tile), the analogue of the paper's ``[block_M+16, ...]``
    over-fetch descriptor.

Quantization: A is fp8 e4m3 with 1x128 per-tile scales, B is fp8 e4m3 with
128x128 per-block scales (DeepSeek-V3 recipe, as in the paper).  The MXU on
v5e consumes bf16, so operands are upconverted in VREGs; the memory-side
wins — which are what the paper measures — are dtype-native.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.quant_kernel import FP8_MAX
from repro.kernels.plan import (  # noqa: F401  (metadata lives in plan.py;
    QUANT_BLOCK,                   # re-exported here for pre-plan callers)
    KernelConfig,
    TilePlan,
    make_group_metadata,
    make_tile_plan,
)


def validate_kernel_config(m, k, n, block_m, block_n, block_k):
    """TPU-adapted alignment constraints (analogue of paper's block_N % 64).

    Folded into :class:`repro.kernels.plan.KernelConfig`: construction
    checks the static block constraints, :meth:`KernelConfig.validate`
    the shape-dependent ones.  M is deliberately unconstrained — handling
    arbitrary (ragged) M without padding is the point of the paper.
    """
    KernelConfig(block_m=block_m, block_n=block_n,
                 block_k=block_k).validate(m, k, n)


def select_index(s, idx, axis):
    """``s`` narrowed to entry ``idx`` of ``axis`` (kept as a size-1 dim)
    for a traced ``idx``.  Mosaic lowers no ``dynamic_slice`` of a value,
    so an iota mask keeps the one entry and the sum adds only exact zeros
    to it: the result is bitwise the sliced entry, whatever the others
    (possibly garbage) hold."""
    pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, axis)
    return jnp.sum(jnp.where(pos == idx, s, 0.0), axis=axis, keepdims=True)


def _accumulate_visit(a_ref, sa_ref, b_ref, sb_ref, acc_ref, *,
                      n_i, k_i, block_m, block_n, block_k):
    """One visit's MXU work: the fine-grained-rescaled partial products of
    this (m_tile, n_i, k_i) step accumulated into the f32 scratch.  Shared
    by the plain and the quantizing-epilogue kernels — the visit machinery
    is identical, only the store phase differs."""
    # MXU work on the full, always-aligned tile (rows of a neighbouring
    # group compute garbage that the masked store below discards — the
    # cost-equivalent of the paper's redundant overlapping TMA write).
    a = a_ref[...].astype(jnp.float32)                 # (bm, bk)
    b = b_ref[0].astype(jnp.float32)                   # (bk, bn)

    # --- fine-grained rescale (DeepSeek 1x128 x 128x128 recipe) ---------
    # sa_ref: (bm, KB) over-fetched whole scale rows; sb_ref: (1, KB, NB)
    # the group's whole scale block.  This step's entries are picked by
    # mask (select_index), never sliced at a traced offset.
    kq = block_k // QUANT_BLOCK                        # quant tiles per k step
    nq = block_n // QUANT_BLOCK                        # quant blocks per n step
    sa_all = sa_ref[...]
    sb_all = sb_ref[0]
    # one MXU dot per 128-wide quant sub-tile so per-tile scales stay exact
    for j in range(kq):
        aj = a[:, j * QUANT_BLOCK:(j + 1) * QUANT_BLOCK]
        bj = b[j * QUANT_BLOCK:(j + 1) * QUANT_BLOCK]
        pj = jax.lax.dot(aj, bj, preferred_element_type=jnp.float32)
        sa_j = select_index(sa_all, k_i * kq + j, 1)          # (bm, 1)
        sb_j = select_index(sb_all, k_i * kq + j, 0)          # (1, NB)
        col_scale = jnp.concatenate(
            [jnp.broadcast_to(select_index(sb_j, n_i * nq + q, 1),
                              (1, QUANT_BLOCK)) for q in range(nq)],
            axis=1)                                           # (1, bn)
        acc_ref[...] += pj * sa_j * col_scale


def _gmm_kernel(group_offsets_ref, group_ids_ref, m_tile_ids_ref,  # prefetch
                a_ref, sa_ref, b_ref, sb_ref,                      # VMEM in
                out_ref,                                           # VMEM out
                acc_ref,                                           # scratch
                *, block_m, block_n, block_k, k_steps, num_groups,
                out_dtype):
    n_i = pl.program_id(0)
    t = pl.program_id(1)
    k_i = pl.program_id(2)

    g = group_ids_ref[t]
    m_tile = m_tile_ids_ref[t]

    @pl.when(k_i == 0)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    _accumulate_visit(a_ref, sa_ref, b_ref, sb_ref, acc_ref,
                      n_i=n_i, k_i=k_i, block_m=block_m, block_n=block_n,
                      block_k=block_k)

    @pl.when(k_i == k_steps - 1)
    def _store():
        # Masked RMW — the two-phase overlapping-store analogue.  Rows of
        # this tile owned by group g are [start, end); rows owned by *no*
        # group (>= sum(group_sizes) — the capacity-buffer tail) are
        # zero-filled so the output is fully defined (the fp8 backward's
        # dx feeds a scatter-add; garbage tails would corrupt real token
        # gradients); everything else is preserved from the previous
        # (adjacent) visit's contents.  Padding visits in the schedule
        # sweep the tail tiles precisely so this zero-fill reaches every
        # unowned row (see make_group_metadata).
        start = group_offsets_ref[g]
        end = group_offsets_ref[g + 1]
        total = group_offsets_ref[num_groups]
        rows = m_tile * block_m + jax.lax.broadcasted_iota(
            jnp.int32, (block_m, block_n), 0)
        owned = (rows >= start) & (rows < end)
        unowned = rows >= total
        prev = out_ref[...]
        out_ref[...] = jnp.where(
            owned, acc_ref[...].astype(out_dtype),
            jnp.where(unowned, jnp.zeros_like(prev), prev))


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "out_dtype",
                     "interpret", "num_groups"))
def gmm_pallas(a_fp8: jax.Array, s_a: jax.Array, b_fp8: jax.Array,
               s_b: jax.Array, group_sizes: jax.Array, *,
               num_groups: int | None = None,
               block_m: int = 128, block_n: int = 128, block_k: int = 128,
               out_dtype: Any = jnp.bfloat16, interpret: bool = False,
               plan: TilePlan | None = None):
    """Padding-free fp8 grouped GEMM.

    a_fp8:  [M, K]   fp8 e4m3 — concatenated groups, arbitrary (ragged) M^g
    s_a:    [M, KB]  f32      — 1x128 tile scales (KB = ceil(K/128))
    b_fp8:  [G, K, N] fp8
    s_b:    [G, KB, NB] f32   — 128x128 block scales
    group_sizes: [G] int32, sum <= M.  Rows in ``[sum(group_sizes), M)``
            (the unowned tail of a capacity buffer) come back as DEFINED
            zeros — the schedule's padding visits sweep the tail tiles and
            the masked store zero-fills every row no group owns, so
            downstream consumers (the fp8 backward's take-VJP scatter-add)
            never see uninitialized memory.
    plan:   optional precomputed :class:`TilePlan` for this
            ``(group_sizes, M, block_m)`` — pass it to amortize the
            schedule across the several GEMMs of one routing decision
            (built here when absent).  The plan MUST have been built from
            these ``group_sizes``: its schedule replaces them wholesale,
            and only the static (m, block_m, num_groups) triple is
            checkable — a plan from a different routing decision gives
            silently wrong output (see :class:`TilePlan`)
    returns [M, N] out_dtype
    """
    m, k = a_fp8.shape
    g, k2, n = b_fp8.shape
    if k != k2:
        raise ValueError(
            f"A and B disagree on K: a_fp8 is [M={m}, K={k}] but b_fp8 is "
            f"[G={g}, K={k2}, N={n}]")
    num_groups = num_groups or g
    validate_kernel_config(m, k, n, block_m, block_n, block_k)
    kb = s_a.shape[1]
    expected_kb = (k + QUANT_BLOCK - 1) // QUANT_BLOCK
    if kb != expected_kb:
        raise ValueError(
            f"s_a has {kb} scale columns but K={k} needs "
            f"ceil(K/{QUANT_BLOCK}) = {expected_kb} (s_a shape "
            f"{s_a.shape}, a_fp8 shape {a_fp8.shape})")

    if m == 0:
        return jnp.zeros((0, n), out_dtype)

    if plan is None:
        plan = make_tile_plan(group_sizes, m, block_m=block_m,
                              num_groups=num_groups)
    else:
        plan.check_against(m, block_m, num_groups)
    k_steps = k // block_k

    grid = (n // block_n, plan.max_visits, k_steps)

    kernel = functools.partial(
        _gmm_kernel, block_m=block_m, block_n=block_n, block_k=block_k,
        k_steps=k_steps, num_groups=num_groups, out_dtype=out_dtype)

    def _run_kernel(group_offsets, group_ids, m_tile_ids):
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=grid,
                in_specs=[
                    # A tile: globally block-aligned HBM->VMEM copy
                    pl.BlockSpec((block_m, block_k),
                                 lambda n_i, t, k_i, go, gi, mi: (mi[t], k_i)),
                    # S_A: over-fetch the whole scale row per tile (padded to
                    # the 128-lane VMEM tile) — paper §2.3 analogue
                    pl.BlockSpec((block_m, kb),
                                 lambda n_i, t, k_i, go, gi, mi: (mi[t], 0)),
                    # B^g tile, selected by the visit's group id
                    pl.BlockSpec((1, block_k, block_n),
                                 lambda n_i, t, k_i, go, gi, mi: (gi[t], k_i, n_i)),
                    # S_B^g: whole per-group scale block (tiny)
                    pl.BlockSpec((1, kb, s_b.shape[2]),
                                 lambda n_i, t, k_i, go, gi, mi: (gi[t], 0, 0)),
                ],
                out_specs=pl.BlockSpec(
                    (block_m, block_n),
                    lambda n_i, t, k_i, go, gi, mi: (mi[t], n_i)),
                scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
            ),
            out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            ),
            interpret=interpret,
        )(group_offsets, group_ids, m_tile_ids, a_fp8, s_a, b_fp8, s_b)

    # all-empty schedule (every group size 0): the zero-visit plan owns no
    # rows, so short-circuit to defined zeros instead of launching visits
    # that leave the whole buffer uninitialized
    return jax.lax.cond(
        plan.total_rows() > 0,
        lambda go, gi, mi: _run_kernel(go, gi, mi),
        lambda go, gi, mi: jnp.zeros((m, n), out_dtype),
        plan.group_offsets, plan.group_ids, plan.m_tile_ids)


def _gmm_bf16_kernel(group_offsets_ref, group_ids_ref, m_tile_ids_ref,
                     a_ref, b_ref,                                  # VMEM in
                     out_ref,                                       # VMEM out
                     acc_ref,                                       # scratch
                     *, block_m, block_n, block_k, k_steps, num_groups,
                     out_dtype):
    """True-bf16 twin of :func:`_gmm_kernel`: identical grid walk, visit
    schedule, and masked-RMW store — no scale operands and no rescale
    (the numerics-baseline orientation, so every fp8-vs-bf16 comparison
    measures OUR schedule on both sides, not XLA's).  Accumulation stays
    one f32 MXU dot per 128-wide K sub-tile, the same reduction order as
    the fp8 kernel (and the ``gmm_bf16_xla_exact`` oracle)."""
    n_i = pl.program_id(0)
    t = pl.program_id(1)
    k_i = pl.program_id(2)

    g = group_ids_ref[t]
    m_tile = m_tile_ids_ref[t]

    @pl.when(k_i == 0)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...].astype(jnp.float32)                 # (bm, bk)
    b = b_ref[0].astype(jnp.float32)                   # (bk, bn)
    for j in range(block_k // QUANT_BLOCK):
        aj = a[:, j * QUANT_BLOCK:(j + 1) * QUANT_BLOCK]
        bj = b[j * QUANT_BLOCK:(j + 1) * QUANT_BLOCK]
        acc_ref[...] += jax.lax.dot(aj, bj,
                                    preferred_element_type=jnp.float32)

    @pl.when(k_i == k_steps - 1)
    def _store():
        # same masked RMW as the fp8 kernel: owned rows store, unowned
        # tail rows zero-fill, everything else preserves the adjacent
        # visit's contents
        start = group_offsets_ref[g]
        end = group_offsets_ref[g + 1]
        total = group_offsets_ref[num_groups]
        rows = m_tile * block_m + jax.lax.broadcasted_iota(
            jnp.int32, (block_m, block_n), 0)
        owned = (rows >= start) & (rows < end)
        unowned = rows >= total
        prev = out_ref[...]
        out_ref[...] = jnp.where(
            owned, acc_ref[...].astype(out_dtype),
            jnp.where(unowned, jnp.zeros_like(prev), prev))


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "out_dtype",
                     "interpret", "num_groups"))
def gmm_pallas_bf16(x: jax.Array, w: jax.Array, group_sizes: jax.Array, *,
                    num_groups: int | None = None,
                    block_m: int = 128, block_n: int = 128,
                    block_k: int = 128,
                    out_dtype: Any = jnp.bfloat16, interpret: bool = False,
                    plan: TilePlan | None = None):
    """Padding-free bf16 grouped GEMM — the true-Pallas ``(gemm, bf16)``
    registry entry.

    x:  [M, K] float — concatenated groups (cast to bf16 operands, like
        the ``ragged_dot`` baseline this kernel replaces)
    w:  [G, K, N] float — per-group weights (cast to bf16)
    group_sizes: [G] int32, sum <= M; tail rows come back as DEFINED
        zeros (same masked-store contract as :func:`gmm_pallas`)
    plan: optional precomputed :class:`TilePlan` — the same plan-reuse
        contract as every other kernel of a routing decision.
    returns [M, N] out_dtype with f32 accumulation of bf16 products.
    """
    m, k = x.shape
    g, k2, n = w.shape
    if k != k2:
        raise ValueError(
            f"x and w disagree on K: x is [M={m}, K={k}] but w is "
            f"[G={g}, K={k2}, N={n}]")
    num_groups = num_groups or g
    validate_kernel_config(m, k, n, block_m, block_n, block_k)

    if m == 0:
        return jnp.zeros((0, n), out_dtype)
    x16 = x.astype(jnp.bfloat16)
    w16 = w.astype(jnp.bfloat16)

    if plan is None:
        plan = make_tile_plan(group_sizes, m, block_m=block_m,
                              num_groups=num_groups)
    else:
        plan.check_against(m, block_m, num_groups)
    k_steps = k // block_k

    grid = (n // block_n, plan.max_visits, k_steps)

    kernel = functools.partial(
        _gmm_bf16_kernel, block_m=block_m, block_n=block_n, block_k=block_k,
        k_steps=k_steps, num_groups=num_groups, out_dtype=out_dtype)

    def _run_kernel(group_offsets, group_ids, m_tile_ids):
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=grid,
                in_specs=[
                    # A tile: globally block-aligned HBM->VMEM copy
                    pl.BlockSpec((block_m, block_k),
                                 lambda n_i, t, k_i, go, gi, mi: (mi[t], k_i)),
                    # B^g tile, selected by the visit's group id
                    pl.BlockSpec((1, block_k, block_n),
                                 lambda n_i, t, k_i, go, gi, mi: (gi[t], k_i, n_i)),
                ],
                out_specs=pl.BlockSpec(
                    (block_m, block_n),
                    lambda n_i, t, k_i, go, gi, mi: (mi[t], n_i)),
                scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
            ),
            out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            ),
            interpret=interpret,
        )(group_offsets, group_ids, m_tile_ids, x16, w16)

    # all-empty schedule: short-circuit to defined zeros (same contract
    # as the fp8 kernel)
    return jax.lax.cond(
        plan.total_rows() > 0,
        lambda go, gi, mi: _run_kernel(go, gi, mi),
        lambda go, gi, mi: jnp.zeros((m, n), out_dtype),
        plan.group_offsets, plan.group_ids, plan.m_tile_ids)


def _gmm_quant_kernel(group_offsets_ref, group_ids_ref, m_tile_ids_ref,
                      a_ref, sa_ref, b_ref, sb_ref,                # VMEM in
                      q_ref, s_ref,                                # VMEM out
                      acc_ref,                                     # scratch
                      *, block_m, block_n, block_k, k_steps, num_groups,
                      out_dtype):
    """Quantizing-epilogue twin of :func:`_gmm_kernel`.

    Identical visit machinery; the store phase rounds the accumulator
    through ``out_dtype`` (so the payload is bitwise what the unfused
    GEMM -> quantize_tilewise composition produces), computes the per-row
    amax over each 128-wide N quant tile, and emits the fp8 payload plus
    the 1x128 scales directly — the bf16 output never exists.  The masked
    RMW extends to both outputs: unowned tail rows get payload 0 and
    scale 1, exactly what quantizing a zero-filled row yields, so the
    zero-fill contract survives fusion.
    """
    n_i = pl.program_id(0)
    t = pl.program_id(1)
    k_i = pl.program_id(2)

    g = group_ids_ref[t]
    m_tile = m_tile_ids_ref[t]

    @pl.when(k_i == 0)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    _accumulate_visit(a_ref, sa_ref, b_ref, sb_ref, acc_ref,
                      n_i=n_i, k_i=k_i, block_m=block_m, block_n=block_n,
                      block_k=block_k)

    @pl.when(k_i == k_steps - 1)
    def _store():
        start = group_offsets_ref[g]
        end = group_offsets_ref[g + 1]
        total = group_offsets_ref[num_groups]
        # per-ROW masks (bm, 1): the amax reduction is along N, so row
        # ownership decides both the payload columns and the scale columns
        rows = m_tile * block_m + jax.lax.broadcasted_iota(
            jnp.int32, (block_m, 1), 0)
        owned = (rows >= start) & (rows < end)
        unowned = rows >= total
        # round through out_dtype first: the unfused composition stores the
        # GEMM output in out_dtype and quantizes its f32 upcast — matching
        # that rounding point is what makes fused-vs-unfused bitwise
        h = acc_ref[...].astype(out_dtype).astype(jnp.float32)
        nq = block_n // QUANT_BLOCK
        tiles = h.reshape(block_m, nq, QUANT_BLOCK)
        amax = jnp.max(jnp.abs(tiles), axis=-1)                  # (bm, nq)
        scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
        qv = (tiles / scale[..., None]).reshape(block_m, block_n)
        # payload select in f32, then one cast: fp8->f32->fp8 on the
        # preserved columns is lossless, and the owned columns round
        # exactly once (same as the standalone quantize kernel)
        prev_q = q_ref[...].astype(jnp.float32)
        q_ref[...] = jnp.where(
            owned, qv,
            jnp.where(unowned, jnp.zeros_like(qv), prev_q)).astype(q_ref.dtype)
        prev_s = s_ref[0]
        s_ref[0] = jnp.where(
            owned, scale, jnp.where(unowned, jnp.ones_like(scale), prev_s))


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "out_dtype",
                     "interpret", "num_groups"))
def gmm_pallas_quant(a_fp8: jax.Array, s_a: jax.Array, b_fp8: jax.Array,
                     s_b: jax.Array, group_sizes: jax.Array, *,
                     num_groups: int | None = None,
                     block_m: int = 128, block_n: int = 128,
                     block_k: int = 128,
                     out_dtype: Any = jnp.bfloat16, interpret: bool = False,
                     plan: TilePlan | None = None):
    """Padding-free fp8 grouped GEMM with a fused 1x128 quantizing epilogue.

    Same contract as :func:`gmm_pallas`, but instead of materializing the
    ``[M, N] out_dtype`` product it emits the DeepSeek-recipe quantized
    form directly from the epilogue:

    returns ``(q, s)``:
      q: [M, N]      fp8 e4m3 — ``out_dtype``-rounded product / scale
      s: [M, N/128]  f32      — per-row 1x128 tile scales

    ``out_dtype`` is the *intermediate rounding* dtype: the accumulator is
    rounded through it before the amax/scale computation, so the result is
    bitwise identical to ``quantize_tilewise(gmm_pallas(...).astype(f32))``.
    Tail rows in ``[sum(group_sizes), M)`` come back as payload 0 /
    scale 1 — what quantizing the unfused path's zero-filled tail yields —
    preserving the zero-fill contract for downstream consumers.
    """
    m, k = a_fp8.shape
    g, k2, n = b_fp8.shape
    if k != k2:
        raise ValueError(
            f"A and B disagree on K: a_fp8 is [M={m}, K={k}] but b_fp8 is "
            f"[G={g}, K={k2}, N={n}]")
    num_groups = num_groups or g
    validate_kernel_config(m, k, n, block_m, block_n, block_k)
    kb = s_a.shape[1]
    expected_kb = (k + QUANT_BLOCK - 1) // QUANT_BLOCK
    if kb != expected_kb:
        raise ValueError(
            f"s_a has {kb} scale columns but K={k} needs "
            f"ceil(K/{QUANT_BLOCK}) = {expected_kb} (s_a shape "
            f"{s_a.shape}, a_fp8 shape {a_fp8.shape})")
    nb = n // QUANT_BLOCK
    q_dtype = a_fp8.dtype

    if m == 0:
        return (jnp.zeros((0, n), q_dtype), jnp.ones((0, nb), jnp.float32))

    if plan is None:
        plan = make_tile_plan(group_sizes, m, block_m=block_m,
                              num_groups=num_groups)
    else:
        plan.check_against(m, block_m, num_groups)
    k_steps = k // block_k
    nq = block_n // QUANT_BLOCK
    n_steps = n // block_n

    grid = (n_steps, plan.max_visits, k_steps)

    kernel = functools.partial(
        _gmm_quant_kernel, block_m=block_m, block_n=block_n, block_k=block_k,
        k_steps=k_steps, num_groups=num_groups, out_dtype=out_dtype)

    def _run_kernel(group_offsets, group_ids, m_tile_ids):
        q, s3 = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=grid,
                in_specs=[
                    pl.BlockSpec((block_m, block_k),
                                 lambda n_i, t, k_i, go, gi, mi: (mi[t], k_i)),
                    pl.BlockSpec((block_m, kb),
                                 lambda n_i, t, k_i, go, gi, mi: (mi[t], 0)),
                    pl.BlockSpec((1, block_k, block_n),
                                 lambda n_i, t, k_i, go, gi, mi: (gi[t], k_i, n_i)),
                    pl.BlockSpec((1, kb, s_b.shape[2]),
                                 lambda n_i, t, k_i, go, gi, mi: (gi[t], 0, 0)),
                ],
                out_specs=[
                    # fp8 payload tile — same walk as the plain kernel's out
                    pl.BlockSpec((block_m, block_n),
                                 lambda n_i, t, k_i, go, gi, mi: (mi[t], n_i)),
                    # 1x128 scales: the nq columns of one N step, same
                    # M-tile walk, in an [N steps, M, nq] slab so the
                    # block's last dim is the array's own (a (bm, nq)
                    # block of [M, NB] breaks Mosaic's 128-lane rule)
                    pl.BlockSpec((1, block_m, nq),
                                 lambda n_i, t, k_i, go, gi, mi: (n_i, mi[t], 0)),
                ],
                scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
            ),
            out_shape=[
                jax.ShapeDtypeStruct((m, n), q_dtype),
                jax.ShapeDtypeStruct((n_steps, m, nq), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            ),
            interpret=interpret,
        )(group_offsets, group_ids, m_tile_ids, a_fp8, s_a, b_fp8, s_b)
        return q, s3.transpose(1, 0, 2).reshape(m, nb)

    # all-empty schedule: payload 0 / scale 1 everywhere — bitwise what
    # quantizing the unfused path's all-zero output produces
    return jax.lax.cond(
        plan.total_rows() > 0,
        lambda go, gi, mi: _run_kernel(go, gi, mi),
        lambda go, gi, mi: (jnp.zeros((m, n), q_dtype),
                            jnp.ones((m, nb), jnp.float32)),
        plan.group_offsets, plan.group_ids, plan.m_tile_ids)
