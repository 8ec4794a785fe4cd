"""Ragged-contraction (wgrad) grouped GEMM — Pallas TPU kernel.

``dw[g] = x_g^T @ dy_g`` where the *contraction* dimension is the ragged M
axis: groups own contiguous, dynamically-sized row ranges of the
concatenated token buffer, and each group's rows contract against the same
rows of the upstream gradient.  This is the last GEMM of the fp8 training
step (paper's training workload) and the ROADMAP's "N-side raggedness"
item — before this kernel the backward detoured through XLA's
``ragged_dot_general`` fallback (``dispatch.wgrad_xla_ragged``).

The forward kernel's insight transfers unchanged: the *schedule* depends
only on ``(group_sizes, M, block_m)``, so the same :class:`TilePlan` built
once per routing decision serves gate/up/down forwards, both dgrads, and
every wgrad.  What changes is the role of a visit:

  * the grid walks ``(K super-tiles, N super-tiles, visits)`` with the
    visit axis innermost; visit t touches M-tile ``m_tile_ids[t]`` on
    behalf of group ``group_ids[t]``;
  * instead of a masked *store* of an output row tile, each visit performs
    a masked *accumulation* into the group's dense ``[k_span*block_k,
    n_span*block_n]`` output super-tile: rows of the M-tile owned by other
    groups (or beyond ``sum(group_sizes)``) are zeroed before the
    transposed dot, so boundary tiles contribute exactly their owned rows;
  * the multi-tile spans are the VMEM-residency lever: one grid cell
    fetches its ``(block_m, k_span*block_k)`` x tile and ``(block_m,
    n_span*block_n)`` dy tile ONCE and sweeps every ``(block_k, block_n)``
    sub-tile of the super-tile from those resident copies — at span 1 the
    x tile is re-fetched from HBM on every N step and dy on every K step
    (the old schedule, still the exact per-cell accumulation this kernel
    reproduces bitwise: the sub-tile dots have the same shapes, operand
    values and visit order regardless of span);
  * consecutive visits of one group share the output block (``group_ids``
    is non-decreasing), so Pallas keeps it resident in VMEM across the
    group's M-tiles and flushes once per group — the accumulation analogue
    of the forward's "safe overlapping write";
  * padding visits either sweep tail tiles (no owned rows -> zero
    contribution) or duplicate the last real visit (detected by comparing
    ``(group_ids, m_tile_ids)`` against the previous visit and skipped —
    accumulation, unlike the forward's store, is not idempotent).

Groups that receive zero rows are never visited, so their output blocks
are undefined on exit; a ``jnp.where`` epilogue pins them to the
mathematically correct zeros.

Two operand precisions share the schedule machinery:

  * :func:`gmm_pallas_wgrad` — operands arrive un-quantized (bf16/f32):
    DeepSeek-V3 (and the paper) keep wgrad at the highest precision of the
    three training GEMMs, so there is no scale bookkeeping — just f32
    accumulation of bf16 products, matching ``ragged_dot_general``
    numerics.  This is the default.
  * :func:`gmm_pallas_wgrad_fp8` — the all-fp8 step of arXiv 2505.20524:
    x and dy arrive as fp8 with their 1x128 per-row tile scales (the SAME
    ``(a8, sa)`` the forward GEMM consumed and the SAME ``(d8, sd)`` the
    dgrad quantized — nothing is re-quantized for the wgrad).  Each visit
    dequantizes its owned rows on the fly: the scale-multiply is folded
    into the masked ``jnp.where`` prologue, so unowned/garbage rows are
    zeroed and owned rows are rescaled in one VPU pass before the
    f32-accumulated transposed dot.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.grouped_gemm_kernel import select_index
from repro.kernels.plan import (QUANT_BLOCK, KernelConfig, TilePlan,
                                make_tile_plan)


def _visit_bookkeeping(group_offsets_ref, group_ids_ref, m_tile_ids_ref,
                       *, block_m, max_visits):
    """Shared per-visit schedule logic of BOTH wgrad kernels (bf16 and
    fp8 operands walk the identical visitation schedule).

    Returns ``(first, last, owned)``:

      * ``first``/``last`` — visit-run boundaries: group_ids is
        non-decreasing, so a group's visits are adjacent and its output
        block stays resident in VMEM between them;
      * ``owned`` — (block_m, 1) row mask: rows of this M-tile inside the
        visit's group range, with *duplicate* padding visits masked out
        entirely (padding visits with no tail tiles to sweep replicate
        the last real visit; re-accumulating it would double-count).
    """
    t = pl.program_id(2)
    g = group_ids_ref[t]
    m_tile = m_tile_ids_ref[t]
    prev_g = group_ids_ref[jnp.maximum(t - 1, 0)]
    prev_tile = m_tile_ids_ref[jnp.maximum(t - 1, 0)]
    next_g = group_ids_ref[jnp.minimum(t + 1, max_visits - 1)]

    first = (t == 0) | (g != prev_g)
    last = (t == max_visits - 1) | (next_g != g)
    dup = (t > 0) & (g == prev_g) & (m_tile == prev_tile)

    start = group_offsets_ref[g]
    end = group_offsets_ref[g + 1]
    rows = m_tile * block_m + jax.lax.broadcasted_iota(
        jnp.int32, (block_m, 1), 0)
    owned = (rows >= start) & (rows < end) & jnp.logical_not(dup)
    return first, last, owned


def _zero_empty_groups(dw, plan, out_dtype):
    """Empty groups are never visited, so their output blocks are
    undefined on exit — pin them to the mathematically correct zeros
    (shared epilogue of both wgrad drivers)."""
    nonempty = (plan.group_offsets[1:] - plan.group_offsets[:-1]) > 0
    return jnp.where(nonempty[:, None, None], dw, jnp.zeros((), out_dtype))


def _run_ragged_contraction(kernel_body, operands, in_specs, group_sizes, *,
                            m, k, n, num_groups, block_m, block_n, block_k,
                            out_dtype, interpret, plan,
                            n_span=1, k_span=1):
    """Shared driver of both wgrad precisions: M=0 short-circuit,
    plan-or-build, the (K super-tiles, N super-tiles, visits) grid, the
    pallas_call scaffold (dense [G, K, N] output, f32 super-tile
    accumulator scratch, parallel/parallel/arbitrary semantics), and the
    empty-group epilogue.  The precision variants differ only in their
    operand list + BlockSpecs and the kernel body; everything
    scheduling-related lives HERE once."""
    if m == 0:
        return jnp.zeros((num_groups, k, n), out_dtype)
    if plan is None:
        plan = make_tile_plan(group_sizes, m, block_m=block_m,
                              num_groups=num_groups)
    wk = block_k * k_span
    wn = block_n * n_span
    grid = (k // wk, n // wn, plan.max_visits)
    kernel = functools.partial(
        kernel_body, block_m=block_m, block_k=block_k, block_n=block_n,
        max_visits=plan.max_visits, out_dtype=out_dtype,
        n_span=n_span, k_span=k_span)
    dw = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (1, wk, wn),
                lambda k_i, n_i, t, go, gi, mi: (gi[t], k_i, n_i)),
            scratch_shapes=[pltpu.VMEM((wk, wn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((num_groups, k, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(plan.group_offsets, plan.group_ids, plan.m_tile_ids, *operands)
    return _zero_empty_groups(dw, plan, out_dtype)


def _span_accumulate(acc_ref, x, dy, *, block_k, block_n, n_span, k_span):
    """Accumulate every (block_k, block_n) sub-tile dot of one visit into
    the f32 super-tile accumulator.  The sub-tile dots are EXACTLY the
    single-tile kernel's per-(k, n)-cell dots — same operand shapes, same
    values, same per-cell f32 addition order across visits — assembled
    into one super-tile update, so any span is bitwise-equal to span 1.
    ``x``/``dy`` are the visit's masked f32 operand tiles, ``(block_m,
    k_span*block_k)`` and ``(block_m, n_span*block_n)``, already resident
    in VMEM — the static sub-tile loop re-slices them instead of
    re-fetching from HBM."""
    rows = []
    for kk in range(k_span):
        xs = x[:, kk * block_k:(kk + 1) * block_k]
        cells = [
            jax.lax.dot_general(
                xs, dy[:, nn * block_n:(nn + 1) * block_n],
                dimension_numbers=(((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            for nn in range(n_span)
        ]
        rows.append(cells[0] if n_span == 1
                    else jnp.concatenate(cells, axis=1))
    update = rows[0] if k_span == 1 else jnp.concatenate(rows, axis=0)
    acc_ref[...] += update


def _gmm_wgrad_kernel(group_offsets_ref, group_ids_ref, m_tile_ids_ref,
                      x_ref, dy_ref,                     # VMEM in
                      out_ref,                           # VMEM out
                      acc_ref,                           # scratch
                      *, block_m, block_k, block_n, max_visits, out_dtype,
                      n_span, k_span):
    first, last, owned = _visit_bookkeeping(
        group_offsets_ref, group_ids_ref, m_tile_ids_ref,
        block_m=block_m, max_visits=max_visits)

    @pl.when(first)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # mask BOTH operands: rows beyond M (the block-padded tail of the last
    # tile) or beyond sum(group_sizes) may hold garbage/NaN, and 0 * NaN
    # would still poison the accumulation.  One mask covers the whole
    # fetched span tile — the sub-tile loop slices the resident copy.
    x = jnp.where(owned, x_ref[...].astype(jnp.float32), 0.0)    # (bm, wk)
    dy = jnp.where(owned, dy_ref[...].astype(jnp.float32), 0.0)  # (bm, wn)
    _span_accumulate(acc_ref, x, dy, block_k=block_k, block_n=block_n,
                     n_span=n_span, k_span=k_span)

    @pl.when(last)
    def _store():
        out_ref[0] = acc_ref[...].astype(out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=("num_groups", "block_m", "block_n", "block_k",
                     "out_dtype", "interpret", "n_span", "k_span"))
def gmm_pallas_wgrad(x: jax.Array, dy: jax.Array, group_sizes: jax.Array, *,
                     num_groups: int | None = None,
                     block_m: int = 128, block_n: int = 128,
                     block_k: int = 128,
                     out_dtype: Any = jnp.float32, interpret: bool = False,
                     plan: TilePlan | None = None,
                     n_span: int = 1, k_span: int = 1):
    """Padding-free ragged-contraction grouped GEMM (wgrad orientation).

    x:  [M, K] float — concatenated groups, arbitrary (ragged) M^g,
        ``sum(group_sizes) <= M`` (rows beyond the last group, and any
        garbage they hold, are excluded from the contraction)
    dy: [M, N] float — upstream gradient over the same row buffer
    group_sizes: [G] int32
    plan: optional precomputed :class:`TilePlan` for this
        ``(group_sizes, M)`` — the SAME plan the forward/dgrad GEMMs of
        this routing decision used (the schedule is orientation-agnostic).
        When given, its ``block_m`` governs the contraction tiling and the
        ``block_m`` argument is ignored.  The usual TilePlan contract
        applies: it must have been built from these ``group_sizes``.
    n_span/k_span: multi-tile schedule — one grid cell owns a
        ``(k_span*block_k, n_span*block_n)`` output super-tile and keeps
        its x/dy operand tiles VMEM-resident across the sub-tiles, so x
        is fetched once per ``n_span`` N steps and dy once per ``k_span``
        K steps.  Bitwise-equal to span 1 (the per-cell dots and their
        accumulation order are unchanged); K must divide by
        ``block_k*k_span`` and N by ``block_n*n_span``.
    returns [G, K, N] out_dtype with ``dw[g] = x_g^T @ dy_g`` in f32
        accumulation; groups with zero rows come back exactly zero.
    """
    m, k = x.shape
    m2, n = dy.shape
    if m != m2:
        raise ValueError(
            f"x and dy disagree on M: x is [M={m}, K={k}] but dy is "
            f"[M={m2}, N={n}]")
    num_groups = num_groups or group_sizes.shape[0]
    if plan is not None:
        block_m = plan.block_m
        plan.check_against(m, block_m, num_groups)
    KernelConfig(block_m=block_m, block_n=block_n, block_k=block_k,
                 n_span=n_span, k_span=k_span).validate(m, k, n,
                                                        family="wgrad")

    wk = block_k * k_span
    wn = block_n * n_span
    in_specs = [
        # x tile: globally block-aligned copy of the visit's M-tile,
        # K-span slice (resident across the super-tile's N sub-steps)
        pl.BlockSpec((block_m, wk),
                     lambda k_i, n_i, t, go, gi, mi: (mi[t], k_i)),
        # dy tile: same M-tile, N-span slice
        pl.BlockSpec((block_m, wn),
                     lambda k_i, n_i, t, go, gi, mi: (mi[t], n_i)),
    ]
    return _run_ragged_contraction(
        _gmm_wgrad_kernel, (x, dy), in_specs, group_sizes,
        m=m, k=k, n=n, num_groups=num_groups, block_m=block_m,
        block_n=block_n, block_k=block_k, out_dtype=out_dtype,
        interpret=interpret, plan=plan, n_span=n_span, k_span=k_span)


# ---------------------------------------------------------------------------
# fp8-operand variant (arXiv 2505.20524: the all-fp8 training step)
# ---------------------------------------------------------------------------

def _gmm_wgrad_fp8_kernel(group_offsets_ref, group_ids_ref, m_tile_ids_ref,
                          x_ref, sx_ref, dy_ref, sdy_ref,   # VMEM in
                          out_ref,                          # VMEM out
                          acc_ref,                          # scratch
                          *, block_m, block_k, block_n, max_visits,
                          out_dtype, n_span, k_span):
    k_i = pl.program_id(0)
    n_i = pl.program_id(1)
    first, last, owned = _visit_bookkeeping(
        group_offsets_ref, group_ids_ref, m_tile_ids_ref,
        block_m=block_m, max_visits=max_visits)

    @pl.when(first)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # per-row 1x128 tile scales for this visit's K-span / N-span slice
    # (whole scale rows travel on the M-tile like the forward's S_A
    # over-fetch; the span widens the slice, not the fetch), each column
    # picked by mask and broadcast over its 128 lanes
    def expand(s_ref, first, count):                    # -> (bm, count*128)
        s = s_ref[...]
        return jnp.concatenate(
            [jnp.broadcast_to(select_index(s, first + c, 1),
                              (block_m, QUANT_BLOCK)) for c in range(count)],
            axis=1)

    kq = block_k // QUANT_BLOCK
    nq = block_n // QUANT_BLOCK
    sx_full = expand(sx_ref, k_i * k_span * kq, k_span * kq)      # (bm, wk)
    sdy_full = expand(sdy_ref, n_i * n_span * nq, n_span * nq)    # (bm, wn)

    # dequantize-on-visit with the scale-multiply folded into the masked
    # prologue: one jnp.where zeroes unowned rows (whose fp8 payload AND
    # scale rows may be garbage — 0 * NaN would poison the accumulation)
    # and rescales owned ones, then the sub-tile dots accumulate in f32
    x = jnp.where(owned, x_ref[...].astype(jnp.float32) * sx_full, 0.0)
    dy = jnp.where(owned, dy_ref[...].astype(jnp.float32) * sdy_full, 0.0)
    _span_accumulate(acc_ref, x, dy, block_k=block_k, block_n=block_n,
                     n_span=n_span, k_span=k_span)

    @pl.when(last)
    def _store():
        out_ref[0] = acc_ref[...].astype(out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=("num_groups", "block_m", "block_n", "block_k",
                     "out_dtype", "interpret", "n_span", "k_span"))
def gmm_pallas_wgrad_fp8(x_fp8: jax.Array, s_x: jax.Array,
                         dy_fp8: jax.Array, s_dy: jax.Array,
                         group_sizes: jax.Array, *,
                         num_groups: int | None = None,
                         block_m: int = 128, block_n: int = 128,
                         block_k: int = 128,
                         out_dtype: Any = jnp.float32,
                         interpret: bool = False,
                         plan: TilePlan | None = None,
                         n_span: int = 1, k_span: int = 1):
    """Padding-free ragged-contraction grouped GEMM with fp8 operands.

    x_fp8:  [M, K]  fp8 e4m3 — the forward's quantized activation (the
            VJP residual; NOT re-quantized for the wgrad)
    s_x:    [M, KB] f32 — its 1x128 tile scales (KB = ceil(K/128))
    dy_fp8: [M, N]  fp8 e4m3 — the upstream gradient as quantized for the
            dgrad (one ``quantize_tilewise(dy)`` serves both backward GEMMs)
    s_dy:   [M, NB] f32 — its 1x128 tile scales (NB = ceil(N/128))
    group_sizes: [G] int32, ``sum <= M`` (tail rows excluded)
    plan:   optional precomputed :class:`TilePlan` — the SAME plan every
            other GEMM of this routing decision used; its ``block_m``
            governs the contraction tiling when given.
    n_span/k_span: multi-tile schedule (see :func:`gmm_pallas_wgrad`) —
            the scale rows stay resident with their operand tile, so the
            span cuts the scale-row re-fetch too.
    returns [G, K, N] out_dtype with ``dw[g] = x_g^T @ dy_g`` where each
            visit dequantizes its owned rows (scale-multiply in the masked
            prologue) before the f32-accumulated transposed dot; groups
            with zero rows come back exactly zero.
    """
    m, k = x_fp8.shape
    m2, n = dy_fp8.shape
    if m != m2:
        raise ValueError(
            f"x and dy disagree on M: x_fp8 is [M={m}, K={k}] but dy_fp8 "
            f"is [M={m2}, N={n}]")
    kb = (k + QUANT_BLOCK - 1) // QUANT_BLOCK
    nb = (n + QUANT_BLOCK - 1) // QUANT_BLOCK
    if s_x.shape != (m, kb):
        raise ValueError(
            f"s_x must be [M={m}, ceil(K/{QUANT_BLOCK})={kb}], got "
            f"{s_x.shape} (x_fp8 {x_fp8.shape})")
    if s_dy.shape != (m, nb):
        raise ValueError(
            f"s_dy must be [M={m}, ceil(N/{QUANT_BLOCK})={nb}], got "
            f"{s_dy.shape} (dy_fp8 {dy_fp8.shape})")
    num_groups = num_groups or group_sizes.shape[0]
    if plan is not None:
        block_m = plan.block_m
        plan.check_against(m, block_m, num_groups)
    KernelConfig(block_m=block_m, block_n=block_n, block_k=block_k,
                 wgrad_precision="fp8", n_span=n_span,
                 k_span=k_span).validate(m, k, n, family="wgrad")

    wk = block_k * k_span
    wn = block_n * n_span
    in_specs = [
        # x tile: the visit's M-tile, K-span slice (fp8 payload, resident
        # across the super-tile's N sub-steps)
        pl.BlockSpec((block_m, wk),
                     lambda k_i, n_i, t, go, gi, mi: (mi[t], k_i)),
        # S_x: whole scale row per M-tile (forward-style over-fetch,
        # padded to the 128-lane VMEM tile)
        pl.BlockSpec((block_m, kb),
                     lambda k_i, n_i, t, go, gi, mi: (mi[t], 0)),
        # dy tile: same M-tile, N-span slice (fp8 payload)
        pl.BlockSpec((block_m, wn),
                     lambda k_i, n_i, t, go, gi, mi: (mi[t], n_i)),
        # S_dy: whole scale row per M-tile
        pl.BlockSpec((block_m, nb),
                     lambda k_i, n_i, t, go, gi, mi: (mi[t], 0)),
    ]
    return _run_ragged_contraction(
        _gmm_wgrad_fp8_kernel, (x_fp8, s_x, dy_fp8, s_dy), in_specs,
        group_sizes, m=m, k=k, n=n, num_groups=num_groups, block_m=block_m,
        block_n=block_n, block_k=block_k, out_dtype=out_dtype,
        interpret=interpret, plan=plan, n_span=n_span, k_span=k_span)
