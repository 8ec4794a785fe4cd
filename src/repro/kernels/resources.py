"""Static kernel-resource model: per-program VMEM footprints + feasibility.

The paper's second pillar is *static* TMA-alignment-aware management:
every descriptor's tile geometry is decided before launch, against known
alignment (16B global / 128B shared) and SMEM budgets.  This module is
the Pallas/TPU analogue — a pure-arithmetic model of what one kernel
program keeps resident in VMEM under a given ``(block_m, block_n,
block_k)`` geometry, mirroring the BlockSpecs the kernels in this
package actually declare:

* grouped GEMM (``gmm_pallas``): A tile ``(bm, bk)`` fp8, the whole S_A
  scale row ``(bm, ceil(K/128))`` f32 (over-fetched per M-tile), B tile
  ``(bk, bn)`` fp8, S_B block ``(ceil(K/128), ceil(N/128))`` f32, the
  output tile, and one f32 accumulator scratch ``(bm, bn)``;
* the quantizing-epilogue twin (``gmm_pallas_quant``): fp8 payload tile
  + ``(bm, bn/128)`` f32 scale tile instead of the wide output;
* ragged wgrad: x ``(bm, k_span*bk)`` / dy ``(bm, n_span*bn)`` operand
  tiles (bf16, or fp8 + their 1x128 scale rows) — the multi-tile spans
  keep each operand tile VMEM-resident across the sub-tiles of one
  ``(k_span*bk, n_span*bn)`` output super-tile — plus that super-tile's
  f32 dw block and accumulator;
* tilewise quantize / fused act_quant: whole-K row blocks ``(bm, K)``
  (one input for quantize, gate AND up for the fused epilogue) plus the
  fp8 payload and f32 scale outputs.

Tiles are costed at the TPU's physical VMEM layout (last dim padded to
128 lanes, second-to-last to the dtype's sublane granularity), and
pipelined blocks are double-buffered (:data:`PIPELINE_BUFFERS`) — the
standard Pallas grid pipeline keeps the next block in flight while the
current one computes.

Consumers: ``analysis/resource_lint.py`` proves every pool entry fits
every device budget (REPRO-V01..V07); ``plan.autotune`` prunes
statically-infeasible candidates before measuring; and
``KernelConfig.validate`` raises with the computed footprint instead of
letting Mosaic fail opaquely at compile time.

Stdlib-only — no jax import, so the budget math runs device-free (the
CI's fast pre-suite lint step).
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

#: bump when the footprint formulas or budgets change: the autotune JSON
#: cache namespaces its keys by this, so selections made under an older
#: model (e.g. pre-pruning) are ignored rather than trusted
RESOURCE_MODEL_VERSION = 3

QUANT_BLOCK = 128   # 1x128 / 128x128 scale granularity (must agree with
                    # plan/ref/quantization — REPRO-R06 checks the set)
LANE = 128          # VMEM lane width: last tile dim pads to this
MXU_M = 128         # rows of one MXU pass (cost + degeneracy granularity)

#: pipelined in/out blocks are double-buffered by the Pallas grid
#: pipeline; scratch (accumulators) is single-buffered
PIPELINE_BUFFERS = 2

#: decode pool entries never exceed this tile height (serving M is
#: batch*top_k rows TOTAL; see plan.DECODE_BLOCK_MS)
DECODE_MAX_BLOCK_M = 16

#: per-device VMEM budget in bytes (the ``plan.DEVICE_SPECS`` limit).
#: TPU VMEM is ~16 MiB/core on v5e-class parts and double that on the
#: larger v4/v5p parts; the "cpu" (interpret-mode) entry carries the
#: TIGHTEST real budget so configs tuned on CPU CI transfer to any TPU.
VMEM_BYTES: "Dict[str, int]" = {
    "tpu v5 lite": 16 * 2**20,
    "tpu v5e": 16 * 2**20,
    "tpu": 32 * 2**20,
    "cpu": 16 * 2**20,
}

#: footprint-modelled operator families (dispatch families map 1:1)
FAMILIES = ("gemm", "gemm_quant", "wgrad", "quantize", "act_quant")


def vmem_budget(device_kind: str) -> int:
    """VMEM budget for a device kind, longest-prefix matched (mirrors
    ``plan.device_spec``'s matching so ``"TPU v5 lite"`` hits the v5e
    entry).  A kind no entry matches raises."""
    kind = device_kind.lower()
    best = None
    for prefix, budget in VMEM_BYTES.items():
        if kind.startswith(prefix) and (best is None or len(prefix) > best[0]):
            best = (len(prefix), budget)
    if best is None:
        raise ValueError(f"no VMEM budget for device kind {device_kind!r}; "
                         f"known: {sorted(VMEM_BYTES)}")
    return best[1]


# ---------------------------------------------------------------------------
# Tile arithmetic
# ---------------------------------------------------------------------------

def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def _round_up(x: int, mult: int) -> int:
    return _ceil_div(x, mult) * mult


def sublane(itemsize: int) -> int:
    """Second-to-last-dim granularity of a VMEM tile: 8 sublanes of
    32-bit lanes — 8 rows for f32, 16 for bf16, 32 for fp8/int8."""
    return max(8 * (4 // max(itemsize, 1)), 8)


def tile_bytes(rows: int, cols: int, itemsize: int) -> int:
    """Bytes one ``(rows, cols)`` block occupies in VMEM at its physical
    tiling (cols padded to the 128-lane width, rows to the dtype's
    sublane granularity)."""
    return (_round_up(max(rows, 1), sublane(itemsize))
            * _round_up(max(cols, 1), LANE) * itemsize)


def config_blocks(config: Any) -> "Tuple[int, int, int]":
    """``(block_m, block_n, block_k)`` from a KernelConfig-like object or
    a plain dict (fixtures use dicts — a misaligned geometry cannot even
    construct a KernelConfig)."""
    if isinstance(config, dict):
        return (int(config["block_m"]), int(config.get("block_n", 128)),
                int(config.get("block_k", 128)))
    return (int(config.block_m), int(config.block_n), int(config.block_k))


def config_spans(config: Any) -> "Tuple[int, int]":
    """``(n_span, k_span)`` multi-tile wgrad spans from a KernelConfig-like
    object or a plain dict; absent fields mean the single-tile schedule."""
    if isinstance(config, dict):
        return (int(config.get("n_span", 1)), int(config.get("k_span", 1)))
    return (int(getattr(config, "n_span", 1)),
            int(getattr(config, "k_span", 1)))


def _totals(pipelined: "Dict[str, int]",
            scratch: "Dict[str, int]") -> "Dict[str, Any]":
    buffers = {**{name: b * PIPELINE_BUFFERS for name, b in pipelined.items()},
               **scratch}
    single = sum(pipelined.values()) + sum(scratch.values())
    return {"buffers": buffers,
            "total": sum(buffers.values()),
            "total_single": single}


# ---------------------------------------------------------------------------
# Per-family footprints (bytes resident per kernel program)
# ---------------------------------------------------------------------------

def gemm_footprint(block_m: int, block_n: int, block_k: int, *,
                   k: int, n: int, out_itemsize: int = 2,
                   quant_output: bool = False,
                   precision: str = "fp8") -> "Dict[str, Any]":
    """Grouped-GEMM per-program VMEM residency under the kernel's actual
    BlockSpecs.  The S_A/S_B scale fetches are *whole rows/blocks* per
    M-tile (shape-dependent: ``ceil(K/128)`` columns), so the footprint
    grows with K even at fixed tile geometry.  ``quant_output`` models
    the fused quantizing epilogue: the wide output tile is replaced by
    the fp8 payload + its ``(bm, bn/128)`` f32 scale tile.
    ``precision="bf16"`` models the true-bf16 kernel (``gmm_pallas_bf16``):
    2-byte operand tiles and no scale buffers at all."""
    kb = _ceil_div(k, QUANT_BLOCK)
    nb = _ceil_div(n, QUANT_BLOCK)
    if precision == "bf16":
        pipelined = {
            "a_tile": tile_bytes(block_m, block_k, 2),
            "b_tile": tile_bytes(block_k, block_n, 2),
        }
    else:
        pipelined = {
            "a_tile": tile_bytes(block_m, block_k, 1),
            "s_a_row": tile_bytes(block_m, kb, 4),
            "b_tile": tile_bytes(block_k, block_n, 1),
            "s_b_block": tile_bytes(kb, nb, 4),
        }
    if quant_output:
        pipelined["out_payload"] = tile_bytes(block_m, block_n, 1)
        pipelined["out_scales"] = tile_bytes(
            block_m, _ceil_div(block_n, QUANT_BLOCK), 4)
    else:
        pipelined["out_tile"] = tile_bytes(block_m, block_n, out_itemsize)
    scratch = {"acc_f32": tile_bytes(block_m, block_n, 4)}
    return _totals(pipelined, scratch)


def wgrad_footprint(block_m: int, block_n: int, block_k: int, *,
                    k: int, n: int, precision: str = "bf16",
                    n_span: int = 1, k_span: int = 1) -> "Dict[str, Any]":
    """Ragged-contraction (wgrad) per-program residency: x/dy operand
    tiles (bf16, or fp8 + their whole 1x128 scale rows), the f32 dw
    output block, and its accumulator scratch.  The multi-tile spans
    widen every block: one program owns a ``(k_span*bk, n_span*bn)``
    output super-tile and holds the matching ``(bm, k_span*bk)`` x and
    ``(bm, n_span*bn)`` dy operand tiles VMEM-resident across its
    sub-tiles — that residency is exactly what the wider footprint pays
    for the ``k_span``/``n_span``-fold fetch reduction."""
    fp8 = precision == "fp8"
    it = 1 if fp8 else 2
    wk = block_k * k_span
    wn = block_n * n_span
    pipelined = {
        "x_tile": tile_bytes(block_m, wk, it),
        "dy_tile": tile_bytes(block_m, wn, it),
        "dw_tile": tile_bytes(wk, wn, 4),
    }
    if fp8:
        pipelined["s_x_row"] = tile_bytes(block_m, _ceil_div(k, QUANT_BLOCK), 4)
        pipelined["s_dy_row"] = tile_bytes(block_m, _ceil_div(n, QUANT_BLOCK), 4)
    scratch = {"acc_f32": tile_bytes(wk, wn, 4)}
    return _totals(pipelined, scratch)


def quantize_footprint(block_m: int, *, k: int, m: Optional[int] = None,
                       fused: bool = False,
                       in_itemsize: Optional[int] = None) -> "Dict[str, Any]":
    """Tilewise-quantize / fused act_quant per-program residency: the
    kernels block over M only and keep whole-K rows resident.  ``fused``
    models the activation epilogue's EXTRA buffer — it reads the gate AND
    up producer outputs (two inputs) where the plain quantizer reads one.
    The kernel clamps its tile height to M (pass ``m``) exactly like
    ``act_quantize_pallas`` does."""
    if m is not None:
        block_m = min(block_m, max(8, m))
    kb = _ceil_div(k, QUANT_BLOCK)
    if in_itemsize is None:
        in_itemsize = 2 if fused else 4     # bf16 producer outputs / f32 in
    pipelined = {
        "in_rows": (2 if fused else 1) * tile_bytes(block_m, k, in_itemsize),
        "out_payload": tile_bytes(block_m, k, 1),
        "out_scales": tile_bytes(block_m, kb, 4),
    }
    return _totals(pipelined, {})


def footprint(family: str, config: Any, *, m: int, k: int, n: int,
              out_itemsize: int = 2,
              wgrad_precision: Optional[str] = None,
              gemm_precision: Optional[str] = None) -> "Dict[str, Any]":
    """Per-program VMEM footprint of ``family`` under ``config`` at shape
    ``(m, k, n)``.  ``config`` is a KernelConfig-like object or a plain
    ``{"block_m": ..}`` dict.  Returns ``{"buffers", "total",
    "total_single"}`` — ``total`` is double-buffered (the pipelined
    steady state), ``total_single`` the unpipelined floor.
    ``gemm_precision="bf16"`` selects the true-bf16 kernel's operand
    buffers; the wgrad family reads the config's multi-tile spans."""
    bm, bn, bk = config_blocks(config)
    if family in ("gemm", "gemm_quant"):
        return gemm_footprint(bm, bn, bk, k=k, n=n,
                              out_itemsize=out_itemsize,
                              quant_output=family == "gemm_quant",
                              precision=gemm_precision or "fp8")
    if family == "wgrad":
        prec = wgrad_precision
        if prec is None:
            prec = (config.get("wgrad_precision", "bf16")
                    if isinstance(config, dict)
                    else getattr(config, "wgrad_precision", "bf16"))
        ns, ks = config_spans(config)
        return wgrad_footprint(bm, bn, bk, k=k, n=n, precision=prec,
                               n_span=ns, k_span=ks)
    if family in ("quantize", "act_quant"):
        return quantize_footprint(bm, k=k, m=m, fused=family == "act_quant")
    raise ValueError(f"no footprint model for operator family {family!r}; "
                     f"modelled families: {FAMILIES}")


# ---------------------------------------------------------------------------
# Static feasibility checks (shared by the lint and the autotune pruner)
# ---------------------------------------------------------------------------

def alignment_issues(config: Any) -> "List[Tuple[str, str]]":
    """``(code, message)`` pairs for the paper's 16B/128B-analogue static
    alignment rules: sublane (block_m % 8), lane (block_n % 128), and
    scale-tile integrality (block_k % QUANT_BLOCK — a tile must cover a
    whole number of 1x128 scale columns)."""
    bm, bn, bk = config_blocks(config)
    out = []
    if bm % 8:
        out.append(("sublane", f"block_m={bm} is not a multiple of 8 "
                               f"(sublane granularity)"))
    if bn % LANE:
        out.append(("lane", f"block_n={bn} is not a multiple of {LANE} "
                            f"(lane width / fp8 payload row alignment)"))
    if bk % QUANT_BLOCK:
        out.append(("quant", f"block_k={bk} is not a multiple of "
                             f"QUANT_BLOCK={QUANT_BLOCK} — the tile would "
                             f"cover a fractional 1x128 scale column"))
    return out


def degeneracy_issues(config: Any, *, m: int, k: int, n: int,
                      elementwise: bool = False,
                      n_span: int = 1, k_span: int = 1) -> "List[str]":
    """Grid-degeneracy hazards at a concrete shape: a tile wider than the
    operand it walks (zero or fractional grid steps), or an M tile so
    tall the grid degenerates to one mostly-empty visit (``block_m >=
    2*M`` — the half-size tile covers the same rows in the same number of
    visits at half the fetch).  Elementwise kernels clamp their tile
    height to M, so only the GEMM-shaped families carry the M hazard.
    The wgrad caller passes its multi-tile spans: the grid steps by whole
    ``(k_span*bk, n_span*bn)`` super-tiles, so a span that outgrows the
    operand is degenerate even when the base tile fits."""
    bm, bn, bk = config_blocks(config)
    bn, bk = bn * n_span, bk * k_span
    span_n = f" * n_span={n_span}" if n_span > 1 else ""
    span_k = f" * k_span={k_span}" if k_span > 1 else ""
    out = []
    if elementwise:
        return out
    if n and bn > n:
        out.append(f"block_n{span_n}={bn} is wider than the operand "
                   f"(N={n}): the N grid has zero full steps")
    if k and bk > k:
        out.append(f"block_k{span_k}={bk} is wider than the operand "
                   f"(K={k}): the K grid has zero full steps")
    if m and bm >= 2 * m and bm > 8:
        out.append(f"block_m={bm} is degenerate for M={m}: one visit "
                   f"covers every row with >=50% of the fetched A rows "
                   f"(and the C flush) wasted")
    return out


def infeasible_reason(family: str, config: Any, m: int, k: int, n: int, *,
                      vmem_bytes: float,
                      wgrad_precision: Optional[str] = None,
                      gemm_precision: Optional[str] = None
                      ) -> "Optional[str]":
    """One-line reason this ``(family, config, shape)`` triple can never
    run well (or at all) on a device with ``vmem_bytes`` of VMEM, or
    ``None`` when statically feasible.  This is the pruning predicate
    ``plan.autotune`` applies before ranking/measuring candidates."""
    for code, msg in alignment_issues(config):
        return f"misaligned ({code}): {msg}"
    elementwise = family in ("quantize", "act_quant")
    ns, ks = config_spans(config) if family == "wgrad" else (1, 1)
    for msg in degeneracy_issues(config, m=m, k=k, n=n,
                                 elementwise=elementwise,
                                 n_span=ns, k_span=ks):
        return f"degenerate grid: {msg}"
    fp = footprint(family, config, m=m, k=k, n=n,
                   wgrad_precision=wgrad_precision,
                   gemm_precision=gemm_precision)
    if fp["total"] > vmem_bytes:
        return (f"VMEM footprint {fp['total']} B (double-buffered) exceeds "
                f"the {int(vmem_bytes)} B budget")
    return None
