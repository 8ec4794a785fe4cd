"""TilePlan subsystem: plan-once/run-many grouped GEMM configuration.

The paper's core mechanism is a *preconfigured descriptor pool* with cheap
runtime selection (log2(block_M) TMA descriptors, Eq. 2): configure
expensive launch state once, select per launch.  This module is the
repo-wide analogue, split into three pieces:

``KernelConfig``
    One frozen record of every tile-shape decision (``block_m/n/k``), the
    dispatch backend, and the output dtype.  It replaces the loose
    ``block_m=128``-style kwargs that used to be scattered across
    ``dispatch.py``, ``core/``, models, serve, and benchmarks — tile
    shapes are a first-class tuned artifact, not folklore constants.
    Static alignment constraints are validated at construction; the
    shape-dependent ones via :meth:`KernelConfig.validate`.

``TilePlan``
    The visitation schedule (``group_offsets/group_ids/m_tile_ids``) the
    padding-free kernel walks — the descriptor-selection analogue.  It
    depends only on ``(group_sizes, m, block_m)``: *not* on K, N, or the
    weight operand.  One MoE layer application therefore builds it once
    per routing decision and reuses it across every GEMM that shares the
    same ``group_sizes`` — gate/up/down forward and the dgrads in the
    custom VJP (the transposed-N plan is the same plan, for free).

Pool autotuner
    ``CONFIG_POOL`` is a small pool of candidate configs (the descriptor
    pool analogue), ranked by a roofline cost model seeded from the
    ``benchmarks/roofline.py`` device table, then measured on the live
    backend.  Selections persist to a JSON cache keyed by
    ``(device kind, backend, M-bucket, K, N, G)`` so the measurement runs
    once per shape class per machine.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import logging
import os
import time
from typing import Any, Iterable, Optional

import jax
import jax.numpy as jnp

from repro.analysis import events as _events
from repro.kernels import resources as _resources

logger = logging.getLogger("repro.plan")

QUANT_BLOCK = 128  # the paper's 1x128 / 128x128 quantization granularity


# ---------------------------------------------------------------------------
# KernelConfig
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class KernelConfig:
    """Frozen tile-shape + backend + out-dtype descriptor for one grouped
    GEMM.  Hashable, so it can ride through ``jax.jit`` static args and
    ``custom_vjp`` nondiff args."""

    block_m: int = 128
    block_n: int = 128
    block_k: int = 128
    backend: Optional[str] = None      # dispatch registry name; None = auto
    # None = the call site decides (grouped_linear uses x.dtype, the raw
    # dispatch entry bf16); pin a dtype to override every consumer
    out_dtype: Any = None
    # operand precision of the training step's wgrad GEMM: "bf16" (the
    # DeepSeek recipe — wgrad keeps the highest-precision operands) or
    # "fp8" (arXiv 2505.20524's all-fp8 step: x and dy arrive as fp8 with
    # their 1x128 tile scales, dequantized per visit inside the kernel)
    wgrad_precision: str = "bf16"
    # route the fp8 FFN's gate/up GEMMs through the quantizing-epilogue
    # producer (``op="gemm_quant"``): the GEMMs emit fp8 + 1x128 scales
    # directly and the activation epilogue dequantizes on load, so the
    # bf16 g/u intermediates never exist.  Off by default — the fused
    # recipe quantizes g/u once more than the bf16-residual recipe, an
    # e4m3-relative-error tolerance delta (see core.grouped_gemm)
    fuse_producer: bool = False
    # multi-tile wgrad spans: one grid cell of the wgrad kernel owns an
    # (k_span*block_k, n_span*block_n) output super-tile, so the x operand
    # tile is fetched once per n_span N steps and the dy tile once per
    # k_span K steps (VMEM-resident reuse).  Only the wgrad family reads
    # these; every other op treats a span>1 config as its base block shape
    n_span: int = 1
    k_span: int = 1

    def __post_init__(self):
        # normalize out_dtype so configs built from jnp scalar types and
        # from the JSON cache (dtype names) are identical under ==/hash
        # (they ride through jit static args — a hash split compiles twice)
        if self.out_dtype is not None:
            object.__setattr__(self, "out_dtype", jnp.dtype(self.out_dtype))
        # static (shape-independent) constraints — TPU-adapted analogue of
        # the paper's block_N % 64 bookkeeping (§2.3)
        if self.block_m % 8 != 0:
            raise ValueError(
                f"block_m must be a multiple of 8 (sublane), got {self.block_m}")
        if self.block_n % 128 != 0:
            raise ValueError(
                f"block_n must be a multiple of 128 (lane width), got {self.block_n}")
        if self.block_k % QUANT_BLOCK != 0:
            raise ValueError(
                f"block_k must be a multiple of {QUANT_BLOCK}, got {self.block_k}")
        if self.wgrad_precision not in ("bf16", "fp8"):
            raise ValueError(
                f"wgrad_precision must be 'bf16' or 'fp8', "
                f"got {self.wgrad_precision!r}")
        for axis in ("n_span", "k_span"):
            v = getattr(self, axis)
            if not isinstance(v, int) or isinstance(v, bool) or v < 1:
                raise ValueError(f"{axis} must be an int >= 1, got {v!r}")

    def validate(self, m: int, k: int, n: int, *,
                 family: str = "gemm") -> "KernelConfig":
        """Shape-dependent constraints.  M is deliberately unconstrained —
        handling arbitrary (ragged) M without padding is the point of the
        paper.

        Beyond divisibility, the static resource model budget-checks the
        per-program VMEM footprint for ``family`` against the current
        device, so an explicitly infeasible config raises here with the
        computed footprint instead of surfacing as an opaque Mosaic
        allocation error at compile time."""
        eff_k, eff_n = self.effective_blocks(family)
        if k % eff_k != 0:
            raise ValueError(
                f"K={k} must be a multiple of block_k={self.block_k}"
                + (f" * k_span={self.k_span}" if eff_k != self.block_k else ""))
        if n % eff_n != 0:
            raise ValueError(
                f"N={n} must be a multiple of block_n={self.block_n}"
                + (f" * n_span={self.n_span}" if eff_n != self.block_n else ""))
        if family in _resources.FAMILIES:
            budget = device_spec().vmem_bytes
            fp = _resources.footprint(family, self, m=m, k=k, n=n,
                                      wgrad_precision=self.wgrad_precision)
            if fp["total_single"] > budget:
                raise ValueError(
                    f"{family} config (block_m={self.block_m}, "
                    f"block_n={self.block_n}, block_k={self.block_k}) needs "
                    f"{fp['total_single']} B of VMEM per program at "
                    f"M={m}, K={k}, N={n} — over the {budget} B device "
                    f"budget even single-buffered (buffers: {fp['buffers']})")
        return self

    def effective_blocks(self, family: str = "gemm") -> "tuple[int, int]":
        """(K, N) divisibility units for ``family``: the wgrad grid steps
        by whole (k_span*block_k, n_span*block_n) super-tiles; every other
        family ignores the spans."""
        if family == "wgrad":
            return self.block_k * self.k_span, self.block_n * self.n_span
        return self.block_k, self.block_n

    def compatible(self, k: int, n: int, family: str = "gemm") -> bool:
        eff_k, eff_n = self.effective_blocks(family)
        return k % eff_k == 0 and n % eff_n == 0

    def with_(self, **kw) -> "KernelConfig":
        return dataclasses.replace(self, **kw)

    # ---- (de)serialization for the autotune cache ----------------------
    def to_dict(self) -> dict:
        return {"block_m": self.block_m, "block_n": self.block_n,
                "block_k": self.block_k, "backend": self.backend,
                "out_dtype": (None if self.out_dtype is None
                              else jnp.dtype(self.out_dtype).name),
                "wgrad_precision": self.wgrad_precision,
                "fuse_producer": self.fuse_producer,
                "n_span": self.n_span, "k_span": self.k_span}

    @classmethod
    def from_dict(cls, d: dict) -> "KernelConfig":
        name = d.get("out_dtype")
        return cls(block_m=int(d["block_m"]), block_n=int(d["block_n"]),
                   block_k=int(d["block_k"]), backend=d.get("backend"),
                   out_dtype=None if name is None else jnp.dtype(name),
                   wgrad_precision=d.get("wgrad_precision", "bf16"),
                   fuse_producer=bool(d.get("fuse_producer", False)),
                   n_span=int(d.get("n_span", 1)),
                   k_span=int(d.get("k_span", 1)))

    @classmethod
    def default(cls, device_kind: Optional[str] = None) -> "KernelConfig":
        """Per-device default tile shape (untuned seed of the pool)."""
        kind = (device_kind or _device_kind()).lower()
        for prefix, cfg_kw in _DEVICE_DEFAULTS:
            if kind.startswith(prefix):
                return cls(**cfg_kw)
        return cls()


# per-device default block shapes, first prefix match wins.  v5e has half
# the VMEM of v4/v5p, so the default stays at one 128x128 output tile;
# larger parts get a taller M tile to amortize B traffic.
_DEVICE_DEFAULTS = (
    ("tpu v5 lite", dict(block_m=128)),
    ("tpu v5e", dict(block_m=128)),
    ("tpu", dict(block_m=256)),
    ("cpu", dict(block_m=128)),
)


def _device_kind() -> str:
    return jax.devices()[0].device_kind


# ---------------------------------------------------------------------------
# Default-config seam (serve/train thread a tuned config through here)
# ---------------------------------------------------------------------------

_default_config: Optional[KernelConfig] = None


def set_default_config(config: Optional[KernelConfig]) -> None:
    """Install the config that ``config=None`` call sites resolve to.

    TRACE-TIME semantics: the default is read while a function is being
    traced, so it does not affect already-jitted traces (the seam is not
    part of any jit cache key).  Install it *before* the first call of a
    jitted function — or thread the config explicitly as trainer
    (``make_train_step(kernel_config=...)``) and serve
    (``Engine(kernel_config=...)``) do, which re-trace by construction.
    """
    global _default_config
    _default_config = config


def get_default_config() -> KernelConfig:
    return _default_config if _default_config is not None \
        else KernelConfig.default()


def pinned_default() -> Optional[KernelConfig]:
    """The explicitly installed default, or None when unset — callers that
    would otherwise *tune* (benchmarks) check this to honour a pin."""
    return _default_config


@contextlib.contextmanager
def default_config(config: Optional[KernelConfig]):
    """Scoped :func:`set_default_config` (trainer wraps loss tracing)."""
    global _default_config
    prev = _default_config
    _default_config = config
    try:
        yield
    finally:
        _default_config = prev


def resolve_config(config: Optional[KernelConfig] = None, *,
                   backend: Optional[str] = None,
                   out_dtype: Any = None,
                   wgrad_precision: Optional[str] = None) -> KernelConfig:
    """Effective config for a call site: explicit ``config`` >
    installed default > per-device default, with per-call ``backend`` /
    ``out_dtype`` / ``wgrad_precision`` overrides applied on top."""
    cfg = config if config is not None else get_default_config()
    if backend is not None:
        # an explicit "auto" escapes a pinned concrete backend back to
        # auto-resolution (None is the config's backend field spelling)
        cfg = cfg.with_(backend=None if backend == "auto" else backend)
    if out_dtype is not None:
        cfg = cfg.with_(out_dtype=out_dtype)
    if wgrad_precision is not None:
        cfg = cfg.with_(wgrad_precision=wgrad_precision)
    return cfg


# ---------------------------------------------------------------------------
# Group metadata (descriptor selection, Eq. 2) and TilePlan
# ---------------------------------------------------------------------------

def make_group_metadata(group_sizes: jax.Array, m: int, block_m: int,
                        num_groups: int):
    """Device-side visitation schedule — the analogue of the paper's
    runtime descriptor selection (Eq. 2).

    Returns (group_offsets[G+1], group_ids[T], m_tile_ids[T]) where
    T = ceil(m/block_m) + num_groups - 1 is the static worst-case visit
    count: every tile is visited once, plus one extra visit per group
    boundary that splits a tile.

    Padding visits (t >= num_real) sweep the *tail tiles* — the output
    tiles entirely beyond ``sum(group_sizes)`` that no group owns — so the
    kernel's store can zero-fill every unowned row (rows in
    ``[sum(group_sizes), m)`` are DEFINED zeros, not garbage; the fp8
    backward's ``dx`` tail feeds a scatter-add and must not pollute real
    token gradients).  The worst-case visit count always suffices: the
    number of unused padding visits, ``T - num_real``, is at least
    ``num_tiles - ceil(total/block_m)``, the tail-tile count.  When there
    is no tail, padding visits clamp to the last real (group, tile) visit
    and redo an identical masked write — idempotent (the paper's "safe
    overlapping write").  Consumers that *accumulate* per visit instead of
    storing (the wgrad kernel) must therefore skip duplicate visits:
    ``(group_ids[t], m_tile_ids[t]) == (group_ids[t-1], m_tile_ids[t-1])``
    identifies them.

    When every group is empty (``num_real == 0``) every visit is a padding
    visit pinned to group 0; the sweep covers all tiles and the kernel
    zero-fills the whole buffer (``gmm_pallas`` still short-circuits to
    ``jnp.zeros`` to skip the launch).
    """
    # one event per schedule build: the plan-once/run-many contract
    # (REPRO-C02) counts these at trace time
    _events.emit("plan_build", m=m, block_m=block_m, num_groups=num_groups)
    group_sizes = group_sizes.astype(jnp.int32)
    group_offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(group_sizes)])
    starts = group_offsets[:-1]
    ends = group_offsets[1:]
    first_tile = starts // block_m
    last_tile_excl = (ends + block_m - 1) // block_m
    tiles_per = jnp.maximum(last_tile_excl - first_tile, 0)
    # zero-size groups get zero visits (even when their offset is unaligned)
    tiles_per = jnp.where(group_sizes == 0, 0, tiles_per)

    num_tiles = (m + block_m - 1) // block_m
    max_visits = max(num_tiles + num_groups - 1, 1)

    visit_ends = jnp.cumsum(tiles_per)            # [G]
    t = jnp.arange(max_visits, dtype=jnp.int32)
    # group that owns visit t (padding visits keep the last real group's
    # id — its row range never intersects a tail tile, so their masked
    # store owns no rows).  num_real == 0 would clamp to -1 and feed
    # searchsorted garbage — pin those schedules to group 0 (empty range).
    num_real = visit_ends[-1]
    t_clamped = jnp.maximum(jnp.minimum(t, num_real - 1), 0)
    group_ids = jnp.searchsorted(visit_ends, t_clamped, side="right")
    group_ids = jnp.minimum(group_ids, num_groups - 1).astype(jnp.int32)
    visits_before = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), visit_ends[:-1]])
    m_tile_ids = (first_tile[group_ids]
                  + (t_clamped - visits_before[group_ids])).astype(jnp.int32)
    m_tile_ids = jnp.clip(m_tile_ids, 0, max(num_tiles - 1, 0))
    # padding visits sweep the tail tiles (entirely beyond sum(sizes)) so
    # the kernel zero-fills them; with no tail they clamp to the last real
    # tile and redo its idempotent masked write (see docstring)
    total = ends[-1]
    last_real_tile = (total + block_m - 1) // block_m - 1      # -1 if total==0
    pad_tile = jnp.minimum(last_real_tile + 1 + (t - num_real),
                           max(num_tiles - 1, 0))
    m_tile_ids = jnp.where(t >= num_real,
                           jnp.maximum(pad_tile, 0).astype(jnp.int32),
                           m_tile_ids)
    group_ids = jnp.where(num_real == 0, 0, group_ids)
    return group_offsets, group_ids, m_tile_ids


@dataclasses.dataclass(frozen=True)
class TilePlan:
    """Precomputed grouped-GEMM schedule, reusable across every GEMM that
    shares the same ``group_sizes`` (M-side raggedness): gate/up/down
    forward GEMMs of one MoE application and the dgrads of its backward.
    A registered pytree, so it flows through ``jit`` and ``custom_vjp``
    residuals.

    CONTRACT: a plan is only valid for the exact ``group_sizes`` it was
    built from.  The static fields (m, block_m, num_groups) are checked
    at use; the offsets/ids are traced values that consumers trust
    without re-deriving (that is the point of plan-once/run-many — the
    same trade the paper's preconfigured descriptors make).  Passing a
    plan from a *different* routing decision that happens to share the
    static shape produces silently wrong output: never cache plans
    across routing decisions.
    """
    group_offsets: jax.Array   # [G+1] int32 row offsets (cumsum of sizes)
    group_ids: jax.Array       # [T]   int32 visit -> group
    m_tile_ids: jax.Array      # [T]   int32 visit -> output M tile
    m: int                     # static row count of the (capacity) buffer
    block_m: int
    num_groups: int

    @property
    def num_tiles(self) -> int:
        return (self.m + self.block_m - 1) // self.block_m

    @property
    def max_visits(self) -> int:
        return max(self.num_tiles + self.num_groups - 1, 1)

    def total_rows(self) -> jax.Array:
        """Traced sum of group sizes (rows the kernel actually owns)."""
        return self.group_offsets[-1]

    def check_against(self, m: int, block_m: int, num_groups: int) -> None:
        if (self.m, self.block_m, self.num_groups) != (m, block_m, num_groups):
            raise ValueError(
                f"TilePlan built for (m={self.m}, block_m={self.block_m}, "
                f"num_groups={self.num_groups}) used with (m={m}, "
                f"block_m={block_m}, num_groups={num_groups}); rebuild the "
                f"plan or pass a matching KernelConfig")


def _tile_plan_flatten(p: TilePlan):
    return ((p.group_offsets, p.group_ids, p.m_tile_ids),
            (p.m, p.block_m, p.num_groups))


def _tile_plan_unflatten(aux, children):
    return TilePlan(*children, *aux)


jax.tree_util.register_pytree_node(TilePlan, _tile_plan_flatten,
                                   _tile_plan_unflatten)


def make_tile_plan(group_sizes: jax.Array, m: int, *,
                   config: Optional[KernelConfig] = None,
                   block_m: Optional[int] = None,
                   num_groups: Optional[int] = None) -> TilePlan:
    """Build the visitation schedule once per routing decision."""
    if block_m is None:
        block_m = (config or get_default_config()).block_m
    num_groups = num_groups if num_groups is not None else group_sizes.shape[0]
    offsets, group_ids, m_tile_ids = make_group_metadata(
        group_sizes, m, block_m, num_groups)
    return TilePlan(offsets, group_ids, m_tile_ids, m=int(m),
                    block_m=int(block_m), num_groups=int(num_groups))


# ---------------------------------------------------------------------------
# PlanCache: serve every static plan shape once
# ---------------------------------------------------------------------------

class PlanCache:
    """Serves every *static* plan shape exactly once.

    A :class:`TilePlan`'s arrays depend on the ``group_sizes`` data, so
    the plan itself cannot be cached across calls — but the plan
    *builder* can: for one static key ``(m, block_m, num_groups,
    group_sizes dtype, device)`` the schedule derivation traces once and
    every later call (same static shape, new sizes) replays the compiled
    builder.  Eager call sites that used to re-derive the schedule per
    call — ``padded_baseline``'s block-aligned inner GEMM, a serving
    loop's per-step plans — pay the metadata math once per shape class,
    the same trade the paper's preconfigured descriptor pool makes.

    ``builds`` counts builder compilations (the regression surface for
    "two calls with the same static shape build exactly one plan").
    """

    def __init__(self):
        self._builders: "dict[tuple, Any]" = {}
        self.builds = 0

    def clear(self) -> None:
        self._builders.clear()
        self.builds = 0

    def get(self, group_sizes: jax.Array, m: int, *,
            block_m: Optional[int] = None,
            num_groups: Optional[int] = None) -> TilePlan:
        if block_m is None:
            block_m = get_default_config().block_m
        if num_groups is None:
            num_groups = group_sizes.shape[0]
        key = (int(m), int(block_m), int(num_groups),
               jnp.dtype(group_sizes.dtype).name, _device_kind())
        builder = self._builders.get(key)
        if builder is None:
            self.builds += 1

            def build(gs, _m=int(m), _bm=int(block_m), _g=int(num_groups)):
                return make_tile_plan(gs, _m, block_m=_bm, num_groups=_g)

            builder = jax.jit(build)
            self._builders[key] = builder
        return builder(group_sizes)


#: process-wide instance — cached plans sit beside the autotune entries as
#: the other per-shape-class artifact
PLAN_CACHE = PlanCache()


def shared_plan(group_sizes: jax.Array, m: int, *,
                block_m: Optional[int] = None,
                num_groups: Optional[int] = None) -> TilePlan:
    """Build (or replay) a :class:`TilePlan` through the process-wide
    :data:`PLAN_CACHE`."""
    return PLAN_CACHE.get(group_sizes, m, block_m=block_m,
                          num_groups=num_groups)


# ---------------------------------------------------------------------------
# Block-shape pool (the descriptor-pool analogue)
# ---------------------------------------------------------------------------

# block_m sweeps the paper's log2 descriptor axis; the (block_n, block_k)
# cross stays small — one 128-lane output tile or a double-wide variant.
# ONE pool serves every autotune op family (the keys of ``_AUTOTUNE_OPS``
# below — gemm/decode/wgrad/wgrad_fp8/quantize/act_quant/gemm_quant, i.e.
# the registry-derived family list, not a hardcoded enumeration): each op
# ranks the same candidates by its own roofline terms and caches the
# winner under its own key.
#
# The decode-specialized entries (block_m=8/16) extend the descriptor axis
# down to serving's tiny-M regime: a decode step's grouped GEMM has
# M = batch*top_k rows TOTAL, so a 128-row tile wastes >=87% of its
# fetched A rows and C flush.  The MXU-occupancy term in the cost model
# (``_eff_rows``) keeps these entries from ever ranking at training
# shapes: below 128 rows the compute time per visit is flat, so shrinking
# block_m only buys anything when it cuts *memory* traffic — i.e. when M
# itself is tiny.
DECODE_BLOCK_MS = (8, 16)
DECODE_POOL: "tuple[KernelConfig, ...]" = tuple(
    KernelConfig(block_m=bm) for bm in DECODE_BLOCK_MS)
# multi-tile wgrad span axis: same 128x128 base tile, but one grid cell
# owns a (k_span*128, n_span*128) output super-tile so the x operand tile
# is fetched once per n_span N steps and dy once per k_span K steps
# (kernels/wgrad_kernel.py).  Only the wgrad family reads the spans —
# autotune for every other op drops the span>1 entries up front, so the
# shared pool stays one namespace.  The axis stops at 4: span 8's
# (1024, 1024) f32 super-tile accumulator alone would blow the v5e VMEM
# budget the resource model proves entries against (REPRO-V01).
WGRAD_SPANS = (2, 4)
CONFIG_POOL: "tuple[KernelConfig, ...]" = DECODE_POOL + tuple(
    KernelConfig(block_m=bm, block_n=bn, block_k=bk)
    for bm in (64, 128, 256, 512)
    for bn, bk in ((128, 128), (256, 128))
) + tuple(
    KernelConfig(block_m=bm, n_span=s, k_span=s)
    for bm in (128, 256, 512)
    for s in WGRAD_SPANS
)


def candidate_pool(k: int, n: int,
                   pool: Optional[Iterable[KernelConfig]] = None,
                   require_transposable: bool = True,
                   family: str = "gemm"
                   ) -> "tuple[KernelConfig, ...]":
    """Pool entries legal for this (K, N) — never empty for 128-aligned
    shapes; falls back to the per-device default otherwise.

    ``require_transposable`` (default) additionally demands legality for
    the transposed (N, K) orientation: the fp8 custom VJP runs the dgrad
    through the same config against ``w^T``, so a forward-only-legal
    selection would crash every training step's backward.

    ``family`` feeds span-aware divisibility: for ``"wgrad"`` an entry
    must divide (K, N) by its whole (k_span*block_k, n_span*block_n)
    super-tile, so e.g. the span-4 entries drop out at K=256.
    """
    def legal(c):
        return c.compatible(k, n, family) and (
            not require_transposable or c.compatible(n, k, family))

    cands = tuple(c for c in (tuple(pool) if pool is not None else CONFIG_POOL)
                  if legal(c))
    if not cands:
        d = KernelConfig.default()
        cands = (d,) if legal(d) else ()
    return cands


# ---------------------------------------------------------------------------
# Roofline cost model (seeded from benchmarks/roofline.py device numbers)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DeviceSpec:
    name: str
    peak_flops: float      # bf16 MXU (or SIMD) FLOP/s
    hbm_bw: float          # bytes/s
    hbm_bytes: float       # per-chip capacity (roofline "fits" column)
    # per-core VMEM budget the static resource model proves tile configs
    # against (kernels/resources.py owns the numbers; the "cpu" entry
    # carries the tightest real-TPU budget so interpret-mode selections
    # transfer to hardware)
    vmem_bytes: int = _resources.VMEM_BYTES["cpu"]


DEVICE_SPECS = {
    "tpu v5e": DeviceSpec("tpu v5e", peak_flops=1.97e14, hbm_bw=8.2e11,
                          hbm_bytes=16e9,
                          vmem_bytes=_resources.VMEM_BYTES["tpu v5e"]),
    "tpu": DeviceSpec("tpu", peak_flops=2.75e14, hbm_bw=1.2e12,
                      hbm_bytes=32e9,
                      vmem_bytes=_resources.VMEM_BYTES["tpu"]),
    "cpu": DeviceSpec("cpu", peak_flops=2e11, hbm_bw=5e10, hbm_bytes=64e9,
                      vmem_bytes=_resources.VMEM_BYTES["cpu"]),
}


def device_spec(device_kind: Optional[str] = None) -> DeviceSpec:
    kind = (device_kind or _device_kind()).lower()
    # real v5e hardware reports device_kind "TPU v5 lite"
    if kind.startswith(("tpu v5 lite", "tpu v5e")):
        return DEVICE_SPECS["tpu v5e"]
    for prefix in ("tpu", "cpu"):
        if kind.startswith(prefix):
            return DEVICE_SPECS[prefix]
    raise ValueError(f"no device spec for device kind {kind!r}; "
                     f"known: {sorted(DEVICE_SPECS)}")


# the MXU processes a full 128-row pass regardless of how few rows a tile
# holds: compute time per visit is flat below this granularity, so the
# cost model charges tiles their *occupied* MXU rows — the term that
# confines the decode entries (block_m=8/16) to the tiny-M regime where
# their memory-traffic savings are real
MXU_M = 128


def _eff_rows(block_m: int) -> int:
    return -(-block_m // MXU_M) * MXU_M


def estimate_cost_s(m: int, k: int, n: int, g: int, config: KernelConfig,
                    spec: Optional[DeviceSpec] = None,
                    quant_output: bool = False,
                    precision: str = "fp8") -> float:
    """Roofline estimate of one grouped GEMM under ``config``: max of the
    compute and memory terms, with the visit-inflation the plan implies
    (worst case: every group boundary splits a tile, +G-1 visits).
    Compute charges MXU occupancy (``_eff_rows``): a sub-128-row tile
    takes a full MXU pass; memory charges the bytes actually moved.

    ``quant_output`` models the quantizing-epilogue variant
    (``op="gemm_quant"``): the bf16 C flush is replaced by the fp8
    payload + f32 1x128 scale rows — half the output bytes, same
    compute.  ``precision="bf16"`` models the true-bf16 kernel
    (``op="gemm_bf16"``): 2-byte operands, no scale-row traffic."""
    spec = spec or device_spec()
    bm, bn = config.block_m, config.block_n
    num_tiles = -(-m // bm)
    visits = num_tiles + max(g - 1, 0)
    n_steps = -(-n // bn)
    kb = -(-k // QUANT_BLOCK)
    nb = -(-n // QUANT_BLOCK)
    # every visit computes a full (bm, k) x (k, n) tile row
    flops = 2.0 * visits * _eff_rows(bm) * k * n
    if precision == "bf16":
        a_bytes = visits * n_steps * bm * k * 2        # bf16 A, no scales
        b_bytes = visits * k * n * 2                   # bf16 B per visit
    else:
        a_bytes = visits * n_steps * bm * (k + 4 * kb)  # fp8 A + f32 S_A
        b_bytes = visits * k * n                        # fp8 B per visit
    if quant_output:
        c_bytes = num_tiles * bm * (n + 4 * nb)        # fp8 C + f32 scales
    else:
        c_bytes = num_tiles * bm * n * 2               # bf16 C flush
    return max(flops / spec.peak_flops,
               (a_bytes + b_bytes + c_bytes) / spec.hbm_bw)


def wgrad_operand_bytes(m: int, k: int, n: int, g: int,
                        config: KernelConfig,
                        precision: str = "bf16") -> int:
    """Modeled operand HBM bytes of one wgrad pass (x + dy fetches; the
    dw flush is schedule-independent and excluded).  This is the traffic
    model the multi-tile schedule exists to shrink:

    * single-tile (``n_span = k_span = 1``): each visit walks every
      (k, n) grid cell, so per visit the operands cost
      ``kn_steps * (bm*bk + bm*bn)`` elements — x is re-fetched from HBM
      on every N step and dy on every K step.
    * multi-tile: one grid cell owns a ``(k_span*bk, n_span*bn)`` output
      super-tile, the x tile stays VMEM-resident across its n_span N
      steps and dy across its k_span K steps, so per visit the operands
      cost ``ceil(n_steps/n_span) * bm*k + ceil(k_steps/k_span) * bm*n``
      elements — at full span this is the ideal ``k*bm + n*bm``, one
      fetch of each operand tile per visit.

    With ``precision="fp8"`` the payloads are 1-byte and each grid cell
    additionally fetches the whole f32 1x128 scale rows for its tiles."""
    bm = config.block_m
    visits = -(-m // bm) + max(g - 1, 0)
    k_steps = -(-k // config.block_k)
    n_steps = -(-n // config.block_n)
    k_groups = -(-k_steps // config.k_span)
    n_groups = -(-n_steps // config.n_span)
    if precision == "fp8":
        kb = -(-k // QUANT_BLOCK)
        nb = -(-n // QUANT_BLOCK)
        x_bytes = visits * n_groups * bm * k              # fp8 payload
        dy_bytes = visits * k_groups * bm * n
        scale_bytes = visits * k_groups * n_groups * bm * 4 * (kb + nb)
        return int(x_bytes + dy_bytes + scale_bytes)
    x_bytes = visits * n_groups * bm * k * 2              # bf16 payload
    dy_bytes = visits * k_groups * bm * n * 2
    return int(x_bytes + dy_bytes)


def estimate_cost_s_wgrad(m: int, k: int, n: int, g: int,
                          config: KernelConfig,
                          spec: Optional[DeviceSpec] = None,
                          precision: str = "bf16") -> float:
    """Roofline estimate of the ragged-contraction (wgrad) grouped GEMM
    ``dw[g] = x_g^T @ dy_g`` under ``config``.  Same visit inflation as the
    forward (the contraction walks the same M-tile schedule); operand
    traffic is :func:`wgrad_operand_bytes` — per visit the old single-tile
    schedule moves ``kn_steps*(bm*bk + bm*bn)`` operand elements while a
    full-span multi-tile schedule moves ``k*bm + n*bm`` — and the dense
    ``[G, K, N]`` f32 output flushes once per group.  The memory term is
    what shrinks with wider spans, so on memory-bound wgrad shapes the
    model prefers the widest span that divides the shape and fits VMEM
    (the resource model prunes the rest); on compute-bound shapes the
    span axis is cost-neutral and measurement arbitrates.  With
    ``precision="fp8"`` the operands are 1-byte fp8 plus their f32 1x128
    tile-scale rows (over-fetched whole per grid cell, like the
    forward)."""
    spec = spec or device_spec()
    bm = config.block_m
    visits = -(-m // bm) + max(g - 1, 0)
    flops = 2.0 * visits * _eff_rows(bm) * k * n
    operand_bytes = wgrad_operand_bytes(m, k, n, g, config,
                                        precision=precision)
    dw_bytes = g * k * n * 4                             # f32 dw flush
    return max(flops / spec.peak_flops,
               (operand_bytes + dw_bytes) / spec.hbm_bw)


def estimate_cost_s_quantize(m: int, k: int, config: KernelConfig,
                             spec: Optional[DeviceSpec] = None) -> float:
    """Roofline estimate of one 1x128 tilewise quantization pass under
    ``config`` (the kernel's tile height is ``block_m``).  The pass is
    memory-bound and its traffic is tile-height-independent (read the
    f32 payload, write fp8 + f32 scale rows); the grid term models
    per-tile dispatch overhead, so the model ranks taller tiles first and
    live measurement arbitrates the rest — exactly the split the GEMM
    families use for their tile-free backends."""
    spec = spec or device_spec()
    tiles = -(-m // config.block_m)
    kb = -(-k // QUANT_BLOCK)
    bytes_moved = m * k * 4 + m * k * 1 + m * kb * 4
    return bytes_moved / spec.hbm_bw + tiles * 1e-6


def estimate_cost_s_act_quant(m: int, k: int, config: KernelConfig,
                              spec: Optional[DeviceSpec] = None) -> float:
    """Roofline estimate of one fused activation->quantize epilogue pass
    (``op="act_quant"``): reads the gate AND up GEMM outputs (bf16),
    writes fp8 payload + f32 scale rows — ~3x fewer HBM bytes for the
    intermediate than the unfused write-h/read-h/write-q sequence.  Same
    model split as :func:`estimate_cost_s_quantize`: traffic is
    tile-height-independent, the grid term ranks taller tiles first,
    measurement arbitrates."""
    spec = spec or device_spec()
    tiles = -(-m // config.block_m)
    kb = -(-k // QUANT_BLOCK)
    bytes_moved = 2 * m * k * 2 + m * k * 1 + m * kb * 4
    return bytes_moved / spec.hbm_bw + tiles * 1e-6


# ---------------------------------------------------------------------------
# Persistent autotune cache
# ---------------------------------------------------------------------------

_CACHE_VERSION = 1
_cache_mem: "dict[str, dict[str, dict]]" = {}   # path -> entries


def default_cache_path() -> str:
    return os.environ.get(
        "REPRO_TILEPLAN_CACHE",
        os.path.join(os.path.expanduser("~"), ".cache", "repro",
                     "tileplan_cache.json"))


def _m_bucket(m: int) -> int:
    """Paper-flavoured log2 bucketing: shapes in the same power-of-two M
    band share a tuned config."""
    b = 1
    while b < max(m, 1):
        b *= 2
    return b


def cache_key(device_kind: str, backend: str, m: int, k: int, n: int,
              g: int, op: str = "gemm") -> str:
    """Cache key for one (device, backend, shape-class, op) selection.

    ``op`` is any key of :data:`_AUTOTUNE_OPS` — the registry-derived
    family list (currently gemm, decode, wgrad, wgrad_fp8, quantize,
    act_quant, gemm_quant; new dispatch families join by adding an entry
    there, never by editing this function).  The non-default ops append
    ``|<op>``.

    Every key is additionally namespaced by the static resource model's
    version (``|rm<N>``): pool selections made under an older footprint
    model — in particular any selection from before static feasibility
    pruning existed — must be re-tuned, not trusted.  Old-format entries
    in an existing cache file simply never match (and are preserved on
    save), so stale caches are ignored rather than crashed on.
    """
    suffix = "" if op == "gemm" else f"|{op}"
    return (f"{device_kind}|{backend}|M{_m_bucket(m)}|K{k}|N{n}|G{g}{suffix}"
            f"|rm{_resources.RESOURCE_MODEL_VERSION}")


def _read_cache_file(path: str) -> "dict[str, dict]":
    try:
        with open(path) as f:
            raw = json.load(f)
        if raw.get("version") == _CACHE_VERSION:
            return dict(raw.get("entries", {}))
    except (OSError, ValueError):
        pass
    return {}


def load_cache(path: Optional[str] = None) -> "dict[str, dict]":
    path = path or default_cache_path()
    if path not in _cache_mem:
        _cache_mem[path] = _read_cache_file(path)
    return _cache_mem[path]


def save_cache(entries: "dict[str, dict]",
               path: Optional[str] = None) -> None:
    path = path or default_cache_path()
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    # merge with whatever is on disk *now* — concurrent processes tuning
    # different shapes must not drop each other's (expensive, measured)
    # entries; ours win on key collisions
    merged = {**_read_cache_file(path), **entries}
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump({"version": _CACHE_VERSION, "entries": merged}, f,
                  indent=1, sort_keys=True)
    os.replace(tmp, path)
    _cache_mem[path] = merged


def clear_cache_memo() -> None:
    """Drop the in-process cache view (tests; does not touch the file)."""
    _cache_mem.clear()


# ---------------------------------------------------------------------------
# Autotuner: measured pool selection on the live backend
# ---------------------------------------------------------------------------

# autotune op family -> dispatch OpKey.  THE authoritative family list:
# cache_key suffixes, candidate legality, and the cost-model switch in
# autotune() all derive from these keys — a new dispatch family plugs in
# by adding one entry (+ a _measure_candidate branch), nothing else.
_AUTOTUNE_OPS = {
    "gemm": ("gemm", "fp8"),
    "gemm_bf16": ("gemm", "bf16"),   # true bf16 Pallas baseline kernel
    "decode": ("gemm", "fp8"),       # tiny-M serving shapes, decode pool
    "gemm_quant": ("gemm_quant", "fp8"),  # fused quantizing epilogue
    "wgrad": ("wgrad", "bf16"),
    "wgrad_fp8": ("wgrad", "fp8"),
    "quantize": ("quantize", "fp8"),
    "act_quant": ("act_quant", "fp8"),
}

# autotune op -> (resource-model family, operand precision) for the
# static feasibility pruning pass.  The precision slot feeds
# ``wgrad_precision`` for the wgrad family (scale-row buffers) and
# ``gemm_precision`` for the gemm family (bf16 = 2-byte operand tiles,
# no scale buffers); None means the family's fp8 default footprint.
_RESOURCE_FAMILIES = {
    "gemm": ("gemm", None),
    "gemm_bf16": ("gemm", "bf16"),
    "decode": ("gemm", None),
    "gemm_quant": ("gemm_quant", None),
    "wgrad": ("wgrad", "bf16"),
    "wgrad_fp8": ("wgrad", "fp8"),
    "quantize": ("quantize", None),
    "act_quant": ("act_quant", None),
}

# how many pool entries static feasibility pruning eliminated this
# process, per op — benchmarks/run.py snapshots this next to the rows it
# measured so BENCH_*.json records the model's contribution
_PRUNE_STATS: "dict[str, int]" = {}
# full report of the most recent autotune() call (tests + bench notes)
_LAST_REPORT: "dict[str, Any]" = {}


def prune_stats() -> "dict[str, int]":
    """Per-op count of statically-pruned pool entries this process."""
    return dict(_PRUNE_STATS)


def reset_prune_stats() -> None:
    _PRUNE_STATS.clear()


def last_autotune_report() -> "dict[str, Any]":
    """The most recent autotune() call's selection report: op, cache key,
    cache_hit, pruned [(config dict, reason)], skipped [(config dict,
    reason)] from the measurement loop, and the winning source."""
    return dict(_LAST_REPORT)


def _prune_infeasible(cands, op: str, m: int, k: int, n: int,
                      spec: "DeviceSpec"):
    """Drop statically-infeasible candidates before ranking/measuring.
    Returns ``(kept, pruned)`` with ``pruned`` as (config, reason) pairs.
    If the model would reject everything the original pool stands (the
    lint will flag the pool itself; selection must not dead-end)."""
    family, prec = _RESOURCE_FAMILIES[op]
    kept, pruned = [], []
    for c in cands:
        reason = _resources.infeasible_reason(
            family, c, m, k, n, vmem_bytes=spec.vmem_bytes,
            wgrad_precision=prec if family == "wgrad" else None,
            gemm_precision=prec if family == "gemm" else None)
        (kept if reason is None else pruned).append(
            c if reason is None else (c, reason))
    if not kept:
        return tuple(cands), []
    return tuple(kept), pruned


def _measure_candidate(config: KernelConfig, m: int, k: int, n: int, g: int,
                       *, iters: int = 3, warmup: int = 1,
                       seed: int = 0, op: str = "gemm") -> float:
    """Median wall seconds of one operator application under ``config`` on
    random operands (the live-backend measurement behind pool selection):
    grouped GEMM (``"gemm"``/``"decode"``), its quantizing-epilogue twin
    (``"gemm_quant"``), ragged wgrad contraction
    (``"wgrad"``/``"wgrad_fp8"``), tilewise quantization (``"quantize"``),
    or the fused activation->quantize epilogue (``"act_quant"``)."""
    import numpy as np
    from repro.kernels import dispatch, ref

    rng = np.random.default_rng(seed)
    g_eff = max(g, 1)                       # "quantize" callers pass g=0
    sizes = rng.multinomial(m, np.full(g_eff, 1.0 / g_eff)).astype(np.int32)
    gs = jnp.asarray(sizes)
    g = g_eff

    if op == "wgrad":
        x = jnp.asarray(rng.standard_normal((m, k)), jnp.bfloat16)
        dy = jnp.asarray(rng.standard_normal((m, n)), jnp.bfloat16)

        def run():
            return dispatch.grouped_gemm_wgrad(x, dy, gs, num_groups=g,
                                               config=config)
    elif op == "wgrad_fp8":
        x8, sx = ref.quantize_tilewise_ref(
            jnp.asarray(rng.standard_normal((m, k)), jnp.float32))
        d8, sd = ref.quantize_tilewise_ref(
            jnp.asarray(rng.standard_normal((m, n)), jnp.float32))

        def run():
            return dispatch.grouped_gemm_wgrad_fp8(x8, sx, d8, sd, gs,
                                                   num_groups=g,
                                                   config=config)
    elif op == "quantize":
        x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)

        def run():
            return dispatch.quantize_tilewise(x, backend=config.backend,
                                              config=config)
    elif op == "act_quant":
        ga = jnp.asarray(rng.standard_normal((m, k)), jnp.bfloat16)
        ua = jnp.asarray(rng.standard_normal((m, k)), jnp.bfloat16)

        def run():
            return dispatch.act_quantize(ga, ua, backend=config.backend,
                                         config=config)
    elif op == "gemm_bf16":
        xb = jnp.asarray(rng.standard_normal((m, k)), jnp.bfloat16)
        wb = jnp.asarray(rng.standard_normal((g, k, n)), jnp.bfloat16)

        def run():
            return dispatch.grouped_gemm_bf16(xb, wb, gs, num_groups=g,
                                              config=config)
    elif op == "gemm_quant":
        a8, sa = ref.quantize_tilewise_ref(
            jnp.asarray(rng.standard_normal((m, k)), jnp.float32))
        b8, sb = jax.vmap(ref.quantize_blockwise_ref)(
            jnp.asarray(rng.standard_normal((g, k, n)), jnp.float32))

        def run():
            return dispatch.grouped_gemm_quant(a8, sa, b8, sb, gs,
                                               config=config)
    else:
        a8, sa = ref.quantize_tilewise_ref(
            jnp.asarray(rng.standard_normal((m, k)), jnp.float32))
        b8, sb = jax.vmap(ref.quantize_blockwise_ref)(
            jnp.asarray(rng.standard_normal((g, k, n)), jnp.float32))

        def run():
            return dispatch.grouped_gemm_fp8(a8, sa, b8, sb, gs,
                                             config=config)

    for _ in range(warmup):
        jax.block_until_ready(run())
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(run())
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2]


def autotune(m: int, k: int, n: int, g: int, *,
             backend: Optional[str] = None,
             pool: Optional[Iterable[KernelConfig]] = None,
             cache_path: Optional[str] = None,
             measure: bool = True,
             max_candidates: int = 4,
             refresh: bool = False,
             seed: int = 0,
             op: str = "gemm") -> KernelConfig:
    """Select a ``KernelConfig`` for the shape class of (M, K, N, G).

    ``op`` is any key of :data:`_AUTOTUNE_OPS` — the registry-derived
    family list (a new dispatch family joins by adding an entry there):
    ``"gemm"`` is the forward/dgrad orientation (ragged M output rows),
    ``"decode"`` the same orientation restricted to the
    decode-specialized pool (tiny constant M per serving step;
    block_m<=16), ``"gemm_quant"`` the quantizing-epilogue producer
    (same orientation, fp8 + 1x128-scale output — its roofline drops the
    bf16 output write), ``"wgrad"`` the ragged-contraction orientation
    (``dw[g] = x_g^T @ dy_g``), ``"wgrad_fp8"`` that contraction on fp8
    operands + 1x128 tile scales, ``"quantize"`` the tilewise quantizer's
    tile height, and ``"act_quant"`` the fused activation->quantize
    epilogue's tile height (both K-only legality; N and G are ignored —
    pass 0).  Each ranks by its own roofline terms and caches under
    distinct keys: a routing decision tunes once per operator it uses.

    Pool candidates are ranked by the roofline cost model, the top
    ``max_candidates`` are measured on the live backend (skipped with
    ``measure=False`` — pure cost-model selection), and the winner is
    persisted to the JSON cache so later runs (and later processes) reuse
    it without re-measuring.
    """
    from repro.kernels import dispatch

    if op not in _AUTOTUNE_OPS:
        raise ValueError(f"unknown autotune op {op!r}; use one of "
                         f"{tuple(_AUTOTUNE_OPS)}")
    op_key = _AUTOTUNE_OPS[op]
    # configs carry the family-neutral backend name (one config string
    # rides a whole training step); the OpKey precision — not the name —
    # selects each family's twin at run time
    base = dispatch.resolve(op_key, backend)
    # cache keys keep the historical per-precision spelling (the fp8
    # wgrad entries were published as ``<name>_fp8``)
    resolved = base + ("_fp8" if op == "wgrad_fp8" else "")
    tile_free = dispatch.op_ignores_tiles(op_key, base)
    kind = _device_kind()
    key = cache_key(kind, resolved, m, k, n, g, op=op)
    entries = load_cache(cache_path)
    if not refresh and key in entries:
        entry = entries[key]
        # a cost-model-only entry does not satisfy a measured request —
        # upgrade it (tile-free backends never measure, so theirs stand)
        wants_measured = measure and not tile_free
        if entry.get("source") == "measured" or not wants_measured:
            _LAST_REPORT.clear()
            _LAST_REPORT.update(op=op, key=key, cache_hit=True,
                                pruned=[], skipped=[],
                                source=entry.get("source"))
            return KernelConfig.from_dict(entry["config"])

    if pool is None and op == "decode":
        pool = DECODE_POOL
    # wgrad's output is never transposed — forward/dgrad legality demands
    # both orientations, wgrad only its own; the quantizer has no (K, N)
    # output tile at all (its block_m is pure scheduling)
    # gemm_quant feeds the same FFN whose dgrads run the transposed
    # orientation under the same config, so it shares gemm's legality
    cands = candidate_pool(
        k, n, pool,
        require_transposable=(op in ("gemm", "gemm_bf16", "decode",
                                     "gemm_quant")),
        family=_RESOURCE_FAMILIES[op][0])
    if op not in ("wgrad", "wgrad_fp8"):
        # the span axes exist for the wgrad schedule only — every span>1
        # entry is a duplicate of its span-1 base for the other ops
        cands = tuple(c for c in cands if c.n_span == 1 and c.k_span == 1)
    if op in ("quantize", "act_quant"):
        # entries differing only in (block_n, block_k) are duplicates for
        # the quantizer/epilogue — keep one per tile height
        seen, uniq = set(), []
        for c in cands:
            if c.block_m not in seen:
                seen.add(c.block_m)
                uniq.append(c)
        cands = tuple(uniq)
    if not cands:
        raise ValueError(f"no pool candidate is legal for K={k}, N={n}")
    spec = device_spec(kind)
    # static feasibility pruning: the resource model eliminates entries
    # that can never run well at this shape (VMEM over budget, degenerate
    # grid) before a single measurement is spent on them
    cands, pruned = _prune_infeasible(cands, op, m, k, n, spec)
    if pruned:
        _PRUNE_STATS[op] = _PRUNE_STATS.get(op, 0) + len(pruned)
        for c, reason in pruned:
            logger.info("autotune[%s] statically pruned block_m=%d,"
                        "block_n=%d,block_k=%d: %s", op, c.block_m,
                        c.block_n, c.block_k, reason)
    if op in ("gemm", "decode"):
        cost = estimate_cost_s
    elif op == "gemm_bf16":
        cost = lambda m_, k_, n_, g_, c, s: \
            estimate_cost_s(m_, k_, n_, g_, c, s, precision="bf16")  # noqa: E731
    elif op == "gemm_quant":
        cost = lambda m_, k_, n_, g_, c, s: \
            estimate_cost_s(m_, k_, n_, g_, c, s, quant_output=True)  # noqa: E731
    elif op == "quantize":
        cost = lambda m_, k_, n_, g_, c, s: \
            estimate_cost_s_quantize(m_, k_, c, s)                # noqa: E731
    elif op == "act_quant":
        cost = lambda m_, k_, n_, g_, c, s: \
            estimate_cost_s_act_quant(m_, k_, c, s)               # noqa: E731
    else:
        prec = "fp8" if op == "wgrad_fp8" else "bf16"
        cost = lambda *a: estimate_cost_s_wgrad(*a, precision=prec)  # noqa: E731
    if op in ("wgrad", "wgrad_fp8"):
        # secondary key: modeled operand HBM bytes.  On compute-bound
        # shapes the roofline max() ties across span widths — prefer the
        # schedule that moves fewer bytes (the multi-tile point), leaving
        # measurement to arbitrate among the top candidates
        prec_rank = "fp8" if op == "wgrad_fp8" else "bf16"
        ranked = sorted(cands, key=lambda c: (
            cost(m, k, n, g, c, spec),
            wgrad_operand_bytes(m, k, n, g, c, precision=prec_rank)))
    else:
        ranked = sorted(cands, key=lambda c: cost(m, k, n, g, c, spec))
    overrides = {"backend": base}
    if op == "wgrad_fp8":
        overrides["wgrad_precision"] = "fp8"
    ranked = [c.with_(**overrides) for c in ranked]

    skipped: "list[tuple[KernelConfig, str]]" = []
    if measure and not tile_free:
        # a candidate that fails to compile/measure is recorded and
        # skipped, not allowed to abort the sweep (and a statically
        # pruned config never reaches this loop at all)
        timed = []
        for c in ranked[:max_candidates]:
            try:
                timed.append((_measure_candidate(c, m, k, n, g, seed=seed,
                                                 op=op), c))
            except Exception as exc:  # noqa: BLE001 - sweep must survive
                reason = f"{type(exc).__name__}: {exc}"
                skipped.append((c, reason))
                logger.warning("autotune[%s] measurement of block_m=%d,"
                               "block_n=%d,block_k=%d failed, skipping: %s",
                               op, c.block_m, c.block_n, c.block_k, reason)
        if timed:
            best_s, best = min(timed, key=lambda tc: tc[0])
            source = "measured"
        else:
            # every measurement failed — fall back to the cost-model
            # ranking rather than dead-ending the caller
            best, best_s = ranked[0], cost(m, k, n, g, ranked[0], spec)
            source = "cost_model"
    else:
        # tile-shape-independent backends (the XLA paths) or measure=False:
        # cost-model order is the selection
        best, best_s = ranked[0], cost(m, k, n, g, ranked[0], spec)
        source = "cost_model"

    entries[key] = {"config": best.to_dict(), "seconds": best_s,
                    "source": source, "pool_size": len(cands), "op": op,
                    "pruned": len(pruned),
                    "skipped": [{"config": c.to_dict(), "reason": r}
                                for c, r in skipped]}
    _LAST_REPORT.clear()
    _LAST_REPORT.update(op=op, key=key, cache_hit=False,
                        pruned=[(c.to_dict(), r) for c, r in pruned],
                        skipped=[(c.to_dict(), r) for c, r in skipped],
                        source=source)
    save_cache(entries, cache_path)
    return best


def decode_config(m: int, k: int, n: int, g: int, *,
                  backend: Optional[str] = None,
                  cache_path: Optional[str] = None,
                  measure: bool = False,
                  **kw) -> KernelConfig:
    """Decode-specialized pool selection (``op="decode"``): the serving
    engine's per-step grouped GEMM has tiny, *constant* M (batch x top_k
    rows total), so selection runs once at engine construction and the
    returned ``block_m<=16`` config rides every decode step.  Cost-model
    selection by default (``measure=False``) — engine construction should
    not block on kernel timing; pass ``measure=True`` to tune on-device.
    """
    # one event per pool selection: the decode-plan contract (REPRO-C06)
    # pins exactly one per Engine construction
    _events.emit("decode_select", m=m, k=k, n=n, g=g)
    return autotune(m, k, n, g, backend=backend, cache_path=cache_path,
                    measure=measure, op="decode", **kw)
