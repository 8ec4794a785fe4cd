"""Unified operator registry for every grouped-GEMM-shaped kernel seam.

The paper's core idea is ONE dispatch seam that adapts to variable group
dimensions at runtime instead of padding.  This module is that seam for
the whole repo: a single registry keyed by :class:`OpKey` ``(family,
precision)`` —

  =============  ===========  ==============================================
  family         precision    operation
  =============  ===========  ==============================================
  ``gemm``       ``fp8``      quantized grouped GEMM ``y[rows of g] =
                              a_g @ b[g]`` (ragged M output rows; the
                              paper's forward/dgrad orientation)
  ``gemm``       ``bf16``     the same orientation on bf16 operands — a
                              true Pallas kernel sharing the fp8 twin's
                              visit schedule (so fp8-vs-bf16 comparisons
                              measure OUR schedule on both sides), with
                              ``jax.lax.ragged_dot`` as the portable /
                              GSPMD fallback
  ``wgrad``      ``bf16``     ragged-contraction ``dw[g] = x_g^T @ dy_g``
                              (M contracted; DeepSeek recipe operands)
  ``wgrad``      ``fp8``      the same contraction on fp8 operands with
                              1x128 tile scales, dequantized per visit
                              (arXiv 2505.20524's all-fp8 step)
  ``gemm_quant`` ``fp8``      grouped GEMM with a fused quantizing
                              epilogue: the producer emits the fp8 payload
                              + 1x128 tile scales directly (the bf16
                              output never exists; kernel entries fuse,
                              XLA entries compose GEMM + quantize so the
                              matrix stays total)
  ``quantize``   ``fp8``      1x128 per-tile fp8 activation quantization
                              (the producer of the gemm family's operands)
  ``act_quant``  ``fp8``      fused activation -> 1x128 fp8 quantization
                              (``silu(g)*u`` / ``gelu(g)`` epilogue; the
                              bf16 intermediate never touches HBM; fp8
                              inputs with scales dequantize on load)
  =============  ===========  ==============================================

Backend *names* are family-neutral and shared across the table: one
``KernelConfig.backend`` string ("pallas", "xla_ragged", ...) rides a
whole training step — forward and dgrad through ``(gemm, fp8)``, wgrad
through ``(wgrad, <precision>)``, activation quantization through
``(quantize, fp8)`` — and the same :class:`~repro.kernels.plan.TilePlan`
through all of them.  Each entry is a :class:`BackendSpec` with

  * an ``available()`` probe returning ``(ok, reason)`` — built on
    the :mod:`repro.compat` TPU probe so selection is testable by
    monkeypatching, and refusal is an explicit
    :class:`BackendUnavailableError` instead of a deep ``AttributeError``;
  * a ``run()`` implementing the family's operation under a
    :class:`repro.kernels.plan.KernelConfig`, optionally consuming a
    precomputed :class:`~repro.kernels.plan.TilePlan`;
  * ``uses_plan`` / ``uses_tiles`` flags — plan/tile-free membership is a
    property of the registry entry, not a parallel frozenset to maintain.

All resolution goes through ONE function, :func:`resolve`, which owns

  * precision-twin derivation (``resolve(("wgrad", "fp8"), "pallas")``
    lands on the fp8 wgrad kernel; the historical ``<name>_fp8`` public
    spelling normalizes to the same entry),
  * availability checks (explicit requests raise with the probe's
    reason),
  * explicit-vs-auto fallback semantics (a *gemm-only* name like
    ``padded_baseline`` auto-resolves in the wgrad family instead of
    stranding a training config's backward; an explicitly requested but
    unavailable entry always raises),
  * tile-compatibility fallback (an *auto-resolved* plan backend whose
    tile shapes don't divide the problem falls back to the first
    tile-free entry of the same op; an explicit request raises via
    ``KernelConfig.validate``).

``backend="auto"`` resolves to the first available of
``pallas`` > ``xla_ragged`` > ``pallas_interpret``.  ``"xla"`` is kept as
an alias of ``"xla_ragged"`` for pre-registry callers.

Every pre-unification public entry point (``grouped_gemm``,
``grouped_gemm_fp8``, ``grouped_gemm_wgrad``, ``grouped_gemm_wgrad_fp8``,
``quantize_tilewise``, ``register_backend``, ``resolve_backend``,
``resolve_wgrad_backend``, ...) survives as a thin alias over the unified
seam — new backends, precisions, and op families plug in via
:func:`register_operator` without growing another registry copy.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro import compat
from repro.analysis import events as _events
from repro.kernels import ref as _ref
from repro.kernels.grouped_gemm_kernel import (QUANT_BLOCK, gmm_pallas,
                                               gmm_pallas_bf16,
                                               gmm_pallas_quant)
from repro.kernels.plan import (KernelConfig, TilePlan,  # noqa: F401
                                make_tile_plan, resolve_config)
from repro.kernels.epilogue_kernel import act_quantize_pallas
from repro.kernels.quant_kernel import quantize_tilewise_pallas
from repro.kernels.wgrad_kernel import gmm_pallas_wgrad, gmm_pallas_wgrad_fp8

# auto-resolution preference, best first (shared by every op family)
AUTO_ORDER = ("pallas", "xla_ragged", "pallas_interpret")

_ALIASES = {"xla": "xla_ragged"}

# suffix of the wgrad family's historical fp8-twin public names
# ("pallas_fp8" etc.); resolution normalizes it away — the OpKey precision,
# not the name, selects the arithmetic
_FP8_SUFFIX = "_fp8"

FAMILIES = ("gemm", "gemm_quant", "wgrad", "quantize", "act_quant")
PRECISIONS = ("bf16", "fp8")


@dataclasses.dataclass(frozen=True)
class OpKey:
    """One operator of the registry: an operation family at an operand
    precision.  Hashable; accepted anywhere as a plain ``(family,
    precision)`` tuple."""
    family: str      # "gemm" | "gemm_quant" | "wgrad" | "quantize" | "act_quant"
    precision: str   # "bf16" | "fp8"

    def __post_init__(self):
        if self.family not in FAMILIES:
            raise ValueError(f"unknown op family {self.family!r}; "
                             f"choose from {FAMILIES}")
        if self.precision not in PRECISIONS:
            raise ValueError(f"unknown operand precision "
                             f"{self.precision!r}; choose from {PRECISIONS}")


def _op_key(op_key) -> OpKey:
    if isinstance(op_key, OpKey):
        return op_key
    return OpKey(*op_key)


class BackendUnavailableError(RuntimeError):
    """Requested backend cannot run here; ``.reason`` says why."""

    def __init__(self, name: str, reason: str):
        super().__init__(f"grouped-GEMM backend {name!r} unavailable: "
                         f"{reason}")
        self.backend = name
        self.reason = reason


@dataclasses.dataclass(frozen=True)
class BackendSpec:
    name: str
    description: str
    available: Callable[[], "tuple[bool, str]"]   # (ok, reason-if-not)
    run: Callable[..., Any]
    uses_plan: bool = False     # walks the TilePlan visitation schedule
    uses_tiles: bool = False    # honours KernelConfig tile shapes at all


# THE registry: every (family, precision) operator's backend table lives
# in this one dict — there is no per-family registry copy to keep in sync.
_OPERATORS: "dict[OpKey, dict[str, BackendSpec]]" = {}

_default_backend_override: Optional[str] = None


def register_operator(op_key, name: str, *, description: str,
                      available: Callable[[], "tuple[bool, str]"],
                      run: Callable[..., Any],
                      uses_plan: bool = False,
                      uses_tiles: bool = False) -> None:
    """Register a backend for one ``(family, precision)`` operator.
    Later PRs (autotuned variants, new hardware paths, new precisions)
    plug in here — this is the ONLY write path into the registry."""
    key = _op_key(op_key)
    _OPERATORS.setdefault(key, {})[name] = BackendSpec(
        name, description, available, run,
        uses_plan=uses_plan, uses_tiles=uses_tiles)


def op_keys() -> "tuple[OpKey, ...]":
    return tuple(_OPERATORS)


def _table(op_key) -> "dict[str, BackendSpec]":
    key = _op_key(op_key)
    if key not in _OPERATORS:
        raise ValueError(f"no operator registered for {key}; "
                         f"registered: {op_keys()}")
    return _OPERATORS[key]


def _canonical(op_key: OpKey, name: str) -> str:
    """Public spelling -> registry name: aliases ("xla"), and — in the
    wgrad family only — the historical ``<name>_fp8`` twin suffix."""
    name = _ALIASES.get(name, name)
    if op_key.family == "wgrad" and name.endswith(_FP8_SUFFIX):
        name = name[: -len(_FP8_SUFFIX)]
    return name


def _display(op_key: OpKey, name: str) -> str:
    """Registry name -> the public spelling pre-unification callers know
    (the wgrad family's fp8 twins carried a ``_fp8`` suffix)."""
    if op_key.family == "wgrad" and op_key.precision == "fp8":
        return name + _FP8_SUFFIX
    return name


def resolve(op_key, backend: Optional[str] = None, *,
            tile: "Optional[tuple]" = None) -> str:
    """THE resolution path: map a requested backend (or ``"auto"`` /
    ``None``) to a concrete, *available* entry of ``op_key``'s table.

    ``tile``, when given, is ``(config, m, k, n)`` and enables the
    tile-compatibility policy for plan-consuming entries: an explicitly
    requested backend whose tile shapes don't divide ``(k, n)`` raises
    via ``config.validate``; an auto-resolved one falls back to the first
    available tile-free entry of the same operator.

    Fallback semantics (one place, every family):

      * explicit name in this op's table but unavailable -> raise
        :class:`BackendUnavailableError` with the probe's reason;
      * explicit name known only to the gemm family (``padded_baseline``
        in the wgrad family) -> auto-resolve instead of stranding a
        training config's backward;
      * name known nowhere -> ``ValueError``;
      * ``auto``/``None`` -> the installed default override if usable
        (the gemm/quantize families treat an unavailable override as an
        explicit request and raise — callers like ``quantize_tilewise``
        turn that into a ref fallback; the wgrad family skips it), then
        the first available of :data:`AUTO_ORDER`.
    """
    key = _op_key(op_key)
    table = _table(key)
    explicit = backend not in (None, "auto")

    if explicit:
        name = _canonical(key, backend)
        if name in table:
            ok, reason = table[name].available()
            if not ok:
                raise BackendUnavailableError(_display(key, name), reason)
            return _tile_policy(key, name, tile, explicit=True)
        if name not in _OPERATORS[OpKey("gemm", "fp8")]:
            known = tuple(_display(key, n) for n in table)
            raise ValueError(f"unknown backend {backend!r}; "
                             f"{key.family}/{key.precision} has {known}")
        # a gemm-only backend name: auto-resolve from here on — a
        # training config pins ONE backend string for the whole step, and
        # a forward-only choice must not strand the other families
        explicit = False

    if _default_backend_override is not None:
        name = _canonical(key, _default_backend_override)
        if key.family == "wgrad":
            # the wgrad family tries the override, then falls back: the
            # override seam predates the family and a gemm-centric pin
            # must not strand the backward
            if name in table and table[name].available()[0]:
                return _tile_policy(key, name, tile, explicit=False)
        elif name in table:
            # the gemm/quantize families treat an unavailable override as
            # an explicit request (historical semantics — quantize's ref
            # fallback depends on the raise); an override the operator
            # never registered (e.g. a kernel name against the bf16
            # baseline table) auto-resolves instead
            ok, reason = table[name].available()
            if not ok:
                raise BackendUnavailableError(_display(key, name), reason)
            return _tile_policy(key, name, tile, explicit=False)

    for cand in AUTO_ORDER:
        if cand in table and table[cand].available()[0]:
            return _tile_policy(key, cand, tile, explicit=False)
    raise BackendUnavailableError(
        "auto", f"no {key.precision} {key.family} backend is available "
                f"(tried {AUTO_ORDER})")


def _tile_policy(key: OpKey, name: str, tile, *, explicit: bool) -> str:
    """Shared tile-incompatibility policy (see :func:`resolve`); every
    resolution ends here and is emitted as a ``backend_resolved`` event."""
    if tile is not None and _OPERATORS[key][name].uses_plan:
        name = _tile_fallback(key, name, tile, explicit=explicit)
    _events.emit("backend_resolved", family=key.family,
                 precision=key.precision, backend=name)
    return name


#: (family, precision, m, k, n) shapes whose TPU tile fallback was warned
_FALLBACK_WARNED: "set[tuple]" = set()


def _tile_fallback(key: OpKey, name: str, tile, *, explicit: bool) -> str:
    table = _OPERATORS[key]
    cfg, m, k, n = tile
    if cfg.compatible(k, n, family=key.family):
        return name
    if explicit:
        # raises with the shape message (or the computed VMEM footprint)
        cfg.validate(m, k, n, family=key.family)
    for fb in ("xla_ragged", "xla_exact"):
        if fb in table and table[fb].available()[0]:
            shape = (key.family, key.precision, m, k, n)
            if compat.has_tpu() and shape not in _FALLBACK_WARNED:
                _FALLBACK_WARNED.add(shape)
                warnings.warn(
                    f"{key.family}/{key.precision} at (M={m}, K={k}, N={n}): "
                    f"{name!r} tiles {cfg.effective_blocks(key.family)} do "
                    f"not divide (K, N); running {fb!r} instead",
                    stacklevel=3)
            return fb
    eff_k, eff_n = cfg.effective_blocks(key.family)
    raise BackendUnavailableError(
        _display(key, name),
        f"tile shapes (block_k={eff_k}, block_n={eff_n}, spans included) "
        f"do not divide (K={k}, N={n}) and no tile-free {key.precision} "
        f"{key.family} backend is available")


def op_backend_names(op_key) -> "tuple[str, ...]":
    return tuple(_table(op_key))


def op_availability(op_key, name: str) -> "tuple[bool, str]":
    key = _op_key(op_key)
    table = _table(key)
    name = _canonical(key, name)
    if name not in table:
        raise ValueError(
            f"unknown backend {name!r} for {key.family}/{key.precision}; "
            f"choose from {tuple(_display(key, n) for n in table)}")
    return table[name].available()


def op_uses_plan(op_key, backend: Optional[str] = "auto") -> bool:
    key = _op_key(op_key)
    return _table(key)[resolve(key, backend)].uses_plan


def op_ignores_tiles(op_key, backend: Optional[str] = "auto") -> bool:
    key = _op_key(op_key)
    return not _table(key)[resolve(key, backend)].uses_tiles


def backend_matrix(op_key=None) -> "dict[str, Any]":
    """Availability/description rows for docs and CLIs.

    ``op_key=None`` keeps the historical shape — the ``(gemm, fp8)``
    table keyed by backend name.  ``op_key="all"`` returns every
    operator: ``{"family/precision": {name: row}}`` (the source of the
    README's family x precision x backend table); a concrete
    ``OpKey``/tuple returns that operator's rows.
    """
    if op_key == "all":
        return {f"{k.family}/{k.precision}": backend_matrix(k)
                for k in sorted(_OPERATORS,
                                key=lambda k: (FAMILIES.index(k.family),
                                               k.precision))}
    key = _op_key(op_key) if op_key is not None else OpKey("gemm", "fp8")
    out = {}
    for name, spec in _table(key).items():
        ok, reason = spec.available()
        out[name] = {"available": ok, "reason": reason,
                     "description": spec.description,
                     "uses_plan": spec.uses_plan,
                     "uses_tiles": spec.uses_tiles}
    return out


def format_backend_matrix() -> str:
    """The README's backend table, generated (``python -m
    repro.kernels.dispatch`` prints it)."""
    lines = ["| family | precision | backend | needs | description |",
             "| --- | --- | --- | --- | --- |"]
    for label, rows in backend_matrix("all").items():
        family, precision = label.split("/")
        for name, row in rows.items():
            disp = _display(OpKey(family, precision), name)
            needs = "—" if row["available"] else row["reason"].split(";")[0]
            if name == "pallas":
                needs = "TPU"
            elif name == "pallas_interpret":
                needs = "no TPU"
            lines.append(f"| `{family}` | `{precision}` | `{disp}` | "
                         f"{needs} | {row['description']} |")
    return "\n".join(lines)


def set_default_backend(name: Optional[str]) -> None:
    """Override what ``backend=None`` / ``"auto"`` resolves to."""
    global _default_backend_override
    if name is not None:
        name = _ALIASES.get(name, name)
        if name not in _table(OpKey("gemm", "fp8")):
            raise ValueError(f"unknown backend {name!r}; "
                             f"choose from {backend_names()}")
    _default_backend_override = name


def default_backend() -> str:
    return resolve_backend("auto")


# ---------------------------------------------------------------------------
# Pre-unification aliases (the public surface of PRs 1-4, unchanged)
# ---------------------------------------------------------------------------

def register_backend(name: str, *, description: str,
                     available: Callable[[], "tuple[bool, str]"],
                     run: Callable[..., jax.Array],
                     uses_plan: bool = False,
                     uses_tiles: bool = False) -> None:
    """Alias: register a ``(gemm, fp8)`` backend."""
    register_operator(OpKey("gemm", "fp8"), name, description=description,
                      available=available, run=run, uses_plan=uses_plan,
                      uses_tiles=uses_tiles)


def register_wgrad_backend(name: str, *, description: str,
                           available: Callable[[], "tuple[bool, str]"],
                           run: Callable[..., jax.Array],
                           uses_plan: bool = False,
                           uses_tiles: bool = False) -> None:
    """Alias: register a wgrad-family backend.  A ``<name>_fp8`` spelling
    registers the fp8-precision twin (the OpKey carries the precision;
    the suffix is only the historical public naming)."""
    precision = "fp8" if name.endswith(_FP8_SUFFIX) else "bf16"
    base = name[: -len(_FP8_SUFFIX)] if precision == "fp8" else name
    register_operator(OpKey("wgrad", precision), base,
                      description=description, available=available, run=run,
                      uses_plan=uses_plan, uses_tiles=uses_tiles)


def backend_names() -> "tuple[str, ...]":
    return op_backend_names(OpKey("gemm", "fp8"))


def wgrad_backend_names() -> "tuple[str, ...]":
    key16, key8 = OpKey("wgrad", "bf16"), OpKey("wgrad", "fp8")
    return (tuple(_table(key16))
            + tuple(_display(key8, n) for n in _table(key8)))


def availability(name: str) -> "tuple[bool, str]":
    name = _ALIASES.get(name, name)
    if name not in _table(OpKey("gemm", "fp8")):
        raise ValueError(f"unknown backend {name!r}; "
                         f"choose from {backend_names()}")
    return op_availability(OpKey("gemm", "fp8"), name)


def wgrad_availability(name: str) -> "tuple[bool, str]":
    precision = "fp8" if _ALIASES.get(name, name).endswith(_FP8_SUFFIX) \
        else "bf16"
    key = OpKey("wgrad", precision)
    base = _canonical(key, name)
    if base not in _table(key):
        raise ValueError(f"unknown wgrad backend {name!r}; "
                         f"choose from {wgrad_backend_names()}")
    return op_availability(key, base)


def resolve_backend(backend: Optional[str] = "auto") -> str:
    """Alias: resolve in the ``(gemm, fp8)`` table."""
    return resolve(OpKey("gemm", "fp8"), backend)


def resolve_wgrad_backend(backend: Optional[str] = "auto", *,
                          precision: str = "bf16") -> str:
    """Alias: resolve in the wgrad table of the requested operand
    ``precision`` ("bf16" | "fp8"); returns the historical public
    spelling (fp8 entries carry the ``_fp8`` suffix).

    Backend names are family-neutral: ``"pallas"`` with
    ``precision="fp8"`` resolves to the fp8 wgrad kernel (and an
    explicitly suffixed ``"pallas_fp8"`` normalizes to whichever twin the
    precision asks for — the operands at the call site, not the name,
    decide the arithmetic)."""
    if precision not in PRECISIONS:
        raise ValueError(f"unknown wgrad precision {precision!r}; "
                         "use 'bf16' or 'fp8'")
    key = OpKey("wgrad", precision)
    return _display(key, resolve(key, backend))


def backend_uses_plan(backend: Optional[str] = "auto") -> bool:
    """Whether the (resolved) gemm backend consumes a precomputed
    TilePlan — callers skip plan construction for the XLA paths."""
    return op_uses_plan(OpKey("gemm", "fp8"), backend)


def backend_ignores_tiles(backend: Optional[str] = "auto") -> bool:
    """Whether tile shapes are a no-op for the (resolved) gemm backend —
    the autotuner skips measurement there (cost-model selection only)."""
    return op_ignores_tiles(OpKey("gemm", "fp8"), backend)


def _plan_tile_frozenset(uses_plan: bool) -> "frozenset[str]":
    # the tile-free view keeps its historical GEMM/wgrad contents — the
    # quantize-flavoured families (whose ref entries are trivially
    # tile-free) stay out of the back-compat frozenset
    names = set()
    for key, table in _OPERATORS.items():
        for name, spec in table.items():
            if (spec.uses_plan if uses_plan
                    else (not spec.uses_tiles
                          and key.family not in ("gemm_quant", "quantize",
                                                 "act_quant"))):
                names.add(_display(key, name))
    return frozenset(names)


# ---------------------------------------------------------------------------
# XLA implementations
# ---------------------------------------------------------------------------

def _dequant_a(a_fp8, s_a, dtype):
    m, k = a_fp8.shape
    scales = jnp.repeat(s_a, QUANT_BLOCK, axis=1)[:, :k]
    return (a_fp8.astype(jnp.float32) * scales).astype(dtype)


def _dequant_b(b_fp8, s_b, dtype):
    g, k, n = b_fp8.shape
    scales = jnp.repeat(jnp.repeat(s_b, QUANT_BLOCK, axis=1), QUANT_BLOCK,
                        axis=2)[:, :k, :n]
    return (b_fp8.astype(jnp.float32) * scales).astype(dtype)


def gmm_xla(a_fp8, s_a, b_fp8, s_b, group_sizes, *, out_dtype=jnp.bfloat16,
            compute_dtype=jnp.bfloat16):
    """ragged_dot on dequantized operands (GSPMD-partitionable)."""
    a = _dequant_a(a_fp8, s_a, compute_dtype)
    b = _dequant_b(b_fp8, s_b, compute_dtype)
    out = jax.lax.ragged_dot(a, b, group_sizes.astype(jnp.int32),
                             preferred_element_type=jnp.float32)
    return out.astype(out_dtype)


def gmm_xla_exact(a_fp8, s_a, b_fp8, s_b, group_sizes, *,
                  out_dtype=jnp.bfloat16):
    """Per-K-block f32 math — bit-identical accumulation order to the
    Pallas kernel (ragged_dot per K block, rescale, accumulate in f32)."""
    m, k = a_fp8.shape
    g, _, n = b_fp8.shape
    kb = k // QUANT_BLOCK
    gs = group_sizes.astype(jnp.int32)
    acc = jnp.zeros((m, n), jnp.float32)
    # row scale for token i and k-block j applied post-dot; column scale is
    # constant within a 128-wide n block.
    for j in range(kb):
        aj = a_fp8[:, j * QUANT_BLOCK:(j + 1) * QUANT_BLOCK].astype(jnp.float32)
        bj = b_fp8[:, j * QUANT_BLOCK:(j + 1) * QUANT_BLOCK, :].astype(jnp.float32)
        part = jax.lax.ragged_dot(aj, bj, gs,
                                  preferred_element_type=jnp.float32)
        # gather this token's group column-scales: expand s_b rows per group
        seg = jnp.repeat(jnp.arange(g), gs, total_repeat_length=m)
        col = jnp.repeat(s_b[:, j, :], QUANT_BLOCK, axis=1)[:, :n]   # (g, n)
        acc = acc + part * s_a[:, j][:, None] * col[seg]
    return acc.astype(out_dtype)


def gmm_bf16_xla_exact(x, w, group_sizes, *, out_dtype=jnp.bfloat16):
    """bf16-operand oracle with :func:`~repro.kernels.grouped_gemm_kernel
    .gmm_pallas_bf16`'s exact reduction order: one dense f32 ``dot`` per
    (group, 128-wide K block) on f32-upcast bf16 operands, row-selected
    by group membership and accumulated in f32 across K blocks.  Dense
    ``dot`` (not ``ragged_dot``) is load-bearing for bitwise parity: XLA
    splits the contraction differently per output row inside a
    ``ragged_dot``, while M-tiling a dense dot is bitwise-stable — and
    the kernel's per-visit dots are exactly M tiles of these.  Tail rows
    beyond ``sum(group_sizes)`` stay exactly zero (the kernel's
    zero-fill contract).  O(G·M·N·K) — test-scale only."""
    x16 = x.astype(jnp.bfloat16)
    w16 = w.astype(jnp.bfloat16)
    m, k = x16.shape
    g, _, n = w16.shape
    gs = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(gs)
    starts = ends - gs
    r = jnp.arange(m, dtype=jnp.int32)
    acc = jnp.zeros((m, n), jnp.float32)
    for j in range(k // QUANT_BLOCK):
        aj = x16[:, j * QUANT_BLOCK:(j + 1) * QUANT_BLOCK].astype(jnp.float32)
        part = jnp.zeros((m, n), jnp.float32)
        for gi in range(g):
            bj = w16[gi, j * QUANT_BLOCK:(j + 1) * QUANT_BLOCK, :].astype(
                jnp.float32)
            pg = jax.lax.dot(aj, bj, preferred_element_type=jnp.float32)
            own = (r >= starts[gi]) & (r < ends[gi])
            part = jnp.where(own[:, None], pg, part)
        acc = acc + part
    return acc.astype(out_dtype)


def wgrad_xla_ragged(x, dy, group_sizes, *, num_groups,
                     out_dtype=jnp.float32):
    """``jax.lax.ragged_dot_general`` with the rows (the ragged dim) as
    the contracting dims, f32 accumulation — the historical wgrad path,
    now the portable fallback of this family."""
    dn = jax.lax.RaggedDotDimensionNumbers(
        dot_dimension_numbers=(((0,), (0,)), ((), ())),
        lhs_ragged_dimensions=[0],
        rhs_group_dimensions=[])
    dw = jax.lax.ragged_dot_general(
        x, dy, group_sizes.astype(jnp.int32), dn,
        preferred_element_type=jnp.float32)
    return dw.astype(out_dtype)


def wgrad_xla_exact(x, dy, group_sizes, *, num_groups,
                    out_dtype=jnp.float32):
    """Dense f32 oracle: one-hot group membership contracted in a single
    einsum.  O(M*G) membership mask — test-scale only, but every term is
    an exact f32 product, and rows beyond ``sum(group_sizes)`` have an
    all-zero membership row (excluded by construction, not by masking
    garbage after the fact)."""
    m = x.shape[0]
    gs = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(gs)
    starts = ends - gs
    r = jnp.arange(m, dtype=jnp.int32)
    member = ((r[:, None] >= starts[None, :])
              & (r[:, None] < ends[None, :])).astype(jnp.float32)  # [M, G]
    dw = jnp.einsum("mg,mk,mn->gkn", member, x.astype(jnp.float32),
                    dy.astype(jnp.float32),
                    preferred_element_type=jnp.float32)
    return dw.astype(out_dtype)


def wgrad_fp8_xla_ragged(x_fp8, s_x, dy_fp8, s_dy, group_sizes, *,
                         num_groups, out_dtype=jnp.float32):
    """fp8-operand twin of :func:`wgrad_xla_ragged`: dequantize both
    operands up front (``_dequant_a`` — the 1x128 row-tile layout is the
    same on the x and dy sides) and reuse the bf16 ragged contraction."""
    x = _dequant_a(x_fp8, s_x, jnp.bfloat16)
    dy = _dequant_a(dy_fp8, s_dy, jnp.bfloat16)
    return wgrad_xla_ragged(x, dy, group_sizes, num_groups=num_groups,
                            out_dtype=out_dtype)


def wgrad_fp8_xla_exact(x_fp8, s_x, dy_fp8, s_dy, group_sizes, *,
                        num_groups, out_dtype=jnp.float32):
    """fp8-operand oracle: exact f32 dequantization then the dense
    one-hot f32 contraction — the ground truth the fp8 wgrad kernel's
    per-visit dequantization is validated against."""
    x = _dequant_a(x_fp8, s_x, jnp.float32)
    dy = _dequant_a(dy_fp8, s_dy, jnp.float32)
    return wgrad_xla_exact(x, dy, group_sizes, num_groups=num_groups,
                           out_dtype=out_dtype)


# ---------------------------------------------------------------------------
# Built-in registrations
# ---------------------------------------------------------------------------

def _avail_always():
    return True, ""


def _avail_tpu():
    if compat.has_tpu():
        return True, ""
    return False, ("requires a TPU (jax.default_backend() == 'tpu'); "
                   "use 'pallas_interpret' for CPU-verifiable runs")


def _avail_interpret():
    if not compat.has_tpu():
        return True, ""
    return False, ("interpret mode is the CPU verification path; "
                   "on a TPU use the compiled 'pallas' entry")


# ---- (gemm, fp8): the paper's forward/dgrad orientation -------------------

def _run_pallas(a8, sa, b8, sb, gs, *, num_groups, config, plan, interpret):
    return gmm_pallas(a8, sa, b8, sb, gs, num_groups=num_groups,
                      block_m=config.block_m, block_n=config.block_n,
                      block_k=config.block_k, out_dtype=config.out_dtype,
                      interpret=interpret, plan=plan)


def _run_xla_ragged(a8, sa, b8, sb, gs, *, config, **_):
    return gmm_xla(a8, sa, b8, sb, gs, out_dtype=config.out_dtype)


def _run_xla_exact(a8, sa, b8, sb, gs, *, config, **_):
    return gmm_xla_exact(a8, sa, b8, sb, gs, out_dtype=config.out_dtype)


def _run_padded_baseline(a8, sa, b8, sb, gs, *, config, **_):
    # deferred import: padding_baseline routes its aligned GEMM back
    # through this registry.  A caller's TilePlan never applies here —
    # padding changes the group offsets, so the baseline plans over the
    # padded sizes (once per static shape, via the PlanCache).
    from repro.core import padding_baseline as pb
    inner = "pallas" if compat.has_tpu() else "pallas_interpret"
    return pb.grouped_gemm_fp8_padded(a8, sa, b8, sb, gs,
                                      config=config.with_(backend=inner))


register_operator(
    ("gemm", "fp8"), "pallas",
    description="compiled Pallas TPU kernel (padding-free, paper §2)",
    available=_avail_tpu,
    run=lambda *a, **kw: _run_pallas(*a, interpret=False, **kw),
    uses_plan=True, uses_tiles=True)
register_operator(
    ("gemm", "fp8"), "pallas_interpret",
    description="Pallas kernel in interpret mode — CPU-verifiable, "
                "bit-identical to 'pallas'",
    available=_avail_interpret,
    run=lambda *a, **kw: _run_pallas(*a, interpret=True, **kw),
    uses_plan=True, uses_tiles=True)
register_operator(
    ("gemm", "fp8"), "xla_ragged",
    description="jax.lax.ragged_dot on bf16-dequantized operands "
                "(portable / GSPMD)",
    available=_avail_always,
    run=_run_xla_ragged)
register_operator(
    ("gemm", "fp8"), "xla_exact",
    description="per-K-block f32 oracle with the kernel's accumulation "
                "order",
    available=_avail_always,
    run=_run_xla_exact)
register_operator(
    ("gemm", "fp8"), "padded_baseline",
    description="the paper's baseline: pad groups to block_m, aligned "
                "grouped GEMM, unpad",
    available=_avail_always,
    run=_run_padded_baseline,
    uses_tiles=True)       # block_m drives the padding; no plan consumed


# ---- (gemm, bf16): the numerics-baseline orientation ----------------------

def _run_pallas_bf16(x, w, gs, *, num_groups, config, plan, interpret):
    return gmm_pallas_bf16(x, w, gs, num_groups=num_groups,
                           block_m=config.block_m, block_n=config.block_n,
                           block_k=config.block_k,
                           out_dtype=config.out_dtype,
                           interpret=interpret, plan=plan)


def _run_bf16_ragged(x, w, gs, *, config, **_):
    out = jax.lax.ragged_dot(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                             gs.astype(jnp.int32),
                             preferred_element_type=jnp.float32)
    return out.astype(config.out_dtype)


def _run_bf16_xla_exact(x, w, gs, *, config, **_):
    return gmm_bf16_xla_exact(x, w, gs, out_dtype=config.out_dtype)


register_operator(
    ("gemm", "bf16"), "pallas",
    description="compiled Pallas TPU kernel on bf16 operands — the fp8 "
                "kernel's visit schedule without the quantize machinery",
    available=_avail_tpu,
    run=lambda *a, **kw: _run_pallas_bf16(*a, interpret=False, **kw),
    uses_plan=True, uses_tiles=True)
register_operator(
    ("gemm", "bf16"), "pallas_interpret",
    description="bf16 Pallas kernel in interpret mode — CPU-verifiable, "
                "bit-identical to 'pallas'",
    available=_avail_interpret,
    run=lambda *a, **kw: _run_pallas_bf16(*a, interpret=True, **kw),
    uses_plan=True, uses_tiles=True)
register_operator(
    ("gemm", "bf16"), "xla_ragged",
    description="jax.lax.ragged_dot on bf16 operands (numerics baseline)",
    available=_avail_always,
    run=_run_bf16_ragged)
register_operator(
    ("gemm", "bf16"), "xla_exact",
    description="per-(group, 128-K-block) dense f32 oracle with the bf16 "
                "kernel's accumulation order",
    available=_avail_always,
    run=_run_bf16_xla_exact)


# ---- (gemm_quant, fp8): the quantizing-epilogue producer ------------------

def _run_gemm_quant_pallas(a8, sa, b8, sb, gs, *, num_groups, config, plan,
                           interpret):
    return gmm_pallas_quant(a8, sa, b8, sb, gs, num_groups=num_groups,
                            block_m=config.block_m, block_n=config.block_n,
                            block_k=config.block_k,
                            out_dtype=config.out_dtype,
                            interpret=interpret, plan=plan)


def _compose_gemm_quant(gemm_name):
    """Unfused composition: run the same-named ``(gemm, fp8)`` entry, then
    the reference tilewise quantizer on its f32 upcast.  Keeps the
    backend matrix total — every backend that can GEMM can gemm_quant —
    and defines the rounding point the fused kernel matches bitwise."""
    def run(a8, sa, b8, sb, gs, *, num_groups=None, config=None, plan=None,
            **_):
        y = _OPERATORS[OpKey("gemm", "fp8")][gemm_name].run(
            a8, sa, b8, sb, gs, num_groups=num_groups, config=config,
            plan=plan)
        return _ref.quantize_tilewise_ref(y.astype(jnp.float32))
    return run


def _run_gemm_quant_ref(a8, sa, b8, sb, gs, *, config, **_):
    y = gmm_xla(a8, sa, b8, sb, gs, out_dtype=config.out_dtype)
    return _ref.quantize_tilewise_ref(y.astype(jnp.float32))


register_operator(
    ("gemm_quant", "fp8"), "pallas",
    description="compiled Pallas TPU kernel: grouped GEMM + fused 1x128 "
                "quantizing epilogue (fp8 payload + scales emitted "
                "directly; no bf16 output write)",
    available=_avail_tpu,
    run=lambda *a, **kw: _run_gemm_quant_pallas(*a, interpret=False, **kw),
    uses_plan=True, uses_tiles=True)
register_operator(
    ("gemm_quant", "fp8"), "pallas_interpret",
    description="quantizing-epilogue kernel in interpret mode — "
                "CPU-verifiable, bit-identical to 'pallas'",
    available=_avail_interpret,
    run=lambda *a, **kw: _run_gemm_quant_pallas(*a, interpret=True, **kw),
    uses_plan=True, uses_tiles=True)
register_operator(
    ("gemm_quant", "fp8"), "xla_ragged",
    description="unfused composition: xla_ragged GEMM then reference "
                "tilewise quantize",
    available=_avail_always,
    run=_compose_gemm_quant("xla_ragged"))
register_operator(
    ("gemm_quant", "fp8"), "xla_exact",
    description="unfused composition: xla_exact GEMM then reference "
                "tilewise quantize",
    available=_avail_always,
    run=_compose_gemm_quant("xla_exact"))
register_operator(
    ("gemm_quant", "fp8"), "padded_baseline",
    description="unfused composition: padded-baseline GEMM then reference "
                "tilewise quantize (the baseline fuses nothing)",
    available=_avail_always,
    run=_compose_gemm_quant("padded_baseline"),
    uses_tiles=True)       # block_m drives the inner padding
register_operator(
    ("gemm_quant", "fp8"), "ref",
    description="unfused dequantize-GEMM + reference quantize — always "
                "available",
    available=_avail_always,
    run=_run_gemm_quant_ref)


# ---- (wgrad, bf16): the ragged-contraction orientation --------------------

def _run_pallas_wgrad(x, dy, gs, *, num_groups, config, plan, interpret):
    return gmm_pallas_wgrad(x, dy, gs, num_groups=num_groups,
                            block_m=config.block_m, block_n=config.block_n,
                            block_k=config.block_k,
                            n_span=config.n_span, k_span=config.k_span,
                            out_dtype=config.out_dtype, interpret=interpret,
                            plan=plan)


def _run_wgrad_xla_ragged(x, dy, gs, *, num_groups, config, **_):
    return wgrad_xla_ragged(x, dy, gs, num_groups=num_groups,
                            out_dtype=config.out_dtype)


def _run_wgrad_xla_exact(x, dy, gs, *, num_groups, config, **_):
    return wgrad_xla_exact(x, dy, gs, num_groups=num_groups,
                           out_dtype=config.out_dtype)


register_operator(
    ("wgrad", "bf16"), "pallas",
    description="compiled Pallas TPU kernel: ragged-M contraction with "
                "per-visit masked accumulation (padding-free wgrad)",
    available=_avail_tpu,
    run=lambda *a, **kw: _run_pallas_wgrad(*a, interpret=False, **kw),
    uses_plan=True, uses_tiles=True)
register_operator(
    ("wgrad", "bf16"), "pallas_interpret",
    description="wgrad kernel in interpret mode — CPU-verifiable, "
                "bit-identical to 'pallas'",
    available=_avail_interpret,
    run=lambda *a, **kw: _run_pallas_wgrad(*a, interpret=True, **kw),
    uses_plan=True, uses_tiles=True)
register_operator(
    ("wgrad", "bf16"), "xla_ragged",
    description="jax.lax.ragged_dot_general — portable fallback",
    available=_avail_always,
    run=_run_wgrad_xla_ragged)
register_operator(
    ("wgrad", "bf16"), "xla_exact",
    description="dense one-hot f32 oracle for the ragged contraction",
    available=_avail_always,
    run=_run_wgrad_xla_exact)


# ---- (wgrad, fp8): the all-fp8 step's contraction -------------------------

def _run_pallas_wgrad_fp8(x8, sx, dy8, sdy, gs, *, num_groups, config, plan,
                          interpret):
    return gmm_pallas_wgrad_fp8(x8, sx, dy8, sdy, gs, num_groups=num_groups,
                                block_m=config.block_m,
                                block_n=config.block_n,
                                block_k=config.block_k,
                                n_span=config.n_span, k_span=config.k_span,
                                out_dtype=config.out_dtype,
                                interpret=interpret, plan=plan)


def _run_wgrad_fp8_xla_ragged(x8, sx, dy8, sdy, gs, *, num_groups, config,
                              **_):
    return wgrad_fp8_xla_ragged(x8, sx, dy8, sdy, gs, num_groups=num_groups,
                                out_dtype=config.out_dtype)


def _run_wgrad_fp8_xla_exact(x8, sx, dy8, sdy, gs, *, num_groups, config,
                             **_):
    return wgrad_fp8_xla_exact(x8, sx, dy8, sdy, gs, num_groups=num_groups,
                               out_dtype=config.out_dtype)


register_operator(
    ("wgrad", "fp8"), "pallas",
    description="compiled Pallas TPU kernel: ragged-M contraction on fp8 "
                "operands, per-visit dequant folded into the masked "
                "prologue (arXiv 2505.20524 all-fp8 step)",
    available=_avail_tpu,
    run=lambda *a, **kw: _run_pallas_wgrad_fp8(*a, interpret=False, **kw),
    uses_plan=True, uses_tiles=True)
register_operator(
    ("wgrad", "fp8"), "pallas_interpret",
    description="fp8 wgrad kernel in interpret mode — CPU-verifiable, "
                "bit-identical to 'pallas_fp8'",
    available=_avail_interpret,
    run=lambda *a, **kw: _run_pallas_wgrad_fp8(*a, interpret=True, **kw),
    uses_plan=True, uses_tiles=True)
register_operator(
    ("wgrad", "fp8"), "xla_ragged",
    description="up-front bf16 dequantization + ragged_dot_general — "
                "portable fp8-operand fallback",
    available=_avail_always,
    run=_run_wgrad_fp8_xla_ragged)
register_operator(
    ("wgrad", "fp8"), "xla_exact",
    description="f32 dequantization + dense one-hot f32 oracle for the "
                "fp8-operand ragged contraction",
    available=_avail_always,
    run=_run_wgrad_fp8_xla_exact)


# ---- (quantize, fp8): the operand producer --------------------------------

def _run_quant_pallas(x, *, config, interpret, **_):
    kw = {} if config is None else {"block_m": config.block_m}
    return quantize_tilewise_pallas(x, interpret=interpret, **kw)


def _run_quant_ref(x, **_):
    return _ref.quantize_tilewise_ref(x)


register_operator(
    ("quantize", "fp8"), "pallas",
    description="Pallas 1x128 per-tile fp8 quantizer (tile height "
                "autotunable via op='quantize')",
    available=_avail_tpu,
    run=lambda *a, **kw: _run_quant_pallas(*a, interpret=False, **kw),
    uses_tiles=True)
register_operator(
    ("quantize", "fp8"), "pallas_interpret",
    description="quantizer kernel in interpret mode — CPU-verifiable, "
                "bit-identical to 'pallas'",
    available=_avail_interpret,
    run=lambda *a, **kw: _run_quant_pallas(*a, interpret=True, **kw),
    uses_tiles=True)
register_operator(
    ("quantize", "fp8"), "xla_ragged",
    description="XLA reference quantizer (tile shapes are a no-op)",
    available=_avail_always,
    run=_run_quant_ref)
register_operator(
    ("quantize", "fp8"), "xla_exact",
    description="XLA reference quantizer (tile shapes are a no-op)",
    available=_avail_always,
    run=_run_quant_ref)
register_operator(
    ("quantize", "fp8"), "padded_baseline",
    description="XLA reference quantizer (the baseline quantizes like "
                "everyone else)",
    available=_avail_always,
    run=_run_quant_ref)
register_operator(
    ("quantize", "fp8"), "ref",
    description="XLA reference quantizer — always available",
    available=_avail_always,
    run=_run_quant_ref)


# ---- (act_quant, fp8): the fused activation epilogue ----------------------

def _run_act_quant_pallas(g, u=None, *, act, config, interpret,
                          s_g=None, s_u=None, **_):
    kw = {} if config is None else {"block_m": config.block_m}
    return act_quantize_pallas(g, u, s_g=s_g, s_u=s_u, act=act,
                               interpret=interpret, **kw)


def _run_act_quant_ref(g, u=None, *, act, s_g=None, s_u=None, **_):
    return _ref.act_quantize_ref(g, u, act, s_g=s_g, s_u=s_u)


register_operator(
    ("act_quant", "fp8"), "pallas",
    description="fused Pallas epilogue: silu(g)*u / gelu(g) + 1x128 fp8 "
                "quantization in one grid pass (tile height autotunable "
                "via op='act_quant')",
    available=_avail_tpu,
    run=lambda *a, **kw: _run_act_quant_pallas(*a, interpret=False, **kw),
    uses_tiles=True)
register_operator(
    ("act_quant", "fp8"), "pallas_interpret",
    description="fused epilogue kernel in interpret mode — CPU-verifiable, "
                "bit-identical to 'pallas'",
    available=_avail_interpret,
    run=lambda *a, **kw: _run_act_quant_pallas(*a, interpret=True, **kw),
    uses_tiles=True)
register_operator(
    ("act_quant", "fp8"), "xla_ragged",
    description="unfused XLA reference: activation then tilewise quantize "
                "(tile shapes are a no-op)",
    available=_avail_always,
    run=_run_act_quant_ref)
register_operator(
    ("act_quant", "fp8"), "xla_exact",
    description="unfused XLA reference: activation then tilewise quantize "
                "(tile shapes are a no-op)",
    available=_avail_always,
    run=_run_act_quant_ref)
register_operator(
    ("act_quant", "fp8"), "padded_baseline",
    description="unfused XLA reference (the baseline has no fused "
                "epilogue either)",
    available=_avail_always,
    run=_run_act_quant_ref)
register_operator(
    ("act_quant", "fp8"), "ref",
    description="unfused silu·mul/gelu + quantize_tilewise reference — "
                "always available",
    available=_avail_always,
    run=_run_act_quant_ref)


# back-compat membership views (derived from the registry flags; prefer
# op_uses_plan / op_ignores_tiles)
PLAN_BACKENDS = _plan_tile_frozenset(uses_plan=True)
TILE_FREE_BACKENDS = _plan_tile_frozenset(uses_plan=False)


# ---------------------------------------------------------------------------
# Operator contract facts (repro.analysis layer 2, rule REPRO-R07)
# ---------------------------------------------------------------------------

# OpKey -> declarative facts the contract checker validates: which public
# dispatch function fronts the operator, whether its hot path is
# padding-free, and how many STANDALONE tilewise quantizations the
# operator itself performs (fused epilogues quantize in-kernel: zero).
_OP_CONTRACT_FACTS: "dict[OpKey, dict]" = {}


def register_operator_contract(op_key, *, entry_point: str,
                               padding_free: bool,
                               standalone_quantizes: int = 0) -> None:
    """Declare contract facts for one operator — registered next to its
    ``register_operator`` block so a new family cannot land without
    naming its invariants (REPRO-R07 fails the lint otherwise)."""
    _OP_CONTRACT_FACTS[_op_key(op_key)] = {
        "entry_point": entry_point,
        "padding_free": padding_free,
        "standalone_quantizes": standalone_quantizes,
    }


def op_contract_facts() -> "dict[OpKey, dict]":
    return dict(_OP_CONTRACT_FACTS)


register_operator_contract(("gemm", "fp8"),
                           entry_point="grouped_gemm_fp8",
                           padding_free=True)
register_operator_contract(("gemm", "bf16"),
                           entry_point="grouped_gemm_bf16",
                           padding_free=True)
register_operator_contract(("gemm_quant", "fp8"),
                           entry_point="grouped_gemm_quant",
                           padding_free=True)
register_operator_contract(("wgrad", "bf16"),
                           entry_point="grouped_gemm_wgrad",
                           padding_free=True)
register_operator_contract(("wgrad", "fp8"),
                           entry_point="grouped_gemm_wgrad_fp8",
                           padding_free=True)
register_operator_contract(("quantize", "fp8"),
                           entry_point="quantize_tilewise",
                           padding_free=True, standalone_quantizes=1)
register_operator_contract(("act_quant", "fp8"),
                           entry_point="act_quantize",
                           padding_free=True)


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------

def grouped_gemm_fp8(a_fp8, s_a, b_fp8, s_b, group_sizes, *,
                     backend: Optional[str] = None,
                     num_groups: Optional[int] = None,
                     config: Optional[KernelConfig] = None,
                     out_dtype=None,
                     plan: Optional[TilePlan] = None):
    """Quantized grouped GEMM through the ``(gemm, fp8)`` operator (the
    low-level entry — operands already fp8 with DeepSeek-style tile/block
    scales).

    Tile shapes travel in ``config`` (a :class:`KernelConfig`; defaults to
    the installed/per-device default); ``backend=``/``out_dtype=`` are
    per-call overrides of the config's fields.  ``plan`` is an optional
    precomputed :class:`TilePlan` for plan-consuming backends.
    """
    cfg = resolve_config(config, backend=backend, out_dtype=out_dtype)
    if cfg.out_dtype is None:
        cfg = cfg.with_(out_dtype=jnp.bfloat16)
    key = OpKey("gemm", "fp8")
    name = resolve(key, cfg.backend)
    return _OPERATORS[key][name].run(
        a_fp8, s_a, b_fp8, s_b, group_sizes, num_groups=num_groups,
        config=cfg, plan=plan)


def grouped_gemm_quant(a_fp8, s_a, b_fp8, s_b, group_sizes, *,
                       backend: Optional[str] = None,
                       num_groups: Optional[int] = None,
                       config: Optional[KernelConfig] = None,
                       out_dtype=None,
                       plan: Optional[TilePlan] = None):
    """Grouped GEMM with a fused 1x128 quantizing epilogue through the
    ``(gemm_quant, fp8)`` operator: returns ``(q[M, N] fp8e4m3,
    s[M, N/128] f32)`` instead of the materialized product — the
    producer's output is already the next GEMM's operand.

    ``out_dtype`` (default bf16) is the *intermediate rounding* dtype:
    the accumulator is rounded through it before the amax/scale step, so
    the result is bitwise what ``quantize_tilewise(grouped_gemm_fp8(...)
    .astype(f32))`` produces — fusion changes traffic, not values.  Tail
    rows beyond ``sum(group_sizes)`` come back as payload 0 / scale 1
    (the quantized image of the zero-fill contract).

    Same tile-fallback semantics as :func:`grouped_gemm_fp8`'s plan
    consumers: an auto-resolved kernel whose tile shapes don't divide
    (K, N) falls back to the unfused composition entries; an explicit
    request raises.
    """
    cfg = resolve_config(config, backend=backend, out_dtype=out_dtype)
    if cfg.out_dtype is None:
        cfg = cfg.with_(out_dtype=jnp.bfloat16)
    num_groups = num_groups if num_groups is not None else b_fp8.shape[0]
    # one event per producer-GEMM dispatch — the producer-fusion
    # contracts (REPRO-C05) pin the gate/up routing count
    _events.emit("gemm_quant", m=a_fp8.shape[0], n=b_fp8.shape[2])
    key = OpKey("gemm_quant", "fp8")
    name = resolve(key, cfg.backend,
                   tile=(cfg, a_fp8.shape[0], a_fp8.shape[1],
                         b_fp8.shape[2]))
    return _OPERATORS[key][name].run(
        a_fp8, s_a, b_fp8, s_b, group_sizes, num_groups=num_groups,
        config=cfg, plan=plan)


def grouped_gemm_bf16(x, w, group_sizes, *, backend: Optional[str] = None,
                      num_groups: Optional[int] = None,
                      config: Optional[KernelConfig] = None,
                      out_dtype=None,
                      plan: Optional[TilePlan] = None):
    """bf16-operand grouped GEMM through the ``(gemm, bf16)`` operator —
    the numerics-baseline orientation ``grouped_linear(precision="bf16")``
    builds on.  A true Pallas kernel (the fp8 twin's visit schedule, bf16
    operands, f32 accumulate) leads the auto order on TPU;
    ``jax.lax.ragged_dot`` keeps the family
    available on every platform.  Same tile-fallback semantics as every other
    plan consumer: an auto-resolved kernel whose tile shapes don't divide
    (K, N) falls back to the tile-free entries, an explicit request
    raises.  Not differentiable — training goes through
    :func:`repro.core.grouped_gemm.grouped_linear`."""
    cfg = resolve_config(config, backend=backend, out_dtype=out_dtype)
    if cfg.out_dtype is None:
        cfg = cfg.with_(out_dtype=x.dtype)
    num_groups = num_groups if num_groups is not None else w.shape[0]
    key = OpKey("gemm", "bf16")
    name = resolve(key, cfg.backend,
                   tile=(cfg, x.shape[0], x.shape[1], w.shape[2]))
    return _OPERATORS[key][name].run(
        x, w, group_sizes, num_groups=num_groups, config=cfg, plan=plan)


def grouped_gemm(x, w, group_sizes, *, backend: Optional[str] = None,
                 out_dtype=None, config: Optional[KernelConfig] = None,
                 plan: Optional[TilePlan] = None):
    """Unified high-level grouped GEMM: ``y[rows of g] = x[rows of g] @
    w[g]`` with the paper's fp8 recipe (1x128 activation tiles, 128x128
    weight blocks) applied before dispatch.

    x: [M, K] float; w: [G, K, N] float; group_sizes: [G] int.
    Not differentiable — training goes through
    :func:`repro.core.grouped_gemm.grouped_linear`, which wraps the same
    registry in a custom VJP.
    """
    a8, sa = _ref.quantize_tilewise_ref(x.astype(jnp.float32))
    b8, sb = jax.vmap(_ref.quantize_blockwise_ref)(w.astype(jnp.float32))
    # explicit out_dtype > config's pinned out_dtype > x.dtype
    cfg = resolve_config(config, backend=backend, out_dtype=out_dtype)
    if cfg.out_dtype is None:
        cfg = cfg.with_(out_dtype=x.dtype)
    return grouped_gemm_fp8(a8, sa, b8, sb, group_sizes,
                            num_groups=w.shape[0], config=cfg, plan=plan)


def grouped_gemm_wgrad(x, dy, group_sizes, *,
                       num_groups: Optional[int] = None,
                       backend: Optional[str] = None,
                       config: Optional[KernelConfig] = None,
                       out_dtype=None,
                       plan: Optional[TilePlan] = None):
    """Ragged-contraction grouped GEMM ``dw[g] = x_g^T @ dy_g`` through
    the ``(wgrad, bf16)`` operator.

    x: [M, K] float; dy: [M, N] float; group_sizes: [G] int,
    ``sum <= M`` (tail rows are excluded from the contraction).  Returns
    [G, K, N] (default f32 — wgrad is the highest-precision GEMM of the
    step).  ``plan`` is the routing decision's :class:`TilePlan` — the
    same object the forward/dgrad GEMMs consumed; the schedule is
    orientation-agnostic, so nothing is rebuilt here.

    An *auto-resolved* plan backend whose tile shapes don't divide
    (K, N) falls back to the first tile-free backend (the bf16 path calls
    in with arbitrary model dims); an explicitly requested one raises.
    """
    cfg = resolve_config(config, backend=backend, out_dtype=out_dtype)
    if cfg.out_dtype is None:
        cfg = cfg.with_(out_dtype=jnp.float32)
    num_groups = num_groups if num_groups is not None \
        else group_sizes.shape[0]
    key = OpKey("wgrad", "bf16")
    name = resolve(key, cfg.backend,
                   tile=(cfg, x.shape[0], x.shape[1], dy.shape[1]))
    return _OPERATORS[key][name].run(
        x, dy, group_sizes, num_groups=num_groups, config=cfg, plan=plan)


def grouped_gemm_wgrad_fp8(x_fp8, s_x, dy_fp8, s_dy, group_sizes, *,
                           num_groups: Optional[int] = None,
                           backend: Optional[str] = None,
                           config: Optional[KernelConfig] = None,
                           out_dtype=None,
                           plan: Optional[TilePlan] = None):
    """fp8-operand ragged-contraction grouped GEMM
    ``dw[g] = dequant(x)_g^T @ dequant(dy)_g`` through the
    ``(wgrad, fp8)`` operator (arXiv 2505.20524's all-fp8 training step).

    x_fp8/s_x: [M, K] fp8 + [M, ceil(K/128)] f32 — the forward's quantized
    activation and its 1x128 tile scales (the VJP residual, NOT
    re-quantized here); dy_fp8/s_dy: [M, N] fp8 + [M, ceil(N/128)] f32 —
    the upstream gradient as the dgrad already quantized it.
    ``backend`` names the family-neutral engine (``"pallas"``,
    ``"pallas_interpret"``, ...); the OpKey precision selects the twin.
    Same fallback semantics as :func:`grouped_gemm_wgrad`: auto-resolved
    tile shapes that don't divide (K, N) fall back to a tile-free fp8
    entry, explicit requests raise.
    """
    cfg = resolve_config(config, backend=backend, out_dtype=out_dtype)
    if cfg.out_dtype is None:
        cfg = cfg.with_(out_dtype=jnp.float32)
    num_groups = num_groups if num_groups is not None \
        else group_sizes.shape[0]
    key = OpKey("wgrad", "fp8")
    name = resolve(key, cfg.backend,
                   tile=(cfg, x_fp8.shape[0], x_fp8.shape[1],
                         dy_fp8.shape[1]))
    return _OPERATORS[key][name].run(
        x_fp8, s_x, dy_fp8, s_dy, group_sizes, num_groups=num_groups,
        config=cfg, plan=plan)


def quantize_tilewise(x, *, backend: Optional[str] = None,
                      config: Optional[KernelConfig] = None):
    """1x128 per-tile fp8 activation quantization through the
    ``(quantize, fp8)`` operator.

    ``config`` (optional) routes an autotuned tile height
    (``op="quantize"`` in :func:`repro.kernels.plan.autotune`) into the
    kernel's ``block_m``; without one the kernel keeps its default.  The
    OUTPUT is tile-height-independent — per-row 1x128 scales don't care
    how rows are batched — so tuning only moves wall time.

    A pure-quantization call never *needs* a kernel backend — when
    *auto*-resolution fails (e.g. an installed default naming an
    unavailable backend), fall back to the XLA reference implementation
    instead of refusing work the ref path can always serve.  An
    explicitly requested unavailable backend still raises: the caller
    asked for that kernel, not a silent stand-in.
    """
    explicit = backend not in (None, "auto")
    key = OpKey("quantize", "fp8")
    try:
        name = resolve(key, backend)
    except BackendUnavailableError:
        if explicit:
            raise
        return _ref.quantize_tilewise_ref(x)
    return _OPERATORS[key][name].run(x, config=config)


def act_quantize(g, u=None, *, act: str = "silu_mul",
                 backend: Optional[str] = None,
                 config: Optional[KernelConfig] = None,
                 s_g=None, s_u=None):
    """Fused activation -> 1x128 fp8 quantization through the
    ``(act_quant, fp8)`` operator.

    ``act="silu_mul"`` computes ``silu(g) * u`` (the SwiGLU expert
    epilogue; ``u`` required); ``act="gelu"`` is unary (``u`` must be
    None).  Returns ``(q[M, K] fp8e4m3, s[M, K/128] f32)`` — the exact
    :func:`quantize_tilewise` output contract applied to the activation,
    so every existing GEMM consumer accepts it unchanged.

    With ``s_g`` (and ``s_u``) the operands are fp8 payloads + 1x128
    scales from the quantizing-epilogue producer
    (:func:`grouped_gemm_quant`): they dequantize on load inside the
    kernel, closing the fp8 hot path with no bf16 intermediate on either
    side of the activation.

    ``config`` routes an autotuned tile height (``op="act_quant"``) into
    the kernel's ``block_m``; the output is tile-height-independent.
    Same fallback semantics as :func:`quantize_tilewise`: auto-resolution
    failures fall back to the unfused reference (activation then
    ``quantize_tilewise_ref``), an explicitly requested unavailable
    backend raises.
    """
    explicit = backend not in (None, "auto")
    key = OpKey("act_quant", "fp8")
    try:
        name = resolve(key, backend)
    except BackendUnavailableError:
        if explicit:
            raise
        return _ref.act_quantize_ref(g, u, act, s_g=s_g, s_u=s_u)
    return _OPERATORS[key][name].run(g, u, act=act, config=config,
                                     s_g=s_g, s_u=s_u)


def quantize_blockwise(w, *, backend: Optional[str] = None):
    """128x128 weight quantization through the registry seam.

    No kernel backend implements this yet (weights are quantized once per
    step outside the hot loop, so XLA ref math is fine everywhere), but
    resolution runs here so a future quant kernel plugs in at ONE place
    and the batched path below inherits it.  Same refusal semantics as
    :func:`quantize_tilewise`: auto-resolution failures fall back to ref,
    an explicitly requested unavailable backend raises.
    """
    explicit = backend not in (None, "auto")
    try:
        resolve(OpKey("quantize", "fp8"), backend)
    except BackendUnavailableError:
        if explicit:
            raise
    return _ref.quantize_blockwise_ref(w)


def quantize_blockwise_batched(w, *, backend: Optional[str] = None):
    """[G, K, N] -> (fp8[G, K, N], f32[G, KB, NB]) — vmap of the
    registry-routed :func:`quantize_blockwise`, so a future quant kernel
    covers the batched (per-expert) path automatically."""
    return jax.vmap(
        lambda wg: quantize_blockwise(wg, backend=backend))(w)


if __name__ == "__main__":
    print(format_backend_matrix())
