"""Layer 5 — retrace detector (REPRO-T01..T03).

Layer 1's event contracts pin *plan* discipline (one TilePlan build per
routing decision); this layer pins *compile* discipline: the jit caches
in front of every hot path must actually hit on shape-stable repeat
calls.  A silent retrace is invisible to correctness tests and to the
event bus — it only shows up as latency — yet it is exactly what sinks
a trace-once-per-bucket serving engine, and it is the failure mode the
paper's configure-once descriptor pool exists to rule out.

Mechanism: :func:`trace_jits` monkeypatches ``jax.jit`` so that every
function jitted inside the window carries a spy whose *Python body* runs
only when jax actually traces it (a jit cache miss).  Each trace emits a
``jit_trace`` event on the :mod:`repro.analysis.events` bus, tagged with
the wrapped function's name.  A :class:`CompileContract` then declares,
for one call sequence, the exact trace count each jitted entry point may
accumulate:

* **REPRO-T01** — ``grouped_linear`` / ``grouped_linear_ffn`` fwd+bwd
  compile once across shape-stable repeat calls (routing changes, i.e.
  new ``group_sizes`` values of the same shape, must not retrace);
* **REPRO-T02** — ``Engine.generate`` compiles exactly once per phase
  (one prefill trace, one decode-loop trace) across repeat generates;
* **REPRO-T03** — the padded baseline compiles once per M-bucket.

Product modules register their compile contracts at import time next to
their layer-1 ``Contract``s (``core/grouped_gemm.py``,
``serve/engine.py``, ``core/padding_baseline.py``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import importlib
import importlib.util
import sys
from collections import Counter
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from repro.analysis import events as ev
from repro.analysis.findings import Finding, relpath

#: modules whose import registers compile contracts (superset of the
#: layer-1 list: the padded baseline carries only a compile contract)
COMPILE_CONTRACT_MODULES = ("repro.core.grouped_gemm", "repro.core.moe",
                            "repro.serve.engine",
                            "repro.core.padding_baseline")


def _fn_name(fun) -> str:
    # functools.partial objects have no __name__; fall back to the
    # wrapped callable's (a jitted partial compiles as ``jit__unknown``)
    return (getattr(fun, "__name__", None)
            or getattr(getattr(fun, "func", None), "__name__", None)
            or "<anonymous>")


@contextlib.contextmanager
def trace_jits():
    """Monkeypatch ``jax.jit`` so every function jitted inside the window
    emits one ``jit_trace`` event per actual trace (jit cache miss).

    The spy wraps the to-be-jitted Python callable: jax only re-enters
    the Python body when the jit cache misses, so counting body entries
    counts compilations exactly.  Existing jitted functions (created
    before the window opened) are not observed — a compile contract must
    construct its subject inside the window (``Engine`` jits in
    ``__init__``, so building the engine inside is sufficient).
    """
    import jax
    real_jit = jax.jit

    def spying_jit(fun=None, **kw):
        if fun is None:                       # decorator form @jit(...)
            return lambda f: spying_jit(f, **kw)
        name = _fn_name(fun)

        @functools.wraps(fun, assigned=("__module__", "__qualname__",
                                        "__doc__"), updated=())
        def spy(*args, **kwargs):
            ev.emit("jit_trace", name=name)
            return fun(*args, **kwargs)
        spy.__name__ = name
        # static_argnames et al. resolve against the wrapper's signature
        # via functools.wraps' __wrapped__
        spy.__wrapped__ = fun
        return real_jit(spy, **kw)

    jax.jit = spying_jit
    try:
        yield
    finally:
        jax.jit = real_jit


@dataclasses.dataclass(frozen=True)
class CompileContract:
    """Exact compile counts for one call sequence.

    ``build`` returns ``(fn, calls)`` where ``calls`` is a sequence of
    argument tuples; the checker constructs everything and runs
    ``fn(*args)`` for each inside one :func:`trace_jits` window, then
    compares the per-name trace tally against ``expected``.  Jitted
    helpers not named in ``expected`` are unconstrained (PlanCache's
    schedule builds jit too, once per distinct group count)."""
    name: str
    description: str = ""
    build: "Optional[Callable[[], Tuple[Callable, Sequence[tuple]]]]" = None
    expected: "Dict[str, int]" = dataclasses.field(default_factory=dict)
    rule: str = "REPRO-T01"
    path: str = ""
    line: int = 1


COMPILE_CONTRACTS: "dict[str, CompileContract]" = {}
_loaded = False


def register_compile_contract(name: str, **kw) -> CompileContract:
    """Register a compile contract (product modules call this at import).
    The registration site becomes the finding location."""
    frame = sys._getframe(1)
    kw.setdefault("path", relpath(frame.f_code.co_filename))
    kw.setdefault("line", frame.f_lineno)
    c = CompileContract(name=name, **kw)
    COMPILE_CONTRACTS[name] = c
    return c


def load_registered() -> "dict[str, CompileContract]":
    global _loaded
    if not _loaded:
        for mod in COMPILE_CONTRACT_MODULES:
            importlib.import_module(mod)
        _loaded = True
    return COMPILE_CONTRACTS


def _tally_findings(tally: "Counter", expected: "Dict[str, int]",
                    c_name: str, rule: str, path: str,
                    line: int) -> "List[Finding]":
    findings = []
    for fn_name, want in sorted(expected.items()):
        got = tally.get(fn_name, 0)
        if got != want:
            verb = "retraced" if got > want else "traced"
            findings.append(Finding(
                rule, path, line,
                f"[{c_name}] {fn_name!r} {verb} {got} time(s) over the "
                f"call sequence; the jit cache must bound it to {want}",
                "shape-stable repeat calls must hit the jit cache — "
                "check for weak-type / dtype drift, python scalars in "
                "traced positions, or non-static aux arguments"))
    return findings


def check_compile_contract(c: CompileContract) -> "List[Finding]":
    """Run one compile contract: build + call sequence inside a single
    trace window, then compare trace tallies against ``expected``."""
    if c.build is None:
        raise ValueError(f"compile contract {c.name!r} has no build()")
    with trace_jits(), ev.capture() as captured:
        fn, calls = c.build()
        for args in calls:
            fn(*args)
    tally = Counter(e.data.get("name", "<anonymous>")
                    for e in ev.of_kind(captured, "jit_trace"))
    return _tally_findings(tally, c.expected, c.name, c.rule, c.path, c.line)


def run_registered(names: "Optional[Sequence[str]]" = None
                   ) -> "List[Finding]":
    registry = load_registered()
    if names is None:
        names = sorted(registry)
    findings: "List[Finding]" = []
    for name in names:
        findings.extend(check_compile_contract(registry[name]))
    return findings


def check_fixture(path: str) -> "List[Finding]":
    """Check a fixture module declaring ``EXPECTED_TRACES`` (name ->
    count) and ``run()`` (executed inside the trace window).  Used by the
    known-bad fixture tests: a shape-varying loop trips REPRO-T01."""
    spec = importlib.util.spec_from_file_location("_retrace_fixture", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    with trace_jits(), ev.capture() as captured:
        mod.run()
    tally = Counter(e.data.get("name", "<anonymous>")
                    for e in ev.of_kind(captured, "jit_trace"))
    return _tally_findings(tally, mod.EXPECTED_TRACES,
                           getattr(mod, "NAME", path), "REPRO-T01",
                           relpath(path), 1)
