"""Which named layer of the program each device op belongs to.

The program names its layers with ``jax.named_scope`` from one closed
vocabulary (``repro.scopes.VOCABULARY``, read here and copied nowhere).
A scope reaches the compiled HLO as part of each instruction's
``op_name`` metadata, the path of the JAX name stack: for example
``jit(train_step)/transpose(jvp())/while/body/moe.experts/quant.weights/
mul``.  An op's *chain* is the vocabulary names on that path, outer to
inner, with the transform wrappers (``jvp(...)``, ``transpose(...)``)
stripped.

A profile's op line holds one event per executed instruction, containers
included: a ``while``, ``conditional`` or ``call`` event spans the ops of
its body, which have events of their own.  Only leaf instructions get a
chain, so nested time is counted once.  Device time comes keyed by
(module event, op) from ``trace_reduce.reduce`` (``Reduced.module_ops``).

Host spans: the engine's ``engine.*`` (``repro.scopes.HOST_SPANS``) share
the device trace's clock with the harness's ``bench.*``; the harness
hands them to ``trace_reduce.reduce`` to label the idle gaps.

A program without the vocabulary (one that predates it) has empty chains
and every reader here returns None for it.
"""
from __future__ import annotations

import collections
import dataclasses
import re

CONTAINERS = frozenset({"while", "conditional", "call"})
UNSCOPED = "-"

_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$')
_OPCODE = re.compile(r'\s*([\w\-]+)\(')
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r'calls=%([\w.\-]+)')
_HEADER = re.compile(r'^(?:ENTRY\s+)?%([\w.\-]+)\s.*\{\s*$')
_WRAPPER = re.compile(r'[\w\-]+\(|\)')


def vocabulary() -> tuple:
    """The program's layer names; empty for a program that has none."""
    try:
        from repro.scopes import VOCABULARY
    except ImportError:
        return ()
    return tuple(VOCABULARY)


def host_spans() -> tuple:
    try:
        from repro.scopes import HOST_SPANS
    except ImportError:
        return ()
    return tuple(HOST_SPANS)


def chain(op_name: str, names) -> tuple:
    """The vocabulary names on an ``op_name`` path, outer to inner."""
    out = []
    for part in _WRAPPER.sub("", op_name).split("/"):
        if part in names and (not out or out[-1] != part):
            out.append(part)
    return tuple(out)


def _opcode(rest: str) -> str | None:
    """The opcode of an instruction's right-hand side (type, then
    ``opcode(operands)``); a tuple type holds spaces and parentheses."""
    if rest.startswith("("):
        depth = 0
        for i, c in enumerate(rest):
            depth += (c == "(") - (c == ")")
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.split(" ", 1)[1] if " " in rest else ""
    m = _OPCODE.match(rest)
    return m.group(1) if m else None


@dataclasses.dataclass(frozen=True)
class Program:
    chains: dict          # leaf instruction -> chain (() when unscoped)
    containers: frozenset  # while / conditional / call instructions


def hlo_chains(hlo_text: str, names=None) -> Program:
    """The chain of every leaf instruction of an optimized HLO module
    that can run as an op of its own: instructions inside fused
    computations are left out (their fusion is the op)."""
    names = vocabulary() if names is None else names
    rows, fused, comp = [], set(), None
    for line in hlo_text.splitlines():
        ins = _INSTR.match(line)
        if ins is None:
            head = _HEADER.match(line)
            if head:
                comp = head.group(1)
            continue
        opcode = _opcode(ins.group(2))
        if opcode == "fusion":
            fused.update(_CALLS.findall(line))
        op = _OP_NAME.search(line)
        rows.append((comp, ins.group(1), opcode,
                     chain(op.group(1), names) if op else ()))
    chains, containers = {}, set()
    for comp, name, opcode, ch in rows:
        if comp in fused:
            continue
        if opcode in CONTAINERS:
            containers.add(name)
        else:
            chains[name] = ch
    return Program(chains, frozenset(containers))


def _of(module_event, module_name) -> bool:
    """A module event (``jit_train_step(123)``) of the named program."""
    return module_event is not None and (
        module_event == module_name
        or module_event.startswith(module_name + "("))


def seconds_under(ctx, pred, program: str | None = None):
    """Device seconds of the leaf ops whose chain ``pred`` accepts, in
    the named program's modules (every program's without ``program``).
    None when the trace or the programs carry no chains, or when no op
    is accepted.  Reads ``ctx.trace.module_ops``, ``ctx.scopes``
    (program key -> instruction -> chain) and ``ctx.programs`` (program
    key -> module name)."""
    module_ops = getattr(ctx.trace, "module_ops", None)
    scopes = getattr(ctx, "scopes", None)
    if not module_ops or not scopes:
        return None
    keys = [program] if program is not None else list(scopes)
    total, hit = 0.0, False
    for (module, op), (_, secs) in module_ops.items():
        for key in keys:
            if key in scopes and _of(module, ctx.programs[key]):
                ch = scopes[key].get(op)
                if ch is not None and pred(ch):
                    total, hit = total + secs, True
                break
    return total if hit else None


def under(prefix: str):
    """Accepts a chain holding ``prefix`` itself or a name below it
    (``under("moe")`` takes every ``moe.*`` scope)."""
    return lambda ch: any(n == prefix or n.startswith(prefix + ".")
                          for n in ch)


def table(reduced, ctx) -> dict:
    """(module event, scope path) -> [count, seconds].  A leaf op's path
    is its chain joined by ``/`` (``-`` when unscoped); a container's is
    ``(container)``, an op the program's HLO lacks ``(not in HLO)`` and
    an op of a module whose HLO is not at hand ``(no program)``."""
    out = collections.defaultdict(lambda: [0, 0.0])
    for (module, op), (n, secs) in reduced.module_ops.items():
        path = "(no program)"
        for key, name in ctx.programs.items():
            if key in ctx.scopes and _of(module, name):
                if op in ctx.containers.get(key, ()):
                    path = "(container)"
                else:
                    ch = ctx.scopes[key].get(op)
                    path = ("(not in HLO)" if ch is None
                            else "/".join(ch) or UNSCOPED)
                break
        row = out[(module, path)]
        row[0] += n
        row[1] += secs
    return dict(out)


def attribution(reduced, ctx, top: int = 5) -> dict:
    """The (module, scope) table of a traced window, the share of device
    busy time (summed over devices) in leaf ops with no chain, and the
    largest such ops.  Reads ``ctx.programs``, ``ctx.scopes`` and
    ``ctx.containers`` (program key -> container instructions)."""
    tab = table(reduced, ctx)
    scoped = sum(s for (_, path), (_, s) in tab.items()
                 if not path.startswith("(") and path != UNSCOPED)
    unscoped = collections.defaultdict(float)
    for (module, op), (_, secs) in reduced.module_ops.items():
        key = next((k for k, name in ctx.programs.items()
                    if k in ctx.scopes and _of(module, name)), None)
        if key is None or (op not in ctx.containers[key]
                           and not ctx.scopes[key].get(op)):
            unscoped[(module, op)] += secs
    worst = sorted(unscoped.items(), key=lambda kv: -kv[1])[:top]
    busy = reduced.busy_s * reduced.devices
    share = 100.0 * (1.0 - scoped / busy) if busy else None
    return {"table": tab, "unscoped_share": share,
            "unscoped_top": [[m, op, s] for (m, op), s in worst]}
