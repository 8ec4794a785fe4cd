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
chain, so nested time is counted once.  An instruction name is unique in
its module but not across modules, so device time is keyed by (module
event, op): each op event goes to the module event on the same device
that contains it in time (``reduce``).

Host spans: the harness's ``bench.*`` and the engine's ``engine.*``
(``repro.scopes.HOST_SPANS``) share the device trace's clock; an idle gap
is labelled by the span that is innermost (the shortest open) for the
largest part of it.

A program without the vocabulary (one that predates it) has empty chains
and every reader here returns None for it.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import re

from bench import trace_reduce

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


@dataclasses.dataclass
class Layered:
    window_s: float
    busy_s: float            # union of op intervals, summed over devices
    devices: int
    module_ops: dict         # (module event name, op) -> [count, seconds]
    idle_gaps: list          # [(innermost host span, seconds)], longest first


def _module_of(starts, mods, s):
    i = bisect.bisect_right(starts, s) - 1
    if i >= 0 and s < mods[i][2]:
        return mods[i][0]
    return None


def reduce(path: str, top_gaps: int = 10,
           require_device: bool = True) -> Layered:
    """Op time per (module, op) in the traced window, and the idle gaps
    labelled by the innermost ``bench.*`` or ``engine.*`` span."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    engine = set(host_spans())
    spans, devices = [], []
    for plane in data.planes:
        lines = {ln.name: ln for ln in plane.lines}
        if plane.name.startswith("/device:"):
            if trace_reduce.OPS_LINE in lines:
                devices.append((lines[trace_reduce.OPS_LINE],
                                lines.get(trace_reduce.MODULES_LINE)))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans += [ev for ev in trace_reduce._events(ln)
                          if ev[0].startswith("bench.") or ev[0] in engine]
    windows = [(s, e) for n, s, e in spans if n == trace_reduce.WINDOW]
    if len(windows) != 1:
        raise ValueError(f"{len(windows)} '{trace_reduce.WINDOW}' spans in "
                         f"{path}")
    lo, hi = windows[0]
    if not devices and require_device:
        raise ValueError(f"no device plane with an "
                         f"'{trace_reduce.OPS_LINE}' line in {path}")

    module_ops = collections.defaultdict(lambda: [0, 0.0])
    busy, gaps = 0.0, []
    for ops_line, mod_line in devices:
        mods = sorted((s, n, e) for n, s, e in
                      (trace_reduce._events(mod_line) if mod_line else ()))
        starts = [s for s, _, _ in mods]
        mods = [(n, s, e) for s, n, e in mods]
        intervals = []
        for name, s, e in trace_reduce._events(ops_line):
            module = _module_of(starts, mods, s)
            s, e = max(s, lo), min(e, hi)
            if e <= s:
                continue
            intervals.append((s, e))
            row = module_ops[(module, trace_reduce.op_name(name))]
            row[0] += 1
            row[1] += (e - s) * 1e-9
        busy += trace_reduce.union_length(intervals) * 1e-9
        gaps += trace_reduce._gaps(intervals, lo, hi)

    inner = [(n, s, e) for n, s, e in spans if n != trace_reduce.WINDOW]

    def label(gs, ge):
        """The span that is innermost for the largest part of the gap:
        at each instant the shortest span open then."""
        cover = [(n, max(s, gs), min(e, ge), e - s) for n, s, e in inner
                 if min(e, ge) > max(s, gs)]
        if not cover:
            return "host: outside any bench span"
        cuts = sorted({t for _, s, e, _ in cover for t in (s, e)})
        held = collections.defaultdict(int)
        for a, b in zip(cuts, cuts[1:]):
            open_ = [c for c in cover if c[1] <= a and b <= c[2]]
            if open_:
                held[min(open_, key=lambda c: c[3])[0]] += b - a
        return "host: " + max(held, key=held.get)

    gaps.sort(key=lambda g: g[0] - g[1])
    return Layered(window_s=(hi - lo) * 1e-9, busy_s=busy,
                   devices=len(devices), module_ops=dict(module_ops),
                   idle_gaps=[(label(s, e), (e - s) * 1e-9)
                              for s, e in gaps[:top_gaps]])


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


def table(layered: Layered, programs: dict, chains: dict,
          containers: dict) -> dict:
    """(module event, scope path) -> [count, seconds].  A leaf op's path
    is its chain joined by ``/`` (``-`` when unscoped); a container's is
    ``(container)``, an op the program's HLO lacks ``(not in HLO)`` and
    an op of a module whose HLO is not at hand ``(no program)``."""
    out = collections.defaultdict(lambda: [0, 0.0])
    for (module, op), (n, secs) in layered.module_ops.items():
        path = "(no program)"
        for key, name in programs.items():
            if key in chains and _of(module, name):
                if op in containers.get(key, ()):
                    path = "(container)"
                else:
                    ch = chains[key].get(op)
                    path = ("(not in HLO)" if ch is None
                            else "/".join(ch) or UNSCOPED)
                break
        row = out[(module, path)]
        row[0] += n
        row[1] += secs
    return dict(out)


def attribution(layered: Layered, programs: dict, hlo: dict,
                top: int = 5) -> dict:
    """The (module, scope) table of a traced window, the share of device
    busy time in leaf ops with no chain, and the largest such ops."""
    parsed = {k: hlo_chains(v) for k, v in hlo.items()}
    chains = {k: p.chains for k, p in parsed.items()}
    containers = {k: p.containers for k, p in parsed.items()}
    tab = table(layered, programs, chains, containers)
    scoped = sum(s for (_, path), (_, s) in tab.items()
                 if not path.startswith("(") and path != UNSCOPED)
    unscoped = collections.defaultdict(float)
    for (module, op), (_, secs) in layered.module_ops.items():
        key = next((k for k, name in programs.items()
                    if k in chains and _of(module, name)), None)
        if key is None or (op not in containers[key]
                           and not chains[key].get(op)):
            unscoped[(module, op)] += secs
    worst = sorted(unscoped.items(), key=lambda kv: -kv[1])[:top]
    share = (100.0 * (1.0 - scoped / layered.busy_s)
             if layered.busy_s else None)
    return {"chains": chains, "table": tab, "unscoped_share": share,
            "unscoped_top": [[m, op, s] for (m, op), s in worst]}
