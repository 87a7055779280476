"""Device time by the program's named scopes, and idle time by its host spans.

Two reductions of a trace that ``trace.load`` has read, beside
``trace.reduce`` (``program_trace.py`` prints both for a cell):

- **Scope time.**  The compiled programs' text (``Compiled.as_text()``)
  gives each operation of a module its opcode and the ``op_name`` of its
  metadata, the path of ``jax.named_scope`` names (and of JAX's own
  levels) it was traced under.  Transform wrappers are stripped from the
  path: ``transpose(jvp(ssd))`` is ``ssd``.  Device time is summed per
  scope over leaf operations only: a ``while``, ``conditional`` or ``call``
  runs its body's operations inside its own interval, so counting it too
  would count that time twice.  An operation lies in the innermost of the
  asked-for scopes on its path, else in ``other``; an operation of the
  module that the text does not name is ``unmapped``.
- **Span attribution.**  Each idle gap of the first device goes to the
  innermost host span covering it: of the spans that overlap it most, the
  shortest.  A gap that no span overlaps goes to ``none``.
"""

from __future__ import annotations

import heapq
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Sequence, Tuple

from benchmarks.chip import trace

OTHER = "other"
NONE = "none"
SPANNING = ("while", "conditional", "call")  # opcodes that span a body

_MODULE = re.compile(r"^HloModule ([^\s,]+)")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+) = .*? ([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_WRAPPER = re.compile(r"[A-Za-z_][\w\-]*\(|\)")

OpMap = Dict[Tuple[str, str], Tuple[str, Tuple[str, ...]]]


def scope_path(op_name: str) -> Tuple[str, ...]:
    """The components of an ``op_name``, transform wrappers stripped."""
    return tuple(c for c in _WRAPPER.sub("", op_name).split("/") if c)


def op_map(texts: Iterable[str]) -> OpMap:
    """``(module, op) -> (opcode, scope path)`` for every instruction of the
    compiled programs' texts."""
    out: OpMap = {}
    for text in texts:
        module = None
        for line in text.splitlines():
            if module is None:
                m = _MODULE.match(line)
                module = m.group(1) if m else None
                continue
            m = _INSTR.match(line)
            if m:
                meta = _OP_NAME.search(line)
                out[(module, m.group(1))] = (
                    m.group(2), scope_path(meta.group(1)) if meta else ())
    return out


def module_of(text: str) -> str:
    """The module name a compiled program's text declares."""
    for line in text.splitlines():
        m = _MODULE.match(line)
        if m:
            return m.group(1)
    raise ValueError("no HloModule line in the compiled text")


def scopes_in(ops: OpMap, module: str) -> set:
    """Every path component of the module's operations."""
    return {c for (mod, _), (_, path) in ops.items() if mod == module
            for c in path}


@dataclass
class ScopeTime:
    seconds: Dict[str, float] = field(default_factory=dict)
    # per asked-for scope, and OTHER; leaf operations, summed over devices
    leaf_s: float = 0.0  # every leaf operation of the module, unmapped too
    unmapped_s: float = 0.0  # operations the compiled text does not name


def scope_time(tr: trace.Trace, ops: OpMap, module: str,
               scopes: Sequence[str]) -> ScopeTime:
    """Device time of ``module``'s leaf operations inside the window, by the
    innermost of ``scopes`` on each operation's path."""
    out = ScopeTime(seconds={s: 0.0 for s in (*scopes, OTHER)})
    wanted = set(scopes)
    for dev in tr.devices.values():
        for mod, op, iv in dev.ops:
            if mod != module:
                continue
            got = trace.clip([iv], tr.window)
            if not got:
                continue
            sec = trace.length(got) * 1e-9
            info = ops.get((mod, op))
            if info is None:
                if op.split(".")[0] in SPANNING:
                    continue
                out.unmapped_s += sec
                out.leaf_s += sec
                continue
            opcode, path = info
            if opcode in SPANNING:
                continue
            out.leaf_s += sec
            inner = [c for c in path if c in wanted]
            out.seconds[inner[-1] if inner else OTHER] += sec
    return out


def innermost(candidates: Sequence[Tuple[str, trace.Interval]],
              gap: trace.Interval) -> str:
    """The span of greatest overlap with ``gap``, the shortest of those;
    ``none`` where nothing overlaps."""
    best, best_overlap, best_len = NONE, 0.0, float("inf")
    for name, (s, e) in candidates:
        overlap = min(e, gap[1]) - max(s, gap[0])
        if overlap <= 0:
            continue
        if overlap > best_overlap or (overlap == best_overlap
                                      and e - s < best_len):
            best, best_overlap, best_len = name, overlap, e - s
    return best


def idle_by_span(tr: trace.Trace) -> Dict[str, float]:
    """Idle seconds of the first device inside the window, by innermost host
    span; every span name of the trace appears, and ``none``."""
    out = {name: 0.0 for name, _ in tr.host_spans}
    out[NONE] = 0.0
    if not tr.devices:
        return out
    dev = tr.devices[min(tr.devices)]
    busy = trace.union(trace.clip((iv for _, _, iv in dev.ops), tr.window))
    spans = sorted(tr.host_spans, key=lambda x: x[1][0])
    active: List[Tuple[float, int]] = []  # (end, index) of spans begun
    j = 0
    for gap in trace.subtract([tr.window], busy):  # in time order
        while j < len(spans) and spans[j][1][0] < gap[1]:
            heapq.heappush(active, (spans[j][1][1], j))
            j += 1
        while active and active[0][0] <= gap[0]:
            heapq.heappop(active)
        name = innermost([spans[k] for _, k in active], gap)
        out[name] += (gap[1] - gap[0]) * 1e-9
    return out

