"""Parser for post-optimization XLA HLO text (``compiled.as_text()``).

This is the TPU analogue of the assembly front-ends in ``repro.core.isa``:
HLO is the "assembly" XLA schedules onto the chip's engines.  The parser
extracts computations, ops, result shapes, operand def-use links, and the
attributes the analyses need (replica groups, called computations, dot
contraction dims).
"""

from __future__ import annotations

import math
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s4": 0.5, "u4": 0.5, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1, "bf16": 2, "f16": 2,
    "f32": 4, "f64": 8, "c64": 8, "c128": 16, "token": 0, "opaque": 0,
}

COLLECTIVE_OPS = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast",
)


@dataclass(frozen=True)
class Shape:
    dtype: str
    dims: Tuple[int, ...]

    @property
    def elements(self) -> int:
        n = 1
        for d in self.dims:
            n *= d
        return n

    @property
    def bytes(self) -> int:
        return int(self.elements * _DTYPE_BYTES.get(self.dtype, 4))


_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\](?:\{[^}]*\})?")


def parse_shapes(text: str) -> Tuple[Shape, ...]:
    """Parse one shape or a tuple of shapes from HLO type syntax."""
    shapes = []
    for m in _SHAPE_RE.finditer(text):
        dtype = m.group(1)
        if dtype not in _DTYPE_BYTES:
            continue
        dims = tuple(int(d) for d in m.group(2).split(",") if d != "")
        shapes.append(Shape(dtype=dtype, dims=dims))
    return tuple(shapes)


def _valid_taps(n_out: int, n_in: int, window: int, stride: int, pad_lo: int,
                lhs_dil: int, rhs_dil: int) -> int:
    """(output, window tap) pairs of one spatial dim that read a real input
    element rather than padding or a dilation hole."""
    last = (n_in - 1) * lhs_dil  # last real position in the dilated input
    period = lhs_dil // math.gcd(stride, lhs_dil)  # outputs between hits
    count = 0
    for k in range(window):
        offset = k * rhs_dil - pad_lo  # position read by output 0
        lo = max(0, -(offset // stride))  # first output reading >= 0
        hi = min(n_out - 1, (last - offset) // stride)  # last reading <= last
        for first in range(lo, min(lo + period, hi + 1)):
            if (first * stride + offset) % lhs_dil == 0:
                count += (hi - first) // period + 1
    return count


@dataclass
class HLOOp:
    name: str
    opcode: str
    shapes: Tuple[Shape, ...]
    operands: Tuple[str, ...]
    attrs: str = ""
    is_root: bool = False
    raw: str = ""

    @property
    def result_bytes(self) -> int:
        return sum(s.bytes for s in self.shapes)

    @property
    def is_collective(self) -> bool:
        base = self.opcode.replace("-start", "").replace("-done", "")
        return base in COLLECTIVE_OPS

    @property
    def called_computations(self) -> Tuple[str, ...]:
        names = []
        for key in ("calls=", "to_apply=", "body=", "condition=", "branch_computations="):
            for m in re.finditer(re.escape(key) + r"\{?%?([\w.\-]+)", self.attrs):
                names.append(m.group(1))
        return tuple(names)

    def _attr_computation(self, key: str) -> Optional[str]:
        m = re.search(re.escape(key) + r"%?([\w.\-]+)", self.attrs)
        return m.group(1) if m else None

    @property
    def body_computation(self) -> Optional[str]:
        return self._attr_computation("body=")

    @property
    def condition_computation(self) -> Optional[str]:
        return self._attr_computation("condition=")

    @property
    def known_trip_count(self) -> Optional[int]:
        """XLA-recorded trip count (backend_config) for while ops."""
        m = re.search(r"known_trip_count[^0-9]*(\d+)", self.attrs)
        return int(m.group(1)) if m else None

    def replica_group_size(self, num_partitions: int) -> int:
        """Number of participants per replica group."""
        m = re.search(r"replica_groups=\[(\d+),(\d+)\]<=", self.attrs)
        if m:
            return int(m.group(2))
        m = re.search(r"replica_groups=\{\{([\d,]+)\}", self.attrs)
        if m:
            return len(m.group(1).split(","))
        return num_partitions

    def conv_macs_per_output(self, lhs_shape: Optional[Shape],
                             rhs_shape: Optional[Shape]) -> float:
        """Multiply-adds behind each convolution output element, on average.

        The TPU compiler writes every dot as a convolution and encodes batch
        dims as spatial ones whose window mostly lands on padding or on the
        holes of a dilation, so the count is the kernel's input features
        times, per spatial dim, the window taps that land on real input."""
        labels = re.search(r"dim_labels=(\w+)_(\w+)->(\w+)", self.attrs)
        out_shape = self.shapes[0] if self.shapes else None
        if not labels or lhs_shape is None or rhs_shape is None \
                or out_shape is None:
            return float(math.prod(self._window_attr("size")))
        lhs_l, rhs_l, out_l = labels.groups()
        macs = float(rhs_shape.dims[rhs_l.index("i")])
        sizes = self._window_attr("size")
        strides = self._window_attr("stride")
        lhs_dil = self._window_attr("lhs_dilate")
        rhs_dil = self._window_attr("rhs_dilate")
        pads = re.search(r"pad=([\d_x-]+)", self.attrs)
        pad_lo = [int(p.split("_")[0]) for p in pads.group(1).split("x")] \
            if pads else []
        for d, window in enumerate(sizes):
            n_out = out_shape.dims[out_l.index(str(d))]
            n_in = lhs_shape.dims[lhs_l.index(str(d))]
            taps = _valid_taps(n_out, n_in, window,
                               strides[d] if d < len(strides) else 1,
                               pad_lo[d] if d < len(pad_lo) else 0,
                               lhs_dil[d] if d < len(lhs_dil) else 1,
                               rhs_dil[d] if d < len(rhs_dil) else 1)
            macs *= taps / max(n_out, 1)
        return macs

    def _window_attr(self, key: str) -> Tuple[int, ...]:
        m = re.search(r"window=\{[^}]*\b" + key + r"=([\dx]+)", self.attrs)
        if not m:
            return ()
        return tuple(int(v) for v in m.group(1).split("x"))

    def dot_contracting(self, lhs_shape: Optional[Shape]) -> int:
        """Product of the LHS contracting dims of a dot (for FLOP counts)."""
        m = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", self.attrs)
        if not m or lhs_shape is None:
            return 0
        k = 1
        for d in m.group(1).split(","):
            if d != "":
                k *= lhs_shape.dims[int(d)]
        return k


@dataclass
class HLOComputation:
    name: str
    ops: List[HLOOp] = field(default_factory=list)
    params: List[HLOOp] = field(default_factory=list)

    @property
    def root(self) -> Optional[HLOOp]:
        for op in self.ops:
            if op.is_root:
                return op
        return self.ops[-1] if self.ops else None

    def op_by_name(self, name: str) -> Optional[HLOOp]:
        for op in self.ops:
            if op.name == name:
                return op
        return None


@dataclass
class HLOModule:
    name: str
    computations: Dict[str, HLOComputation]
    entry_name: str
    num_partitions: int = 1

    @property
    def entry(self) -> HLOComputation:
        return self.computations[self.entry_name]

    def collective_ops(self, computation: Optional[str] = None) -> List[HLOOp]:
        comps = (
            [self.computations[computation]] if computation
            else list(self.computations.values())
        )
        return [op for c in comps for op in c.ops if op.is_collective]


_COMP_HEADER_RE = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->\s*.*\{\s*$")
# Result type matched non-greedily up to the first " opcode(" — robust to
# tuple types containing "/*index=N*/" comments.
_OP_RE = re.compile(
    r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=\s*(.+?)\s+([\w\-]+)\((.*)$"
)
_OPERAND_RE = re.compile(r"%([\w.\-]+)")


def _split_op_line(rest: str) -> Tuple[str, str]:
    """Split ``operands), attrs`` at the closing paren of the operand list."""
    depth = 1
    for i, ch in enumerate(rest):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                return rest[:i], rest[i + 1:]
    return rest, ""


def parse_hlo(text: str) -> HLOModule:
    module_name = "module"
    num_partitions = 1
    m = re.search(r"HloModule\s+([\w.\-]+)", text)
    if m:
        module_name = m.group(1)
    m = re.search(r"num_partitions=(\d+)", text)
    if m:
        num_partitions = int(m.group(1))

    computations: Dict[str, HLOComputation] = {}
    entry_name = ""
    current: Optional[HLOComputation] = None

    for line in text.splitlines():
        stripped = line.strip()
        if not stripped:
            continue
        if current is None:
            hm = _COMP_HEADER_RE.match(stripped)
            if hm and "=" not in stripped.split("(")[0]:
                current = HLOComputation(name=hm.group(2))
                if hm.group(1):
                    entry_name = hm.group(2)
                continue
            continue
        if stripped == "}":
            computations[current.name] = current
            current = None
            continue
        om = _OP_RE.match(stripped)
        if not om:
            continue
        is_root = bool(om.group(1))
        name = om.group(2)
        shapes = parse_shapes(om.group(3))
        opcode = om.group(4)
        operand_str, attrs = _split_op_line(om.group(5))
        operands = tuple(_OPERAND_RE.findall(operand_str)) if opcode != "parameter" else ()
        op = HLOOp(
            name=name, opcode=opcode, shapes=shapes, operands=operands,
            attrs=attrs.strip().lstrip(","), is_root=is_root, raw=stripped,
        )
        current.ops.append(op)
        if opcode == "parameter":
            current.params.append(op)

    if current is not None:  # unterminated trailing computation
        computations[current.name] = current
    if not entry_name and computations:
        entry_name = list(computations)[-1]
    return HLOModule(
        name=module_name,
        computations=computations,
        entry_name=entry_name,
        num_partitions=num_partitions,
    )
