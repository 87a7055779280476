"""Capture a profiler trace of a short window, and reduce it to numbers.

Capture (``capture``) wraps the traced work in ``jax.profiler`` and one host
span, ``window``, whose length is the traced window.  The reduction reads
the ``.xplane.pb`` file with ``jax.profiler.ProfileData`` alone:

- device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
  event per operation run (named by its HLO text, ``%fusion.2 = ...``) and
  their ``XLA Modules`` line one event per program run (named
  ``<module>(<fingerprint>)``);
- host spans are events of the host plane ``/host:CPU``: the benchmark's
  own ``jax.profiler.TraceAnnotation`` around each call it makes into the
  program (``generate``, ``data.next``, ``step``, ...), on the line of the
  thread that made them.

All events share one clock.  Busy time is the union of a device's operation
intervals inside the window (overlapping operations count once); idle is
the rest.  Collective time is the union of collective operations, and the
exposed part of it the share during which no other operation runs on that
device.  Each idle gap is labelled by the benchmark span that overlaps it
most, or ``none``.
"""

from __future__ import annotations

import bisect
import contextlib
import glob
import os
import re
import shutil
import tempfile
from dataclasses import dataclass, field
from typing import Dict, Iterable, Iterator, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]  # (start_ns, end_ns)

WINDOW_SPAN = "window"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OP_NAME = re.compile(r"^%?([^\s=]+)")
_MODULE_NAME = re.compile(r"^(.*?)(\(\d+\))?$")


@dataclass
class DeviceTrace:
    ops: List[Tuple[str, str, Interval]] = field(default_factory=list)
    # (module, op, interval) for each operation run
    modules: List[Tuple[str, Interval]] = field(default_factory=list)


@dataclass
class Trace:
    window: Interval
    devices: Dict[int, DeviceTrace]
    host_spans: List[Tuple[str, Interval]]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint intervals covering exactly what the input covers."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def length(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals: Iterable[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """``a`` minus ``b``; both sorted and disjoint (as ``union`` gives)."""
    out: List[Interval] = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def op_name(hlo_text: str) -> str:
    m = _OP_NAME.match(hlo_text.strip())
    return m.group(1) if m else hlo_text


def module_name(event_name: str) -> str:
    return _MODULE_NAME.match(event_name).group(1)


def is_collective(op: str) -> bool:
    return op.startswith(COLLECTIVES)


def load(path: str, spans: Optional[Sequence[str]] = None) -> Trace:
    """Read one ``.xplane.pb``.  ``spans``: the host span names to keep
    (default: every event of the host's ``python`` line)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[int, DeviceTrace] = {}
    host: List[Tuple[str, Interval]] = []
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(int(m.group(1)), DeviceTrace())
            lines = {line.name: line for line in plane.lines}
            mods = sorted(((module_name(e.name), (e.start_ns, e.end_ns))
                           for e in lines["XLA Modules"].events)
                          if "XLA Modules" in lines else [],
                          key=lambda x: x[1])
            dev.modules = mods
            starts = [iv[0] for _, iv in mods]
            if "XLA Ops" in lines:
                for e in lines["XLA Ops"].events:
                    i = bisect.bisect_right(starts, e.start_ns) - 1
                    mod = mods[i][0] if i >= 0 and \
                        e.start_ns < mods[i][1][1] else "?"
                    dev.ops.append((mod, op_name(e.name),
                                    (e.start_ns, e.end_ns)))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                # The main thread's line is named after the interpreter
                # (``python``, ``python3``); with ``spans`` given, every
                # line is searched for those names.
                if spans is None and not line.name.startswith("python"):
                    continue
                for e in line.events:
                    if spans is None or e.name in spans \
                            or e.name == WINDOW_SPAN:
                        host.append((e.name, (e.start_ns, e.end_ns)))
    windows = [iv for name, iv in host if name == WINDOW_SPAN]
    if windows:
        window = windows[-1]
    else:  # a trace taken without ``capture``: its device activity
        ivs = [iv for d in devices.values() for _, _, iv in d.ops]
        if not ivs:
            raise ValueError(f"{path}: no device operations and no window")
        window = (min(s for s, _ in ivs), max(e for _, e in ivs))
    host = [(n, iv) for n, iv in host if n != WINDOW_SPAN]
    return Trace(window=window, devices=devices, host_spans=host)


@dataclass
class Reduced:
    window_s: float
    busy_s: Dict[int, float]  # per device
    module_s: Dict[str, float]  # summed over devices, per module
    module_calls: Dict[str, int]  # runs of each module on the first device
    op_s: Dict[str, float]  # "module/op", summed over devices
    collective_s: Dict[int, float]
    collective_exposed_s: Dict[int, float]
    gaps: List[Tuple[str, float]]  # (host span, s), first device, longest 1st

    @property
    def busy_mean_s(self) -> float:
        return sum(self.busy_s.values()) / max(len(self.busy_s), 1)

    @property
    def n_devices(self) -> int:
        return len(self.busy_s)


def _label(gap: Interval, spans: Sequence[Tuple[str, Interval]]) -> str:
    best, best_overlap = "none", 0.0
    for name, (s, e) in spans:
        overlap = min(e, gap[1]) - max(s, gap[0])
        if overlap > best_overlap:
            best, best_overlap = name, overlap
    return best


def reduce(tr: Trace) -> Reduced:
    ns = 1e-9
    busy, coll, exposed = {}, {}, {}
    module_s: Dict[str, float] = {}
    module_calls: Dict[str, int] = {}
    op_s: Dict[str, float] = {}
    gaps: List[Tuple[str, float]] = []
    first = min(tr.devices) if tr.devices else None
    for idx, dev in sorted(tr.devices.items()):
        all_ops = union(clip((iv for _, _, iv in dev.ops), tr.window))
        busy[idx] = length(all_ops) * ns
        c = union(clip((iv for _, op, iv in dev.ops if is_collective(op)),
                       tr.window))
        compute = union(clip((iv for _, op, iv in dev.ops
                              if not is_collective(op)), tr.window))
        coll[idx] = length(c) * ns
        exposed[idx] = length(subtract(c, compute)) * ns
        for mod, iv in dev.modules:
            got = clip([iv], tr.window)
            if got:
                module_s[mod] = module_s.get(mod, 0.0) + length(got) * ns
                if idx == first:
                    module_calls[mod] = module_calls.get(mod, 0) + 1
        for mod, op, iv in dev.ops:
            got = clip([iv], tr.window)
            if got:
                key = f"{mod}/{op}"
                op_s[key] = op_s.get(key, 0.0) + length(got) * ns
        if idx == first:
            idle = subtract([tr.window], all_ops)
            gaps = sorted(((_label(g, tr.host_spans), (g[1] - g[0]) * ns)
                           for g in idle), key=lambda x: -x[1])
    return Reduced(window_s=(tr.window[1] - tr.window[0]) * ns, busy_s=busy,
                   module_s=module_s, module_calls=module_calls, op_s=op_s,
                   collective_s=coll, collective_exposed_s=exposed, gaps=gaps)


def breakdown(red: Reduced, top: int = 10) -> dict:
    """The result line's ``breakdown``: the device operations that took most
    time (seconds per device) and idle time by what the host was doing."""
    n = max(red.n_devices, 1)
    ops = sorted(red.op_s.items(), key=lambda x: -x[1])[:top]
    by_span: Dict[str, float] = {}
    for name, sec in red.gaps:
        by_span[name] = by_span.get(name, 0.0) + sec
    idle = sorted(by_span.items(), key=lambda x: -x[1])[:top]
    return {"device_ops": [[k, v / n] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in idle]}


@contextlib.contextmanager
def capture(tmp_root: Optional[str] = None) -> Iterator[List[str]]:
    """Trace the body; yields a list that holds the ``.xplane.pb`` path once
    the body has ended.  The directory is removed when the caller is done
    (``discard``)."""
    import jax
    from jax.profiler import TraceAnnotation

    out: List[str] = []
    tdir = tempfile.mkdtemp(prefix="chipbench-trace-", dir=tmp_root)
    out.append(tdir)
    jax.profiler.start_trace(tdir)
    try:
        with TraceAnnotation(WINDOW_SPAN):
            yield out
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"), recursive=True)
    if not found:
        raise RuntimeError(f"the profiler wrote no trace under {tdir}")
    out.append(found[0])


def discard(captured: List[str]) -> None:
    if captured:
        shutil.rmtree(captured[0], ignore_errors=True)
