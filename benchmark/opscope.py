"""Device time per MTTKRP mode and phase, read from a profiler trace
(``.xplane.pb``).

The MTTKRP program names its phases with ``jax.named_scope``
(``mttkrp.mode<m>`` around ``mttkrp.operands`` / ``mttkrp.reduce`` /
``mttkrp.merge``, splatt_tpu/ops/mttkrp.py).  The scope path reaches the
trace as each device op's ``tf_op`` stat, on the op's entry in its
plane's ``event_metadata``.  ``jax.profiler.ProfileData`` does not expose
those stats, so this module reads the XSpace protobuf itself, with a
small wire-format reader: no TensorFlow, no dependency.

Only leaf ops count.  An op whose interval encloses another op on the
same ``XLA Ops`` line is a container (``while``, ``call``,
``conditional``): its time is its body's, which is counted op by op.
Each leaf op belongs to the program (``XLA Modules`` event) whose
interval holds it.  The window is the one ``tracereduce`` uses: from the
start of the first complete ``cpd.iter`` host span to the end of the
last.
"""

from __future__ import annotations

import bisect
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from tracereduce import DEVICE_PLANE, ITER_SPAN, _base

PHASES = ("operands", "reduce", "merge")
_PHASE = re.compile(r"(?:^|/)mttkrp\.(operands|reduce|merge)(?=/|:|$)")
_MODE = re.compile(r"(?:^|/)mttkrp\.mode(\d+)(?=/|:|$)")

# field numbers (tensorflow/tsl/profiler/protobuf/xplane.proto)
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_LINES, _PLANE_EVENT_MD, _PLANE_STAT_MD = 2, 3, 4, 5
_LINE_NAME, _LINE_TIMESTAMP_NS, _LINE_EVENTS = 2, 3, 4
_EVENT_MD_ID, _EVENT_OFFSET_PS, _EVENT_DURATION_PS = 1, 2, 3
_EVMD_NAME, _EVMD_STATS = 2, 5
_STAT_MD_ID, _STAT_STR, _STAT_REF = 1, 5, 7
_STATMD_NAME = 2
_MAP_KEY, _MAP_VALUE = 1, 2         # map<int64, ...> entries: id, value


def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _int64(v: int) -> int:
    """A varint read as a signed 64-bit id (map keys may be negative)."""
    return v - (1 << 64) if v >= 1 << 63 else v


def _fields(buf, i: int, end: int):
    """(field number, value) of one message: an int for a varint, an
    (start, end) span of `buf` for a length-delimited field."""
    while i < end:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            val = (i, i + n)
            i += n
        elif wire == 1:
            val, i = None, i + 8
        elif wire == 5:
            val, i = None, i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield num, val


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


@dataclass
class Line:
    name: str
    timestamp_ns: int
    events: List[Tuple[int, int, int]]   # (metadata id, offset ps, dur ps)


@dataclass
class Plane:
    name: str
    lines: List[Line] = field(default_factory=list)
    event_names: Dict[int, str] = field(default_factory=dict)
    tf_ops: Dict[int, str] = field(default_factory=dict)


def _line(buf, span) -> Line:
    name, ts, events = "", 0, []
    for num, val in _fields(buf, *span):
        if num == _LINE_NAME:
            name = _text(buf, val)
        elif num == _LINE_TIMESTAMP_NS:
            ts = val
        elif num == _LINE_EVENTS:
            mid = off = dur = 0
            for n2, v2 in _fields(buf, *val):
                if n2 == _EVENT_MD_ID:
                    mid = _int64(v2)
                elif n2 == _EVENT_OFFSET_PS:
                    off = v2
                elif n2 == _EVENT_DURATION_PS:
                    dur = v2
            events.append((mid, off, dur))
    return Line(name, ts, events)


def _plane(buf, span) -> Plane:
    plane = Plane(name="")
    raw_lines, stat_names = [], {}
    md_stats: Dict[int, List[Tuple[int, object]]] = {}
    for num, val in _fields(buf, *span):
        if num == _PLANE_NAME:
            plane.name = _text(buf, val)
        elif num == _PLANE_LINES:
            raw_lines.append(val)
        elif num == _PLANE_EVENT_MD:
            mid, name, stats = 0, "", []
            for n2, v2 in _fields(buf, *val):
                if n2 == _MAP_KEY:
                    mid = _int64(v2)
                if n2 != _MAP_VALUE:
                    continue
                for n3, v3 in _fields(buf, *v2):
                    if n3 == _EVMD_NAME:
                        name = _text(buf, v3)
                    elif n3 == _EVMD_STATS:
                        sid, sval = 0, None
                        for n4, v4 in _fields(buf, *v3):
                            if n4 == _STAT_MD_ID:
                                sid = _int64(v4)
                            elif n4 == _STAT_STR:
                                sval = _text(buf, v4)
                            elif n4 == _STAT_REF:
                                sval = ("ref", _int64(v4))
                        stats.append((sid, sval))
            plane.event_names[mid] = name
            md_stats[mid] = stats
        elif num == _PLANE_STAT_MD:
            sid, sname = 0, ""
            for n2, v2 in _fields(buf, *val):
                if n2 == _MAP_KEY:
                    sid = _int64(v2)
                if n2 != _MAP_VALUE:
                    continue
                for n3, v3 in _fields(buf, *v2):
                    if n3 == _STATMD_NAME:
                        sname = _text(buf, v3)
            stat_names[sid] = sname
    device = bool(DEVICE_PLANE.match(plane.name))
    if device or plane.name.startswith("/host:"):
        for val in raw_lines:
            line = _line(buf, val)
            if device and line.name not in ("XLA Ops", "XLA Modules"):
                continue
            plane.lines.append(line)
    for mid, stats in md_stats.items():
        for sid, sval in stats:
            if stat_names.get(sid) == "tf_op":
                if isinstance(sval, tuple):      # a reference to a name
                    sval = stat_names.get(sval[1], "")
                plane.tf_ops[mid] = sval or ""
    return plane


def read_space(path: str) -> List[Plane]:
    """The device and host planes of an XSpace file."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    return [_plane(buf, val) for num, val in _fields(buf, 0, len(buf))
            if num == _SPACE_PLANES]


Interval = Tuple[float, float, int]      # (start s, end s, metadata id)


def _intervals(line: Line) -> List[Interval]:
    t0 = line.timestamp_ns * 1e-9
    return [(t0 + off * 1e-12, t0 + (off + dur) * 1e-12, mid)
            for mid, off, dur in line.events]


def leaf_ops(ops: List[Interval]) -> Tuple[List[Interval], int]:
    """The ops that enclose no other op on their line, and how many
    containers were left out."""
    order = sorted(ops, key=lambda o: (o[0], -o[1]))
    container = [False] * len(order)
    stack: List[int] = []
    for i, (a, b, _) in enumerate(order):
        while stack and order[stack[-1]][1] <= a:
            stack.pop()
        if stack and b <= order[stack[-1]][1]:
            container[stack[-1]] = True
        stack.append(i)
    leaves = [o for o, c in zip(order, container) if not c]
    return leaves, sum(container)


def phase_of(tf_op: str) -> Tuple[Optional[int], Optional[str]]:
    """(mode, phase) named by a ``tf_op`` path: the innermost scope of
    each kind, None where the path names none."""
    modes = _MODE.findall(tf_op)
    phases = _PHASE.findall(tf_op)
    return (int(modes[-1]) if modes else None,
            phases[-1] if phases else None)


@dataclass
class Phases:
    """Device seconds over the traced window, summed over devices."""
    iterations: int
    window_s: float
    #: (program, mode, phase) -> seconds of leaf ops; mode and phase
    #: None where the op's tf_op path names no such scope
    seconds: Dict[Tuple[str, Optional[int], Optional[str]], float]
    containers: int                   # container ops left out

    def program_s(self, pattern: str) -> float:
        """Leaf-op seconds of the programs matching `pattern`."""
        rx = re.compile(pattern)
        return sum(s for (p, _, _), s in self.seconds.items()
                   if rx.search(p))

    def phase_s(self, phase: str, mode: Optional[int] = None) -> float:
        """Seconds under ``mttkrp.<phase>``, all modes or one."""
        return sum(s for (_, m, ph), s in self.seconds.items()
                   if ph == phase and (mode is None or m == mode))

    def table(self, pattern: str = r"mttkrp") -> List[Tuple[str, Dict]]:
        """Milliseconds per iteration, one row per mode with a column
        per phase, and a last row with what the matching programs ran
        under no phase scope."""
        per = 1e3 / self.iterations
        modes = sorted({m for (_, m, ph) in self.seconds
                        if ph is not None and m is not None})
        rows = [(f"mode{m}", {ph: per * self.phase_s(ph, m)
                              for ph in PHASES}) for m in modes]
        rx = re.compile(pattern)
        rest = sum(s for (p, _, ph), s in self.seconds.items()
                   if rx.search(p) and ph is None)
        rows.append(("unscoped", {"ops": per * rest}))
        return rows


def summarize(planes: List[Plane]) -> Optional[Phases]:
    """The window's device seconds per (program, mode, phase); None
    when the trace holds no complete iteration or no device."""
    its = []
    for p in planes:
        if p.name.startswith("/host:"):
            for line in p.lines:
                its += [(a, b) for a, b, mid in _intervals(line)
                        if p.event_names.get(mid) == ITER_SPAN]
    devices = [p for p in planes if DEVICE_PLANE.match(p.name)]
    if not its or not devices:
        return None
    t0, t1 = min(a for a, _ in its), max(b for _, b in its)
    seconds: Dict[Tuple[str, Optional[int], Optional[str]], float] = \
        defaultdict(float)
    containers = 0
    for p in devices:
        lines = {ln.name: ln for ln in p.lines}
        ops = _intervals(lines["XLA Ops"]) if "XLA Ops" in lines else []
        mods = sorted(_intervals(lines["XLA Modules"])
                      if "XLA Modules" in lines else [])
        starts = [a for a, _, _ in mods]
        leaves, n = leaf_ops(ops)
        containers += n
        for a, b, mid in leaves:
            if b <= t0 or a >= t1:
                continue
            k = bisect.bisect_right(starts, a) - 1
            prog = (_base(p.event_names.get(mods[k][2], ""))
                    if k >= 0 and a < mods[k][1] else "")
            mode, phase = phase_of(p.tf_ops.get(mid, ""))
            seconds[(prog, mode, phase)] += min(b, t1) - max(a, t0)
    return Phases(iterations=len(its), window_s=t1 - t0,
                  seconds=dict(seconds), containers=containers)


def load(path: str) -> Optional[Phases]:
    return summarize(read_space(path))
