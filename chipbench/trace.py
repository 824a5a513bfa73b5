"""Reduction of a profiler trace to the benchmark's device numbers.

``read`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into plain
tuples; ``reduce`` computes, from those alone:

* busy seconds: the union of the intervals in which an XLA op ran on a
  device, averaged over the cell's devices;
* seconds per op class (``OP_CLASSES``: sorts, all-to-alls, while loops),
  summed over the devices and averaged over them;
* the device ops that took most time, and the idle gaps between ops,
  each gap named by the benchmark's host spans (``bench.submit``,
  ``bench.result``, ``bench.to_numpy``) that cover its middle.

On a TPU each event of the ``XLA Ops`` line is named by its HLO
instruction (``%sort.16 = (s32[1200000]{...}, ...) sort(...), ...``), and
the ops of a ``while`` body are events inside the ``while`` event.  So a
class is matched on the opcode, and the ranking of ops leaves the
containers (``while``, ``conditional``, ``call``) out; their time is in the
ops they contain.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
# opcode -> class; the first pattern that matches wins
OP_CLASSES = {
    "all_to_all": re.compile(r"^all-to-all"),
    "sort": re.compile(r"^sort$"),
    "loop": re.compile(r"^while$"),
}
CONTAINERS = ("while", "conditional", "call")
HLO = re.compile(r"^%?(\S+) = (.*?) ([a-z][a-z0-9-]*)\(")


def read(path: str) -> dict:
    """``{plane name: {line name: [(event name, start_ns, end_ns), ...]}}``
    of the one ``.xplane.pb`` under ``path`` (a file or a directory)."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        files = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                          recursive=True)
        if len(files) != 1:
            raise FileNotFoundError(f"{len(files)} xplane files under {path}")
        path = files[0]
    planes: dict = {}
    for plane in ProfileData.from_file(path).planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            evs = lines.setdefault(line.name, [])
            evs.extend((e.name, float(e.start_ns), float(e.end_ns))
                       for e in line.events)
    return planes


def union(intervals) -> list[tuple[float, float]]:
    """Sorted, disjoint cover of ``(start, end)`` intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def parse_op(name: str) -> tuple[str, str]:
    """``(opcode, short name)`` of a device event: the HLO instruction's
    opcode, and its name, opcode, fusion kind and output shape without
    layouts.  A name that is not an HLO instruction is its own opcode."""
    m = HLO.match(name)
    if not m:
        return name, name
    shape = re.sub(r"\{[^{}]*\}", "", m.group(2))
    kind = re.search(r"kind=(k\w+)", name)
    short = " ".join(x for x in (m.group(1), m.group(3),
                                 kind and kind.group(1), shape[:60]) if x)
    return m.group(3), short


def op_class(opcode: str) -> str | None:
    for cls, pat in OP_CLASSES.items():
        if pat.search(opcode):
            return cls
    return None


@dataclass
class Reduced:
    busy_s: float                  # mean over devices
    window_s: float
    class_s: dict = field(default_factory=dict)   # mean over devices
    top_ops: list = field(default_factory=list)   # [(name, s)], mean
    idle_gaps: list = field(default_factory=list)  # [(host span, s)], summed
    n_devices: int = 0

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self, n: int = 10) -> dict:
        return {"device_ops": [[k, v] for k, v in self.top_ops[:n]],
                "idle_gaps": [[k, v] for k, v in self.idle_gaps[:n]]}


def device_planes(planes: dict, device_ids) -> dict:
    out = {}
    for name, lines in planes.items():
        m = DEVICE_PLANE.match(name)
        if m and m.group(1) in device_ids:
            out[m.group(1)] = lines.get(OPS_LINE, [])
    return out


def host_spans(planes: dict, span_names) -> list[tuple[str, float, float]]:
    names = set(span_names)
    return sorted((ev for lines in planes.items()
                   if not DEVICE_PLANE.match(lines[0])
                   for evs in lines[1].values()
                   for ev in evs if ev[0] in names), key=lambda e: e[1])


def name_gaps(spans, times) -> list[str]:
    """For each of the sorted ``times``, the benchmark spans running then
    (``spans`` sorted by start), joined by ``+``, or ``"no span"``."""
    out, active, i = [], [], 0
    for t in times:
        while i < len(spans) and spans[i][1] <= t:
            active.append(spans[i])
            i += 1
        active = [sp for sp in active if sp[2] > t]
        out.append("+".join(sorted({sp[0] for sp in active})) or "no span")
    return out


def reduce(planes: dict, device_ids, window_s: float,
           span_names=()) -> Reduced:
    devs = device_planes(planes, set(device_ids))
    if not devs or not any(devs.values()):
        raise ValueError("no device ops in the trace")
    n = len(devs)
    busy = 0.0
    cls_ns: dict = defaultdict(float)
    ops_ns: dict = defaultdict(float)
    for evs in devs.values():
        busy += sum(e - s for s, e in union((s, e) for _, s, e in evs))
        for name, s, e in evs:
            opcode, short = parse_op(name)
            c = op_class(opcode)
            if c:
                cls_ns[c] += e - s
            if opcode not in CONTAINERS:
                ops_ns[short] += e - s
    spans = host_spans(planes, span_names)
    gaps: dict = defaultdict(float)
    first = devs[sorted(devs)[0]]
    cover = union((s, e) for _, s, e in first)
    holes = [(e0, s1) for (_, e0), (s1, _) in zip(cover, cover[1:])]
    for (e0, s1), name in zip(holes, name_gaps(
            spans, [(e0 + s1) / 2 for e0, s1 in holes])):
        gaps[name] += s1 - e0
    top = sorted(((k, v / n * 1e-9) for k, v in ops_ns.items()),
                 key=lambda kv: -kv[1])
    return Reduced(
        busy_s=busy / n * 1e-9, window_s=window_s,
        class_s={c: cls_ns.get(c, 0.0) / n * 1e-9 for c in OP_CLASSES},
        top_ops=top,
        idle_gaps=sorted(((k, v * 1e-9) for k, v in gaps.items()),
                         key=lambda kv: -kv[1]),
        n_devices=n)
