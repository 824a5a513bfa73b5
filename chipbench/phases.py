#!/usr/bin/env python3
"""What the program records about itself, read for the benchmark.

* The phases of each request's ``QueryRecord`` (``queue_s``, ``plan_s``,
  ``lock_s``, ``run_s``, ``readback_s``, ...): ``mean_ms``.
* The physical operator of each device op: ``Lowered.op_scopes()`` of the
  plan that answered (``QueryRecord.plan``; one program per run, else no
  reading), over the trace's device seconds per HLO instruction
  (``trace.Reduced.top_ops``): ``operator_s``, ``operator_ms``.
* The program's host spans (``hf.*``, ``repro/core/spans.py``) in a
  profiler trace: ``read`` (host threads kept apart), ``span_totals``,
  ``idle_phases``.

A reading of something the program does not record (a program older than
its phases, spans and operator scopes) is None; nothing here raises for it.

    python3 chipbench/phases.py <trace dir or .xplane.pb> [--devices 0 ...]

prints a saved trace's ``hf.*`` span totals and its idle phases.
"""
from __future__ import annotations

import argparse
import glob
import os
import sys
from collections import defaultdict

import numpy as np

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
from chipbench import trace  # noqa: E402

PREFIX = "hf."
NO_QUERY = "no_query"


# -- QueryRecord phases ---------------------------------------------------------

def mean_ms(run, field: str) -> float | None:
    """Mean of ``QueryRecord.<field>`` (seconds) over the run's answered
    requests, in ms.  A record without ``queue_s`` is of a program older
    than its phases, whose ``plan_s`` left the fingerprint out on a hit:
    it reads as nothing, so no reading compares the two meanings."""
    vals = [getattr(r.record, field) for r in run.answered
            if hasattr(r.record, "queue_s") and hasattr(r.record, field)]
    return float(np.mean(vals)) * 1e3 if vals else None


# -- operators ------------------------------------------------------------------

def scopes(run) -> dict | None:
    """Instruction name -> operator label of the program that answered the
    run's requests.  None where none records one, or where programs with
    different maps answered: instruction names (``fusion.3``) repeat from
    one program to the next, and ``trace.Reduced.top_ops`` has already
    summed them over programs, so no map would put their time down
    right."""
    maps, seen = [], set()
    for r in run.answered:
        plan = getattr(r.record, "plan", None)
        if plan is None or id(plan) in seen or not hasattr(plan, "op_scopes"):
            continue
        seen.add(id(plan))
        m = plan.op_scopes()
        if m not in maps:
            maps.append(m)
    return maps[0] if len(maps) == 1 else None


def operator_s(top_ops, op_scopes: dict) -> dict:
    """Device seconds per operator label, and ``"unattributed"`` for ops
    of no operator, from ``trace.Reduced.top_ops`` (short names whose first
    word is the HLO instruction)."""
    out: dict = defaultdict(float)
    for short, s in top_ops:
        out[op_scopes.get(short.split(" ", 1)[0], "unattributed")] += s
    return dict(out)


def operator_ms(run, kind: str) -> float | None:
    """Device ms per answered request under the scopes of operators of
    class ``kind`` (``"MergeJoin"``), mean over the cell's chips."""
    if run.trace is None or not run.answered:
        return None
    sc = scopes(run)
    if not sc:
        return None
    s = sum(v for label, v in operator_s(run.trace.top_ops, sc).items()
            if label.rsplit(".", 1)[0] == kind)
    return s / len(run.answered) * 1e3 if s > 0 else None


# -- hf.* host spans in a trace ---------------------------------------------------

def read(path: str) -> dict:
    """``trace.read``, with host lines of one name (every Python thread's is
    ``python3``) kept apart as ``name``, ``name#1``, ..."""
    from jax.profiler import ProfileData
    planes: dict = {}
    for plane in ProfileData.from_file(_xplane_file(path)).planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            key, k = line.name, 0
            while key in lines:
                k += 1
                key = f"{line.name}#{k}"
            lines[key] = [(e.name, float(e.start_ns), float(e.end_ns))
                          for e in line.events]
    return planes


def _xplane_file(path: str) -> str:
    if not os.path.isdir(path):
        return path
    files = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"{len(files)} xplane files under {path}")
    return files[0]


def hf_spans(planes: dict) -> dict:
    """``{host line: [(name, start_ns, end_ns)]}`` of the ``hf.*`` spans,
    each line's sorted by start."""
    out = {}
    for pname, lines in planes.items():
        if trace.DEVICE_PLANE.match(pname):
            continue
        for lname, evs in lines.items():
            sp = sorted((e for e in evs if e[0].startswith(PREFIX)),
                        key=lambda e: e[1])
            if sp:
                out[f"{pname}:{lname}"] = sp
    return out


def span_totals(planes: dict) -> dict:
    """Seconds per ``hf.*`` span name, summed over threads."""
    out: dict = defaultdict(float)
    for evs in hf_spans(planes).values():
        for name, s, e in evs:
            out[name] += (e - s) * 1e-9
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def innermost(spans: dict, times) -> list[list[str]]:
    """For each of the sorted ``times``, per host line the innermost
    ``hf.*`` span running then (the one that started last)."""
    out = [[] for _ in times]
    for evs in spans.values():
        active, i = [], 0
        for k, t in enumerate(times):
            while i < len(evs) and evs[i][1] <= t:
                active.append(evs[i])
                i += 1
            active = [e for e in active if e[2] > t]
            if active:
                out[k].append(active[-1][0])
    return out


def idle_phases(planes: dict, device_ids) -> list[tuple[str, float]]:
    """The idle gaps between the device ops of the first of ``device_ids``
    (as ``trace.reduce`` finds them), each named by the innermost ``hf.*``
    span over its middle on each host thread (several joined by ``+``),
    or ``no_query`` where none runs: ``[(name, seconds)]``, largest
    first."""
    devs = trace.device_planes(planes, set(device_ids))
    if not devs or not any(devs.values()):
        raise ValueError("no device ops in the trace")
    cover = trace.union((s, e) for _, s, e in devs[sorted(devs)[0]])
    holes = [(e0, s1) for (_, e0), (s1, _) in zip(cover, cover[1:])]
    names = innermost(hf_spans(planes), [(e0 + s1) / 2 for e0, s1 in holes])
    out: dict = defaultdict(float)
    for (e0, s1), live in zip(holes, names):
        out["+".join(sorted(set(live))) or NO_QUERY] += (s1 - e0) * 1e-9
    return sorted(out.items(), key=lambda kv: -kv[1])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", help="a trace directory or .xplane.pb file")
    ap.add_argument("--devices", nargs="+", default=["0"])
    args = ap.parse_args(argv)
    planes = read(args.trace)
    print("hf.* spans (s):")
    for name, s in span_totals(planes).items():
        print(f"  {name} {s!r}")
    print("idle phases (s):")
    for name, s in idle_phases(planes, args.devices):
        print(f"  {name} {s!r}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
