#!/usr/bin/env python3
"""Bring-up smoke of the TPCx-BB serving path on TPU chips.

    python3 chip_smoke.py              # one chip: phases A and B
    python3 chip_smoke.py --chips 4    # four chips: the mix over a 4-device mesh

It drives the path users call — ``Session`` -> ``register`` -> ``submit`` —
with the ``repro.launch.serve`` query mix (Q26, a per-item aggregate and a
global leaderboard rank) over synthetic TPCx-BB tables made from a seed at the
paper's SF100 row counts (``configs/hiframes_tpcx.py``), cut only where the
chip forces it; every cut is printed.  Every answer is checked against a plain
numpy computation over the same host tables.

One chip:
  * phase A — reference kernels (``use_pallas="off"``): the mix twice; the
    second pass must hit the plan cache for every query with no compile;
  * phase B — the same queries once with the Pallas kernels compiled for the
    chip (``use_pallas="compiled"``): answers must match phase A, every
    kernel must resolve to "compiled" and no ``degrade_*`` event may appear.

The sizes are ``V5E_SMOKE_FRACTION`` of SF100 (``configs/hiframes_tpcx.py``):
on one chip the cut keeps the run inside 20 minutes, compiles included; on
four chips it keeps the plans inside each chip's 16 GiB.

Four chips (``--chips 4``): the mix once on a 4-device mesh, where the hash
exchanges really run; the all-to-all count is read from the compiled HLO.

Timings printed here are set-up and smoke times, not benchmark numbers.  The
last line of standard output is ``{"ok": true, "device": {...}}``; on any
failure the script exits nonzero without printing it.  It fails unless JAX
finds a TPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import re
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
SEED = 0


def reference(ss: dict, it: dict) -> dict:
    """The mix's answers by plain numpy, keyed by query name; each answer is
    a column dict sorted by its key column."""
    cls = np.zeros(int(it["i_item_sk"].max()) + 1, np.int64)
    cls[it["i_item_sk"]] = it["i_class_id"]
    known = ss["ss_item_sk"] < len(cls)        # the inner join's survivors
    item = ss["ss_item_sk"][known]
    cust = ss["ss_customer_sk"][known]
    c = cls[item]
    count = np.bincount(cust)
    keep = np.nonzero(count > 4)[0]
    q26 = {"ss_customer_sk": keep, "c_i_count": count[keep],
           "id1": np.bincount(cust, weights=c == 1)[keep].astype(np.int64),
           "id2": np.bincount(cust, weights=c == 2)[keep].astype(np.int64)}

    paid = ss["ss_net_paid"].astype(np.float64)
    n_item = np.bincount(ss["ss_item_sk"])
    items = np.nonzero(n_item)[0]
    per_item = {"ss_item_sk": items, "n": n_item[items],
                "paid": np.bincount(ss["ss_item_sk"], weights=paid)[items]}

    n_cust = np.bincount(ss["ss_customer_sk"])
    custs = np.nonzero(n_cust)[0]
    spend = np.bincount(ss["ss_customer_sk"], weights=paid)[custs]
    leaderboard = {"ss_customer_sk": custs, "spend": spend}
    return {"q26": q26, "per_item": per_item, "leaderboard": leaderboard}


KEYS = {"q26": "ss_customer_sk", "per_item": "ss_item_sk",
        "leaderboard": "ss_customer_sk"}
EXACT = {"q26": ("c_i_count", "id1", "id2"), "per_item": ("n",),
         "leaderboard": ()}
FLOAT = {"q26": (), "per_item": ("paid",), "leaderboard": ("spend",)}


def by_key(name: str, got: dict) -> dict:
    order = np.argsort(got[KEYS[name]], kind="stable")
    return {k: np.asarray(v)[order] for k, v in got.items()}


def check(name: str, got: dict, want: dict, rtol: float) -> None:
    """Raise AssertionError unless ``got`` (sorted by key) answers ``want``:
    the same keys, equal counts, sums within ``rtol``; a leaderboard rank
    must be the SQL RANK() of its own spend, descending."""
    key = KEYS[name]
    if not np.array_equal(got[key], want[key]):
        raise AssertionError(f"{name}: key sets differ "
                             f"({len(got[key])} vs {len(want[key])} groups)")
    for c in EXACT[name]:
        if not np.array_equal(got[c].astype(np.int64),
                              np.asarray(want[c]).astype(np.int64)):
            bad = int(np.sum(got[c] != want[c]))
            raise AssertionError(f"{name}.{c}: {bad} groups differ")
    for c in FLOAT[name]:
        np.testing.assert_allclose(got[c], want[c], rtol=rtol,
                                   err_msg=f"{name}.{c}")
    if name == "leaderboard":
        s = got["spend"]
        rank = len(s) - np.searchsorted(np.sort(s), s, side="right") + 1
        if not np.array_equal(got["r"], rank):
            bad = int(np.sum(got["r"] != rank))
            raise AssertionError(f"leaderboard.r: {bad} ranks differ from "
                                 "RANK() over the returned spend")


class CompileClock:
    """Sums XLA backend compile seconds reported through jax.monitoring."""

    def __init__(self, jax):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if event.endswith("backend_compile_duration"):
            self.seconds += secs


def peak_gib(dev) -> str:
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 2**30:.3f} GiB"


def cold_pass(sess, mix, cfg, clock, label: str) -> dict:
    """Submit every query of the mix at once (their compiles overlap) and
    read each answer back to the host; returns name -> (answer, record,
    degrade events).  No result stays on the device."""
    c0, t0 = clock.seconds, time.perf_counter()
    futures = {name: sess.submit(make(), cfg) for name, make in mix.items()}
    out = {}
    for name in mix:
        t = futures.pop(name).result()
        rec = t.query_record
        out[name] = (by_key(name, t.to_numpy()), rec, degrade_events(t))
        del t
        print(f"{label} {name}: cache {rec.cache}, {rec.compiles} compiles, "
              f"plan+compile {rec.plan_s:.1f} s, run {rec.exec_s:.2f} s")
    print(f"{label}: {time.perf_counter() - t0:.1f} s wall, "
          f"{clock.seconds - c0:.1f} s of compiles (set-up)")
    return out


def warm_pass(sess, mix, cfg, label: str) -> dict:
    """Run the mix one query at a time, each timed from submit to its answer
    on the host; returns what :func:`cold_pass` returns."""
    out = {}
    for name, make in mix.items():
        t0 = time.perf_counter()
        t = sess.submit(make(), cfg).result()
        got = by_key(name, t.to_numpy())
        wall = time.perf_counter() - t0
        rec = t.query_record
        print(f"{label} {name}: {wall:.3f} s wall, cache {rec.cache}, "
              f"{rec.compiles} compiles, {len(got[KEYS[name]]):,} rows")
        out[name] = (got, rec, degrade_events(t))
        del t
    return out


def degrade_events(t) -> list:
    evs = list(getattr(t, "events", ()) or ())
    evs += list(getattr(t.query_record, "events", ()) or ())
    return [e for e in evs if e.kind.startswith("degrade_")]


def table_rows(tcfg, fraction: float) -> None:
    from repro.configs.hiframes_tpcx import SF100
    print(f"tables: store_sales {tcfg.store_sales_rows:,} rows, item "
          f"{tcfg.items:,}, customers {tcfg.customers:,}")
    if fraction != 1.0:
        print(f"cut: {fraction:g} of SF100 (store_sales "
              f"{SF100.store_sales_rows:,} -> {tcfg.store_sales_rows:,}, "
              f"item {SF100.items:,} -> {tcfg.items:,}, customers "
              f"{SF100.customers:,} -> {tcfg.customers:,})")


def setup(sess, fraction: float, clock) -> dict:
    """Register the tables at ``fraction`` of SF100; returns the numpy
    reference answers over the same host tables."""
    from repro.configs.hiframes_tpcx import SF100
    from repro.launch.serve import register_tables

    tcfg = SF100.scaled(fraction)
    table_rows(tcfg, fraction)
    c0, t0 = clock.seconds, time.perf_counter()
    ss, it = register_tables(sess, tcfg, seed=SEED)
    print(f"register: {time.perf_counter() - t0:.1f} s wall, "
          f"{clock.seconds - c0:.1f} s of compiles (set-up)")
    t0 = time.perf_counter()
    want = reference(ss, it)
    print(f"numpy reference: {time.perf_counter() - t0:.1f} s")
    return want


def check_all(label: str, answers: dict, want: dict) -> None:
    for name, (got, _, _) in answers.items():
        check(name, got, want[name], rtol=1e-4)
    print(f"{label}: every answer matches numpy")


def one_chip(mesh, fraction: float, clock) -> None:
    from repro.core.api import ExecConfig
    from repro.launch.serve import build_mix
    from repro.runtime.session import Session

    dev = mesh.devices.flat[0]
    with Session(ExecConfig(mesh=mesh, use_pallas="off")) as sess:
        want = setup(sess, fraction, clock)
        mix = build_mix(sess.table("store_sales"), sess.table("item"))

        check_all("phase A pass 1",
                  cold_pass(sess, mix, None, clock, "phase A pass 1"), want)
        a = warm_pass(sess, mix, None, "phase A pass 2")
        check_all("phase A pass 2", a, want)
        recs = [rec for _, rec, _ in a.values()]
        hits = sum(r.cache == "hit" for r in recs)
        compiles = sum(r.compiles for r in recs)
        print(f"phase A pass 2: {hits}/{len(mix)} cache hits, "
              f"{compiles} compiles")
        if hits != len(mix) or compiles != 0:
            raise AssertionError(
                f"serve contract: pass 2 had {hits}/{len(mix)} cache hits "
                f"and {compiles} compiles (want {len(mix)} and 0)")
        print(f"phase A peak_bytes_in_use: {peak_gib(dev)}")

        cfg_b = dataclasses.replace(sess.cfg, use_pallas="compiled")
        b = cold_pass(sess, mix, cfg_b, clock, "phase B")
        for name, (got, _, bad) in b.items():
            if bad:
                raise AssertionError(f"phase B {name}: degraded: "
                                     + "; ".join(e.render() for e in bad))
            check(name, got, a[name][0], rtol=1e-5)
        check_all("phase B", b, want)
        plans = [p for p in sess.plan_cache.plans()
                 if p.cfg.use_pallas == "compiled"]
        modes = {m for p in plans for m in p.kernels.kernel_modes.values()}
        traced = sorted({k for p in plans for k in p.traced_kernels})
        if len(plans) != len(mix) or modes != {"compiled"}:
            raise AssertionError(f"phase B: {len(plans)} compiled plans, "
                                 f"kernel modes {sorted(modes)}")
        if not traced:
            raise AssertionError("phase B: no Pallas kernel in its programs")
        print(f"phase B kernels (all compiled): {', '.join(traced)}")
        print(f"phase B peak_bytes_in_use: {peak_gib(dev)}")


def all_to_alls(lowered) -> int:
    """all-to-all instructions in the plan's compiled HLO."""
    return len(re.findall(r"\ball-to-all(?:-start)?\(", lowered.hlo_text()))


def four_chips(mesh, fraction: float, clock) -> None:
    from repro.core.api import ExecConfig
    from repro.launch.serve import build_mix
    from repro.runtime.session import Session

    with Session(ExecConfig(mesh=mesh, use_pallas="off")) as sess:
        want = setup(sess, fraction, clock)
        mix = build_mix(sess.table("store_sales"), sess.table("item"))
        check_all("4 chips", cold_pass(sess, mix, None, clock, "4 chips"),
                  want)
        total = 0
        for lowered in sess.plan_cache.plans():
            n = all_to_alls(lowered)
            total += n
            print(f"4 chips plan {lowered.root.short()}: {n} all-to-all in "
                  f"the compiled HLO, {lowered.pplan.collective_count()} "
                  "planned")
        if total == 0:
            raise AssertionError("4 chips: no all-to-all in any program")
        for d in mesh.devices.flat:
            print(f"4 chips {d}: peak_bytes_in_use {peak_gib(d)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases A and B on one chip; 4: the mix on a "
                         "4-chip mesh")
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        import jax
        from jax.sharding import Mesh
        from repro.configs.hiframes_tpcx import V5E_SMOKE_FRACTION
        from repro.runtime.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the program next to this script: "
              f"{e}", file=sys.stderr)
        return 2
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: {args.chips} chips asked, {len(devices)} found",
              file=sys.stderr)
        return 1
    print(f"compile cache: {enable_compile_cache()}")
    print(f"device: {devices[0].device_kind}, {len(devices)} visible, "
          f"mesh of {args.chips}")
    clock = CompileClock(jax)
    mesh = Mesh(np.array(devices[:args.chips]), ("data",))
    fraction = V5E_SMOKE_FRACTION[args.chips]
    t0 = time.perf_counter()
    if args.chips == 1:
        one_chip(mesh, fraction, clock)
    else:
        four_chips(mesh, fraction, clock)
    print(f"smoke: {time.perf_counter() - t0:.1f} s wall, "
          f"{clock.seconds:.1f} s compile")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
