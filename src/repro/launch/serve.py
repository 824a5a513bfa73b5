"""Serving entrypoint: a long-lived dataframe session over one mesh.

Boots a :class:`~repro.runtime.session.Session`, registers the synthetic
TPCx-BB tables with serving layouts (store_sales hash-partitioned on the
join key, item replicated), and replays a Q26-shaped query mix through the
session's plan cache.  Two modes:

  * default — one pass over the mix, then print session stats (plan-cache
    hit rate, compiles, collectives, per-query timings);
  * ``--smoke`` — the CI gate: replay the mix TWICE and assert the serving
    contract (docs/serving.md): every second-pass query HITS the plan
    cache with ZERO new compiles, and the second pass issues strictly
    fewer collectives than the first (pass 1 pays registration).  Exits
    nonzero on violation.

Tables are sized from the paper's SF100 row counts
(``configs/hiframes_tpcx.py``) times ``--scale``.  Run on N fake devices:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        PYTHONPATH=src python -m repro.launch.serve --smoke --scale 5e-5
"""
from __future__ import annotations

import argparse
import sys
import tempfile

from repro import hiframes as hf
from repro.configs.hiframes_tpcx import SF100, TpcxConfig
from repro.core.api import DataFrame, ExecConfig
from repro.data import synth
from repro.runtime.compile_cache import enable_compile_cache
from repro.runtime.session import Session


def build_mix(ss: DataFrame, it: DataFrame) -> dict:
    """The replayed query mix over ``store_sales`` and ``item``, by name:
    Q26 (join + aggregate + filter), a grouped top-up aggregate, and a
    global leaderboard rank — three distinct plan shapes exercising join,
    aggregation, and the global-window path."""

    def q26() -> DataFrame:
        j = ss.merge(it, on=("ss_item_sk", "i_item_sk"))
        c_i = (j.groupby("ss_customer_sk")
               .agg(c_i_count="count",
                    id1=hf.sum_(j["i_class_id"] == 1),
                    id2=hf.sum_(j["i_class_id"] == 2)))
        return c_i[c_i["c_i_count"] > 4]

    def per_item() -> DataFrame:
        return ss.groupby("ss_item_sk").agg(paid=("ss_net_paid", "sum"),
                                            n=("ss_net_paid", "count"))

    def leaderboard() -> DataFrame:
        per = ss.groupby("ss_customer_sk").agg(spend=("ss_net_paid", "sum"))
        return hf.rank(per, [], ["spend"], out="r", ascending=False)

    return {"q26": q26, "per_item": per_item, "leaderboard": leaderboard}


def register_tables(sess: Session, tcfg: TpcxConfig,
                    seed: int = 0) -> tuple[dict, dict]:
    """Register ``store_sales`` (hash-partitioned on the join key) and a
    replicated ``item`` at the row counts of ``tcfg``; returns the two host
    tables (column dicts) they were made from."""
    ss = synth.store_sales(tcfg.store_sales_rows, tcfg.items, tcfg.customers,
                           seed=seed, skew=tcfg.skew)
    it = synth.item(tcfg.items, seed=seed + 1)
    sess.register("store_sales", hf.table(ss, "store_sales"),
                  partition_by="ss_item_sk")
    sess.register("item", hf.table(it, "item").replicate())
    return ss, it


def run_pass(sess: Session, mix, repeats: int = 2) -> dict:
    """Submit the whole mix (each query ``repeats`` times) through the
    session's concurrent admission and collect per-pass totals."""
    futures = [sess.submit(q()) for _ in range(repeats)
               for q in mix.values()]
    recs = [f.result().query_record for f in futures]
    return {"queries": len(recs),
            "hits": sum(r.cache == "hit" for r in recs),
            "compiles": sum(r.compiles for r in recs),
            "collectives": sum(r.collectives for r in recs),
            "plan_s": sum(r.plan_s for r in recs),
            "exec_s": sum(r.exec_s for r in recs)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--scale", type=float, default=1e-3,
                    help="fraction of the SF100 row counts")
    ap.add_argument("--repeats", type=int, default=2,
                    help="times each mix query runs per pass")
    ap.add_argument("--session-dir", default=None,
                    help="stats sidecar directory (default: temp dir)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate: two passes; assert pass-2 hit rate 100%%,"
                         " zero compiles, strictly fewer collectives")
    args = ap.parse_args(argv)

    enable_compile_cache()
    sdir = args.session_dir or tempfile.mkdtemp(prefix="hf-serve-")
    cfg = ExecConfig()
    with Session(cfg, session_dir=sdir) as sess:
        register_tables(sess, SF100.scaled(args.scale))
        mix = build_mix(sess.table("store_sales"), sess.table("item"))
        p1 = run_pass(sess, mix, args.repeats)
        p1_total_coll = p1["collectives"] + sess.stats()[
            "register_collectives"]
        print(f"pass 1: {p1['queries']} queries, {p1['hits']} cache hits, "
              f"{p1['compiles']} compiles, "
              f"{p1_total_coll} collectives (incl. registration), "
              f"plan {p1['plan_s']*1e3:.0f} ms exec {p1['exec_s']*1e3:.0f} ms")
        if not args.smoke:
            st = sess.stats()
            pc = st["plan_cache"]
            print(f"plan cache: {pc['hits']} hits / {pc['misses']} misses, "
                  f"{pc['size']}/{pc['capacity']} entries")
            return 0
        p2 = run_pass(sess, mix, args.repeats)
        print(f"pass 2: {p2['queries']} queries, {p2['hits']} cache hits, "
              f"{p2['compiles']} compiles, {p2['collectives']} collectives, "
              f"plan {p2['plan_s']*1e3:.0f} ms exec {p2['exec_s']*1e3:.0f} ms")
        ok = True
        if p2["hits"] != p2["queries"]:
            print(f"SMOKE FAIL: pass-2 hit rate "
                  f"{p2['hits']}/{p2['queries']} != 100%")
            ok = False
        if p2["compiles"] != 0:
            print(f"SMOKE FAIL: pass-2 compiled {p2['compiles']} new "
                  "executables (expected 0)")
            ok = False
        if not p2["collectives"] < p1_total_coll:
            print(f"SMOKE FAIL: pass-2 collectives {p2['collectives']} not "
                  f"strictly fewer than pass-1 total {p1_total_coll}")
            ok = False
        print("serve smoke:", "PASS" if ok else "FAIL")
        return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
