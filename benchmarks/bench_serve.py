"""Serving-layer benchmark (beyond-paper Fig. 15): cold vs warm query mix
through a Session, plus the P -> P' resharding path.

``fig15_serve_cold`` times the FIRST execution of the Q26-ish mix (plan +
lower + compile + run); ``fig15_serve_warm`` times a later pass where every
query hits the session plan cache (rebind + replay only) — the steady-state
serving cost.  ``fig15_serve_reshard_2to4`` times re-entering a frame
persisted at P=2 on the full mesh via the on-device reshard (skipped below
4 devices).
"""
from __future__ import annotations

import numpy as np

from repro import hiframes as hf
from repro.configs.hiframes_tpcx import SF100
from repro.core.api import ExecConfig
from repro.launch.serve import build_mix, register_tables
from repro.runtime.session import Session

from .common import report, timeit


def run(scale: float = 0.25) -> None:
    with Session(ExecConfig()) as sess:
        # scale 1.0 = 120k store_sales rows, a thousandth of SF100
        register_tables(sess, SF100.scaled(scale * 1e-3))
        mix = build_mix(sess.table("store_sales"), sess.table("item"))

        def one_pass():
            return [sess.collect(q()) for q in mix.values()]

        # cold: dedicated cache-empty timing (no timeit warmup — warmup IS
        # the thing being measured), then steady-state through timeit.
        import time
        t0 = time.perf_counter()
        tables = one_pass()
        cold_us = (time.perf_counter() - t0) * 1e6
        recs = [t.query_record for t in tables]
        report(f"fig15_serve_cold_sf{scale}", cold_us,
               f"queries={len(recs)} compiles={sum(r.compiles for r in recs)}")

        us = timeit(one_pass, warmup=1, repeat=3)
        st = sess.stats()
        report(f"fig15_serve_warm_sf{scale}", us,
               f"hit_rate={st['plan_cache']['hits']}/"
               f"{st['plan_cache']['hits'] + st['plan_cache']['misses']} "
               f"speedup={cold_us / max(us, 1):.1f}x")


def run_reshard(scale: float = 0.25) -> None:
    import jax
    from jax.sharding import Mesh

    if jax.device_count() < 4:
        print("fig15_serve_reshard: skipped (<4 devices)")
        return
    from repro.data import synth
    from repro.runtime.reshard import reshard

    n = max(int(200_000 * scale), 2_000)
    ss = synth.store_sales(n, max(int(2_000 * scale), 64),
                           max(int(10_000 * scale), 128), seed=0)
    cfg2 = ExecConfig(mesh=Mesh(np.array(jax.devices()[:2]), ("data",)))
    cfg4 = ExecConfig(mesh=Mesh(np.array(jax.devices()[:4]), ("data",)))
    p2 = hf.table(ss, "ss").repartition("ss_item_sk").persist(
        cfg2, name="ss2")

    us = timeit(lambda: reshard(p2, 4, cfg4).node.columns["ss_item_sk"],
                warmup=1, repeat=3)
    report(f"fig15_serve_reshard_2to4_sf{scale}", us,
           f"rows={n} (on-device split + hash re-establish)")
