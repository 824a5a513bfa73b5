#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest Poisson rate the program
sustains without a growing backlog.

    python3 chipbench/sweep.py --workload per_item-serve --seeds 7 8 --seconds 30

One process, one session: the cell's tables (from the first seed) and query
are set up once, a closed loop of one client gives the service rate, then
the open loop runs at fractions of it (``--fractions``), ``--seconds``
each, once with the arrivals of each seed.  Per rate and seed it
prints the requests offered and answered, p50 and p95 latency, the
generator's lateness, and the backlog's growth: the mean latency of the
last quarter of requests over that of the first.  The knee is read by
hand and written into the traffic file as a number; the benchmark's runs
never search for a rate.  It fails unless JAX finds a TPU.
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from chipbench import run as bench  # noqa: E402  (sets up the program's path)

from chipbench import loops  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--fractions", type=float, nargs="+",
                    default=[0.5, 0.7, 0.85, 1.0, 1.2])
    args = ap.parse_args(argv)
    cell = bench.Cell(bench.ROOT, args.workload)
    import jax
    import numpy as np
    from jax.sharding import Mesh
    from repro import hiframes as hf
    from repro.core.api import ExecConfig
    from repro.runtime.session import Session

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        bench.log(f"sweep: needs {cell.chips} TPU chip(s), JAX found "
                  f"{len(devices)} {devices[0].platform}")
        return 1
    bench.enable_compile_cache(jax)
    mesh = Mesh(np.array(devices[:cell.chips]), ("data",))
    host = bench.data.make_tables(cell.root, cell.config["tables"],
                                  cell.config["scale"], args.seeds[0])
    with Session(ExecConfig(mesh=mesh)) as sess:
        tables = bench.register(sess, hf, cell, host)

        def make():
            return cell.query.build(hf, tables)

        loops.warm(sess, make, 3)
        reqs = loops.closed(sess, make, args.seconds)
        done = [r for r in reqs if r.answer is not None]
        service = max(r.done for r in done) / len(done)
        print(f"closed loop: {len(done)} answers, {1e3 * service:.3f} ms "
              f"each, {1 / service:.2f} /s", flush=True)
        for f in args.fractions:
            rate = f / service
            for seed in args.seeds:
                report(sess, make, rate, f, seed, args.seconds)
    return 0


def report(sess, make, rate: float, f: float, seed: int,
           seconds: float) -> None:
    import numpy as np
    dues = loops.arrivals(rate, seconds, seed)
    reqs = loops.open_loop(sess, make, dues, seconds)
    ok = [r for r in reqs if r.answer is not None]
    lat = np.array([r.latency for r in ok]) * 1e3
    late = np.array([r.sent - r.due for r in reqs]) * 1e3
    q = max(len(lat) // 4, 1)
    growth = lat[-q:].mean() / lat[:q].mean()
    print(f"rate {rate:.2f}/s ({f:.2f} of service), seed {seed}: offered "
          f"{len(reqs)}, answered {len(ok)}, p50 "
          f"{np.percentile(lat, 50):.3f} ms, p95 "
          f"{np.percentile(lat, 95):.3f} ms, lateness mean "
          f"{late.mean():.3f} max {late.max():.3f} ms, backlog "
          f"growth {growth:.2f}x", flush=True)


if __name__ == "__main__":
    sys.exit(main())
