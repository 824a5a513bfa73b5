"""Benchmark driver — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  --scale shrinks/grows datasets
(defaults are CPU-feasible stand-ins for the paper's cluster sizes);
--skip lets CI drop the slow scaling runs; --out additionally
writes the rows as JSON (the CI bench-smoke artifact).
"""
import argparse
import json
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.25)
    ap.add_argument("--skip", nargs="*", default=[],
                    choices=["relational", "multikey", "analytics", "udf",
                             "tpcx", "scaling", "kernels", "pallas_ab",
                             "validate", "serve", "serve_reshard"])
    ap.add_argument("--out", default=None,
                    help="write results as JSON to this path")
    args = ap.parse_args()

    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()

    from . import (bench_analytics, bench_kernels, bench_pallas_ab,
                   bench_relational, bench_scaling, bench_serve, bench_tpcx,
                   bench_udf, bench_validate)

    suites = {
        "relational": lambda: bench_relational.run(args.scale),
        "multikey": lambda: bench_relational.run_multikey(args.scale),
        "analytics": lambda: bench_analytics.run(args.scale),
        "udf": lambda: bench_udf.run(args.scale),
        "tpcx": lambda: bench_tpcx.run(args.scale),
        "kernels": lambda: bench_kernels.run(args.scale),
        "pallas_ab": lambda: bench_pallas_ab.run(args.scale),
        "validate": lambda: bench_validate.run(args.scale),
        "serve": lambda: bench_serve.run(args.scale),
        "serve_reshard": lambda: bench_serve.run_reshard(args.scale),
        "scaling": lambda: bench_scaling.run(args.scale),
    }
    print("name,us_per_call,derived")
    failed = []
    for name, fn in suites.items():
        if name in args.skip:
            continue
        try:
            fn()
        except Exception:
            failed.append(name)
            traceback.print_exc()
    if args.out:
        import os
        import platform

        from . import common
        rows = [{"name": n, "us_per_call": us, "derived": d}
                for (n, us, d) in common.ROWS]
        # host fingerprint: trend.py only enforces its regression gate
        # between snapshots from the same host — cross-machine absolute
        # timings are noise (see trend.py docstring).
        host = {"nproc": os.cpu_count(), "machine": platform.machine()}
        with open(args.out, "w") as f:
            json.dump({"scale": args.scale, "skipped": args.skip,
                       "failed": failed, "host": host, "rows": rows},
                      f, indent=2)
        print(f"wrote {len(rows)} rows to {args.out}", file=sys.stderr)
    if failed:
        sys.exit(f"benchmark suites failed: {failed}")


if __name__ == "__main__":
    main()
