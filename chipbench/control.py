#!/usr/bin/env python3
"""The control of each cell's comparison: the reference put in the
program's place, one step below what the configuration states, which the
limits must refuse.

    python3 chipbench/control.py --workload per_item-serve --seeds 1 2 3

* a query module's own ``control(host)``, where it defines one: for float
  sums (``per_item``), the sums computed on the device in bfloat16 where
  the configuration states float32;
* otherwise, for exact answers (``q26``): no float is compared, so the
  control breaks the guarantee of an answer exact over the registered
  tables: it answers from a stale snapshot that misses the last
  ``STALE_ROWS`` rows of the configuration's partitioned fact table.

Per seed it prints each compared number of the control beside its limit
and whether the limit refuses it.  The benchmark's runs never run it.  It
runs on whatever device JAX finds; ``chipbench/tests`` run it on the CPU at
a small size.
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from chipbench import run as bench  # noqa: E402  (sets up the program's path)

from chipbench import check, data  # noqa: E402

STALE_ROWS = 1200


def fact_table(cell) -> str:
    """The configuration's partitioned table: the one with ``partition_by``."""
    facts = [name for name, lay in cell.config["tables"].items()
             if lay.get("partition_by")]
    if len(facts) != 1:
        raise ValueError(f"{len(facts)} partitioned tables in "
                         f"{list(cell.config['tables'])}; a stale snapshot "
                         "needs one")
    return facts[0]


def stale(cell, host: dict) -> dict:
    """The query's reference over the fact table without its last rows."""
    fact = fact_table(cell)
    trimmed = {c: v[:-STALE_ROWS] for c, v in host[fact].items()}
    return cell.query.reference({**host, fact: trimmed})


def control_answer(cell, host: dict) -> dict:
    if hasattr(cell.query, "control"):
        return cell.query.control(host)
    if cell.query.FLOAT:
        raise NotImplementedError(
            f"no control for {cell.traffic['query']!r}: a query with float "
            "columns defines its own control(host)")
    return stale(cell, host)


def readings(cell, seed: int, scale: dict | None = None) -> dict:
    """The control's compared numbers on the tables of ``seed``."""
    host = data.make_tables(cell.root, cell.config["tables"],
                            scale or cell.config["scale"], seed)
    want = cell.query.reference(host)
    numbers, _ = check.compare(cell.query, [control_answer(cell, host)],
                               want, cell.limits)
    return numbers


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    import jax
    cell = bench.Cell(bench.ROOT, args.workload)
    print(f"device: {jax.devices()[0].device_kind}", flush=True)
    refused = True
    for seed in args.seeds:
        numbers = readings(cell, seed)
        _, table = check.judge(numbers, cell.limits, 0)
        over = [k for k, e in table.items() if e["value"] > e["limit"]]
        refused &= bool(over)
        print(f"seed {seed}: " + ", ".join(
            f"{k} {e['value']!r} (limit {e['limit']!r})"
            for k, e in table.items()) + f"; refused by {over}", flush=True)
    return 0 if refused else 1


if __name__ == "__main__":
    sys.exit(main())
