#!/usr/bin/env python3
"""The control of each cell's comparison: the reference put in the
program's place, one step below what the configuration states, which the
limits must refuse.

    python3 chipbench/control.py --workload per_item-serve --seeds 1 2 3

* float sums (``per_item``): the configuration states float32; the control
  computes the sums on the device in bfloat16 (inputs and accumulator);
* exact answers (``q26``): no float is compared, so the control breaks the
  guarantee of an answer exact over the registered table: it answers from
  a stale snapshot that misses the table's last ``STALE_ROWS`` rows.

Per seed it prints each compared number of the control beside its limit
and whether the limit refuses it.  The benchmark's runs never run it.  It
runs on whatever device JAX finds; ``chipbench/tests`` run it on the CPU at
a small size.
"""
from __future__ import annotations

import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from chipbench import run as bench  # noqa: E402  (sets up the program's path)

from chipbench import check, data  # noqa: E402

STALE_ROWS = 1200


def bf16_sums(host: dict) -> dict:
    """``per_item`` computed in bfloat16 on the default device."""
    import jax
    import jax.numpy as jnp
    ss = host["store_sales"]
    k = jnp.asarray(ss["ss_item_sk"])
    n_seg = int(ss["ss_item_sk"].max()) + 1
    paid = jax.ops.segment_sum(jnp.asarray(ss["ss_net_paid"], jnp.bfloat16),
                               k, num_segments=n_seg)
    n = jax.ops.segment_sum(jnp.ones_like(k), k, num_segments=n_seg)
    n = np.asarray(n)
    items = np.nonzero(n)[0]
    return {"ss_item_sk": items, "n": n[items],
            "paid": np.asarray(paid.astype(jnp.float32))[items]}


def stale(cell, host: dict) -> dict:
    """The query's reference over the table without its last rows."""
    ss = {c: v[:-STALE_ROWS] for c, v in host["store_sales"].items()}
    return cell.query.reference({**host, "store_sales": ss})


def control_answer(cell, host: dict) -> dict:
    if cell.query.FLOAT:
        if cell.traffic["query"] != "per_item":
            raise NotImplementedError(
                f"no bfloat16 control for {cell.traffic['query']!r}")
        return bf16_sums(host)
    return stale(cell, host)


def readings(cell, seed: int, scale: dict | None = None) -> dict:
    """The control's compared numbers on the tables of ``seed``."""
    host = data.make_tables(scale or cell.config["scale"], seed)
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
