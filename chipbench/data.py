"""Seeded host tables of a configuration, each made by its own file.

A configuration's ``tables`` name its tables in order, each with a spec:
the layout (``partition_by``, ``replicate``) and any generation keys the
table's file reads, such as a key column's skew (``{"zipf": 1.1}``).  The
file ``chipbench/tables/<table>.py`` exposes

* ``make(scale, spec, seed) -> {column: np.ndarray}``: the table at the
  configuration's ``scale`` (a dict of row and key counts);
* ``SEED_OFFSET``: added to the run's seed for this table, distinct among
  the tables of one configuration, so no two draw the same stream;
* ``TINY``: the scale keys it reads, at a size the CPU tests can hold.
"""
from __future__ import annotations

import numpy as np

from chipbench import load_module


def make_tables(root: str, tables: dict, scale: dict, seed: int) -> dict:
    """``{table: {column: array}}`` for the configuration's ``tables`` (name
    -> spec, in their order) at ``scale``, from ``seed``; the files are
    found under ``root``."""
    seed = int(seed) % 2**63          # any whole number; SeedSequence wants >= 0
    mods = {name: load_module(root, "tables", name) for name in tables}
    offsets = [m.SEED_OFFSET for m in mods.values()]
    if len(set(offsets)) != len(offsets):
        raise ValueError(f"tables {list(tables)} share a SEED_OFFSET "
                         f"({offsets}): they would draw the same stream")
    return {name: m.make(scale, tables[name], seed + m.SEED_OFFSET)
            for name, m in mods.items()}


def zipf_keys(rng, n: int, n_keys: int, s: float) -> np.ndarray:
    """``n`` int32 keys over ``0..n_keys-1`` with a bounded Zipf law of
    exponent ``s``: key ``k`` has the share ``(k+1)**-s / sum_j (j+1)**-s``.

    The counts are the law's expected counts, rounded by largest remainder,
    the same for every seed; ``rng`` only orders the rows.  So every seed
    gives the same hot keys and the same skew, and runs differ in order,
    not in work.
    """
    w = np.arange(1, n_keys + 1, dtype=np.float64) ** -float(s)
    want = n * w / w.sum()
    counts = np.floor(want).astype(np.int64)
    rest = np.argsort(counts - want, kind="stable")[:n - int(counts.sum())]
    counts[rest] += 1
    keys = np.repeat(np.arange(n_keys, dtype=np.int32), counts)
    return rng.permutation(keys)
