"""Seeded TPCx-BB tables, the benchmark's own copy of the generators.

``store_sales`` and ``item`` follow ``repro.data.synth`` as it stood when
the benchmark was written; the copy keeps the yardstick fixed while the
program's generators change.
"""
from __future__ import annotations

import numpy as np

N_CLASSES = 16
N_CATEGORIES = 8


def store_sales(n_rows: int, n_items: int, n_customers: int,
                seed: int) -> dict:
    """Uniform item and customer keys."""
    rng = np.random.default_rng(seed)
    return {
        "ss_item_sk": rng.integers(0, n_items, n_rows).astype(np.int32),
        "ss_customer_sk": rng.integers(0, n_customers, n_rows).astype(np.int32),
        "ss_ticket_number": rng.integers(0, n_rows, n_rows).astype(np.int32),
        "ss_net_paid": rng.gamma(2.0, 30.0, n_rows).astype(np.float32),
    }


def item(n_items: int, seed: int) -> dict:
    rng = np.random.default_rng(seed)
    return {
        "i_item_sk": np.arange(n_items, dtype=np.int32),
        "i_class_id": rng.integers(1, N_CLASSES + 1, n_items).astype(np.int32),
        "i_category_id": rng.integers(1, N_CATEGORIES + 1,
                                      n_items).astype(np.int32),
    }


def make_tables(scale: dict, seed: int) -> dict:
    """The configuration's host tables (column dicts) from ``seed``."""
    seed = int(seed) % 2**63          # any whole number; SeedSequence wants >= 0
    return {
        "store_sales": store_sales(scale["store_sales_rows"], scale["items"],
                                   scale["customers"], seed),
        "item": item(scale["items"], seed + 1),
    }
