"""TPCx-BB ``store_sales``: uniform item and customer keys.

The benchmark's copy of ``repro.data.synth.store_sales`` as it stood when
the benchmark was written, so the yardstick stays fixed while the
program's generators change.  It reads no generation key of its spec.
"""
from __future__ import annotations

import numpy as np

SEED_OFFSET = 0
TINY = {"store_sales_rows": 6000, "items": 120, "customers": 300}


def make(scale: dict, spec: dict, seed: int) -> dict:
    n_rows, n_items = scale["store_sales_rows"], scale["items"]
    rng = np.random.default_rng(seed)
    return {
        "ss_item_sk": rng.integers(0, n_items, n_rows).astype(np.int32),
        "ss_customer_sk": rng.integers(0, scale["customers"],
                                       n_rows).astype(np.int32),
        "ss_ticket_number": rng.integers(0, n_rows, n_rows).astype(np.int32),
        "ss_net_paid": rng.gamma(2.0, 30.0, n_rows).astype(np.float32),
    }
