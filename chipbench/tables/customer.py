"""TPCx-BB ``customer``: dense customer keys, each with a current
demographics row drawn uniformly over ``customer_demographics``' fixed
1,920,800 rows.  Only the two columns Q05 reads are made."""
from __future__ import annotations

import numpy as np

SEED_OFFSET = 3
TINY = {"customers": 300}
DEMOGRAPHICS = 2 * 5 * 7 * 20 * 4 * 7 * 7 * 7    # customer_demographics rows


def make(scale: dict, spec: dict, seed: int) -> dict:
    n = scale["customers"]
    rng = np.random.default_rng(seed)
    return {
        "c_customer_sk": np.arange(n, dtype=np.int32),
        "c_current_cdemo_sk": rng.integers(0, DEMOGRAPHICS,
                                           n).astype(np.int32),
    }
