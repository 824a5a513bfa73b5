"""TPCx-BB ``web_clickstreams``: a Zipf-skewed item key, uniform users.

The table holds ``clicks_per_sale`` (from the spec) clicks for each
``store_sales`` row of the scale, rounded: the kit's own row count is not
at hand, so the clicks are sized from the sales.  ``wcs_item_sk`` follows
``data.zipf_keys`` with the exponent the spec gives (``{"zipf": 1.1}``):
item ``k`` holds the share ``(k+1)**-s`` of the clicks over the bounded
law's sum, the same counts on every seed.  ``wcs_user_sk`` is uniform over
the customers and never null.  Only the two columns Q05 reads are made.
"""
from __future__ import annotations

import numpy as np

from chipbench import data

SEED_OFFSET = 2
TINY = {"store_sales_rows": 6000, "items": 120, "customers": 300}


def rows(scale: dict, spec: dict) -> int:
    return int(round(spec["clicks_per_sale"] * scale["store_sales_rows"]))


def make(scale: dict, spec: dict, seed: int) -> dict:
    n_rows = rows(scale, spec)
    rng = np.random.default_rng(seed)
    return {
        "wcs_item_sk": data.zipf_keys(rng, n_rows, scale["items"],
                                      spec["zipf"]),
        "wcs_user_sk": rng.integers(0, scale["customers"],
                                    n_rows).astype(np.int32),
    }
