"""TPCx-BB ``item``: dense item keys, uniform class and category ids.

The benchmark's copy of ``repro.data.synth.item`` as it stood when the
benchmark was written.  It reads no generation key of its spec.
"""
from __future__ import annotations

import numpy as np

SEED_OFFSET = 1
TINY = {"items": 120}
N_CLASSES = 16
N_CATEGORIES = 8


def make(scale: dict, spec: dict, seed: int) -> dict:
    n_items = scale["items"]
    rng = np.random.default_rng(seed)
    return {
        "i_item_sk": np.arange(n_items, dtype=np.int32),
        "i_class_id": rng.integers(1, N_CLASSES + 1, n_items).astype(np.int32),
        "i_category_id": rng.integers(1, N_CATEGORIES + 1,
                                      n_items).astype(np.int32),
    }
