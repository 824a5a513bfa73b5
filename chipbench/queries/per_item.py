"""Per-item sales roll-up: store_sales grouped by item, the sum of net paid
and the number of sales of each item."""
from __future__ import annotations

import numpy as np

KEY = "ss_item_sk"
EXACT = ("n",)
FLOAT = ("paid",)


def build(hf, t: dict):
    return t["store_sales"].groupby("ss_item_sk").agg(
        paid=("ss_net_paid", "sum"), n=("ss_net_paid", "count"))


def reference(h: dict) -> dict:
    ss = h["store_sales"]
    paid = ss["ss_net_paid"].astype(np.float64)
    n = np.bincount(ss["ss_item_sk"])
    items = np.nonzero(n)[0]
    return {"ss_item_sk": items, "n": n[items],
            "paid": np.bincount(ss["ss_item_sk"], weights=paid)[items]}


def control(h: dict) -> dict:
    """The control (``chipbench/control.py``): the configuration states
    float32 sums; these are computed on the default device in bfloat16,
    inputs and accumulator."""
    import jax
    import jax.numpy as jnp
    ss = h["store_sales"]
    k = jnp.asarray(ss["ss_item_sk"])
    n_seg = int(ss["ss_item_sk"].max()) + 1
    paid = jax.ops.segment_sum(jnp.asarray(ss["ss_net_paid"], jnp.bfloat16),
                               k, num_segments=n_seg)
    n = jax.ops.segment_sum(jnp.ones_like(k), k, num_segments=n_seg)
    n = np.asarray(n)
    items = np.nonzero(n)[0]
    return {"ss_item_sk": items, "n": n[items],
            "paid": np.asarray(paid.astype(jnp.float32))[items]}
