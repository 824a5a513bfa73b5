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
