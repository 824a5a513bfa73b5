"""Customer leaderboard: total spend per customer and its SQL RANK(),
descending, over all customers (a global window)."""
from __future__ import annotations

import numpy as np

KEY = "ss_customer_sk"
EXACT = ()
FLOAT = ("spend",)


def build(hf, t: dict):
    per = t["store_sales"].groupby("ss_customer_sk").agg(
        spend=("ss_net_paid", "sum"))
    return hf.rank(per, [], ["spend"], out="r", ascending=False)


def reference(h: dict) -> dict:
    ss = h["store_sales"]
    paid = ss["ss_net_paid"].astype(np.float64)
    n = np.bincount(ss["ss_customer_sk"])
    custs = np.nonzero(n)[0]
    return {"ss_customer_sk": custs,
            "spend": np.bincount(ss["ss_customer_sk"], weights=paid)[custs]}


def extra_wrong(got: dict, want: dict) -> int:
    """Rows whose rank is not the RANK() of the returned spend, descending
    (the ranks are checked against the answer's own sums, whose rounding
    may order near-ties differently from the float64 reference)."""
    s = got["spend"]
    rank = len(s) - np.searchsorted(np.sort(s), s, side="right") + 1
    return int(np.sum(got["r"] != rank))
