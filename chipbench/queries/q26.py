"""TPCx-BB Q26 (the HiFrames paper's Fig. 11 query): store_sales joined
with item, grouped by customer, customers with more than four purchases,
with their purchase count per item class 1 and 2."""
from __future__ import annotations

import numpy as np

KEY = "ss_customer_sk"
EXACT = ("c_i_count", "id1", "id2")
FLOAT = ()


def build(hf, t: dict):
    ss, it = t["store_sales"], t["item"]
    j = ss.merge(it, on=("ss_item_sk", "i_item_sk"))
    c_i = (j.groupby("ss_customer_sk")
           .agg(c_i_count="count",
                id1=hf.sum_(j["i_class_id"] == 1),
                id2=hf.sum_(j["i_class_id"] == 2)))
    return c_i[c_i["c_i_count"] > 4]


def reference(h: dict) -> dict:
    ss, it = h["store_sales"], h["item"]
    cls = np.zeros(int(it["i_item_sk"].max()) + 1, np.int64)
    cls[it["i_item_sk"]] = it["i_class_id"]
    known = ss["ss_item_sk"] < len(cls)        # the inner join's survivors
    cust = ss["ss_customer_sk"][known]
    c = cls[ss["ss_item_sk"][known]]
    count = np.bincount(cust)
    keep = np.nonzero(count > 4)[0]
    return {"ss_customer_sk": keep, "c_i_count": count[keep],
            "id1": np.bincount(cust, weights=c == 1)[keep].astype(np.int64),
            "id2": np.bincount(cust, weights=c == 2)[keep].astype(np.int64)}
