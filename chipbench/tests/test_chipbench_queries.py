"""The benchmark's copies of the queries, run through the program at a
small size on the CPU, agree with the benchmark's numpy references."""
import numpy as np
import pytest

from chipbench import check, data
from chipbench import run as bench

from .conftest import REPO


@pytest.mark.parametrize("query", ["q26", "per_item", "leaderboard"])
def test_query_matches_reference(query):
    from repro import hiframes as hf
    q = bench.load_module(REPO, "queries", query)
    host = data.make_tables({"store_sales_rows": 5000, "items": 97,
                             "customers": 211}, seed=2**31 + 5)
    t = {"store_sales": hf.table(host["store_sales"], "store_sales"),
         "item": hf.table(host["item"], "item").replicate()}
    got = q.build(hf, t).to_numpy()
    limits = {"wrong_rows": 0, **{f"{c}_rel_err": 1e-5 for c in q.FLOAT}}
    numbers, bad = check.compare(q, [got], q.reference(host), limits)
    assert bad == 0, numbers
    assert numbers["wrong_rows"] == 0


def test_compare_counts_each_kind_of_wrong_row():
    q = bench.load_module(REPO, "queries", "per_item")
    want = {"ss_item_sk": np.arange(5), "n": np.full(5, 3),
            "paid": np.arange(5) + 1.0}
    got = {k: v.copy() for k, v in want.items()}
    got["n"] = got["n"].copy()
    got["n"][1] = 4                                   # a count altered
    got = {k: np.concatenate([v[:4], v[2:3]]) for k, v in got.items()}
    got["paid"] = got["paid"].astype(np.float32)      # key 4 missing, 2 twice
    one = check.compare_one(q, got, want)
    assert one["wrong_rows"] == 3
    assert one["paid_rel_err"] == 0.0


def test_same_seed_same_tables_and_large_seeds():
    scale = {"store_sales_rows": 1000, "items": 50, "customers": 60}
    a = data.make_tables(scale, 2**33 + 1)
    b = data.make_tables(scale, 2**33 + 1)
    c = data.make_tables(scale, 2**33 + 2)
    for t in a:
        for col in a[t]:
            np.testing.assert_array_equal(a[t][col], b[t][col])
    assert not np.array_equal(a["store_sales"]["ss_customer_sk"],
                              c["store_sales"]["ss_customer_sk"])
