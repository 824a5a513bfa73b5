"""The benchmark's seeded tables, and its copies of the queries run through
the program at a small size on the CPU against its numpy references."""
import hashlib
import os

import numpy as np
import pytest

from chipbench import check, data
from chipbench import run as bench

from .conftest import REPO, TINY

TABLES = bench.load_json(os.path.join(REPO, "chipbench", "configs",
                                      "tpcxbb-sf1.json"))["tables"]


@pytest.mark.parametrize("query", ["q26", "per_item", "leaderboard"])
def test_query_matches_reference(query):
    from repro import hiframes as hf
    q = bench.load_module(REPO, "queries", query)
    host = data.make_tables(REPO, TABLES, {"store_sales_rows": 5000,
                                           "items": 97, "customers": 211},
                            seed=2**31 + 5)
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
    a = data.make_tables(REPO, TABLES, scale, 2**33 + 1)
    b = data.make_tables(REPO, TABLES, scale, 2**33 + 1)
    c = data.make_tables(REPO, TABLES, scale, 2**33 + 2)
    for t in a:
        for col in a[t]:
            np.testing.assert_array_equal(a[t][col], b[t][col])
    assert not np.array_equal(a["store_sales"]["ss_customer_sk"],
                              c["store_sales"]["ss_customer_sk"])


def digest(tables: dict) -> str:
    """sha256 over each table's columns in order: names, dtypes, bytes."""
    h = hashlib.sha256()
    for t, cols in tables.items():
        for c, v in cols.items():
            h.update(f"{t}.{c}:{v.dtype.str}:{v.shape}|".encode())
            h.update(v.tobytes())
    return h.hexdigest()


# Digests of the host tables as the benchmark made them before each table
# had a file of its own; "tiny" is the CPU tests' cut (conftest.TINY).
SF1 = {
    0: "a9a217fd9d6bdd7629902fb1fb29a4d7f2ffc0406e8700151cf9a3ae6eab6cfa",
    7: "9668a8a13f95531f96076e2616dfaef236ec8ecb70a0295ca8fe706a43d63763",
    2**31 + 3:
        "7e16de69a9d852a6e8b15469676e7ba081001d1aa5e1974ef93c949606874017",
    2**33 + 1:
        "797da8d3148b240598106e6190564e0f6641384ba8e116d349b5d54b71a55144",
    -1: "7174e5a47f32aa4065da643dcbe759d4e3d52a23cdb9b049ebab2913488ee628",
    -(2**40 + 9):
        "7bc97c8d06abfb24bdd0ed13e322225e53a1139e0bfcb96906ecb2ed93595fa7",
    6140000005:
        "2752a2d743f6118681eda2ee07fb88632ef37901555f78f2ac2ebc1ecc747a27",
}
TINY_DIGESTS = {
    0: "806e0e22656df193bce4d72042871159312dabdd0418c544c55d2e21caed55d0",
    7: "15467c5fd92bb5a0d0d71d9f5003d8f32b79e92efaf23c6310c661afc847febb",
    2**31 + 3:
        "b48a65b38c4bed11cca135560ab60f911395fcc31781e09233e05b571012e423",
    2**33 + 1:
        "51199f80033989bbb37ce156bb2cd69c59e8f41eba9ed5177ee905c4be3f2033",
    -1: "0996d9777ae54d7f45b3253b579e91cb62e8aba9f0c832774087f5eb046dab41",
    -(2**40 + 9):
        "d31d8b36d51fdae010d5a9dd64e1f8cfa07737c5b3b4828f36d26a0fe7d88347",
    6140000005:
        "1785f8438b21619b5fd1fab40ee35e9897332c072903e3f9f2bb1917bf036c0c",
}


@pytest.mark.parametrize("seed", list(SF1))
@pytest.mark.parametrize("config", ["tpcxbb-sf1", "tpcxbb-sf1-4chip", "tiny"])
def test_tables_are_the_bytes_they_were(config, seed):
    """Each configuration's tables, column for column and dtype for dtype,
    are the bytes they were before the tables moved into files."""
    if config == "tiny":
        tables, scale, want = TABLES, TINY, TINY_DIGESTS[seed]
    else:
        cfg = bench.load_json(os.path.join(REPO, "chipbench", "configs",
                                           f"{config}.json"))
        tables, scale, want = cfg["tables"], cfg["scale"], SF1[seed]
    assert digest(data.make_tables(REPO, tables, scale, seed)) == want


def test_zipf_keys_keep_one_histogram_across_seeds():
    """The bounded Zipf law's expected counts, the same on every seed, in
    an order that the seed draws."""
    a = data.zipf_keys(np.random.default_rng(5), 10_000, 300, 1.1)
    b = data.zipf_keys(np.random.default_rng(6), 10_000, 300, 1.1)
    assert a.dtype == np.int32 and len(a) == 10_000
    ca, cb = np.bincount(a, minlength=300), np.bincount(b, minlength=300)
    np.testing.assert_array_equal(ca, cb)
    assert not np.array_equal(a, b)
    w = np.arange(1, 301) ** -1.1
    np.testing.assert_allclose(ca, 10_000 * w / w.sum(), atol=1)
    assert np.all(np.diff(ca) <= 0)


def test_tables_of_one_config_draw_apart(tmp_path):
    """Two table files with one SEED_OFFSET would draw the same stream."""
    d = tmp_path / "chipbench" / "tables"
    d.mkdir(parents=True)
    for name in ("a", "b"):
        (d / f"{name}.py").write_text(
            "SEED_OFFSET = 3\nTINY = {}\n\n\ndef make(scale, spec, seed):\n"
            "    return {}\n")
    with pytest.raises(ValueError, match="SEED_OFFSET"):
        data.make_tables(str(tmp_path), {"a": {}, "b": {}}, {}, 1)
