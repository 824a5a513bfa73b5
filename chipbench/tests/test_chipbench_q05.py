"""The Q05 configuration over a Zipf-skewed clickstream: its seeded tables,
the skew its item key has at SF1, its query against the numpy reference
through the harness on one and four virtual CPU devices, and its control."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from chipbench import check, control, data
from chipbench import run as bench

from .conftest import REPO, tiny_scale
from .test_chipbench_run import cpu_env

CELL = "q05-zipf-4chip"
CONFIG = bench.load_json(os.path.join(REPO, "chipbench", "configs",
                                      "tpcxbb-q05-zipf.json"))
TABLES = CONFIG["tables"]


def tiny():
    return tiny_scale(REPO, TABLES)


def test_new_tables_are_seeded_and_stable():
    a = data.make_tables(REPO, TABLES, tiny(), 2**33 + 1)
    b = data.make_tables(REPO, TABLES, tiny(), 2**33 + 1)
    c = data.make_tables(REPO, TABLES, tiny(), 2**33 + 2)
    assert list(a) == ["web_clickstreams", "item", "customer",
                       "customer_demographics"]
    for t in a:
        for col in a[t]:
            np.testing.assert_array_equal(a[t][col], b[t][col])
    for t, col in (("web_clickstreams", "wcs_item_sk"),
                   ("web_clickstreams", "wcs_user_sk"),
                   ("customer", "c_current_cdemo_sk")):
        assert not np.array_equal(a[t][col], c[t][col]), (t, col)
    # the skew is the law's, not the seed's: the same histogram, reordered
    np.testing.assert_array_equal(
        np.bincount(a["web_clickstreams"]["wcs_item_sk"]),
        np.bincount(c["web_clickstreams"]["wcs_item_sk"]))
    # the fixed cross product draws nothing from the seed
    for col in a["customer_demographics"]:
        np.testing.assert_array_equal(a["customer_demographics"][col],
                                      c["customer_demographics"][col])
    assert a["web_clickstreams"]["wcs_user_sk"].max() < tiny()["customers"]


def test_customer_demographics_is_the_sf1_cross_product():
    mod = bench.load_module(REPO, "tables", "customer_demographics")
    assert mod.ROWS == 2 * 5 * 7 * 20 * 4 * 7 * 7 * 7 == 1920800
    assert bench.load_module(REPO, "tables", "customer").DEMOGRAPHICS == \
        mod.ROWS
    cd = mod.make(CONFIG["scale"], TABLES["customer_demographics"], 0)
    assert len(cd["cd_demo_sk"]) == 1920800
    np.testing.assert_array_equal(cd["cd_demo_sk"], np.arange(1920800))
    pairs = set(zip(cd["cd_gender"][:14], cd["cd_education_status"][:14]))
    assert pairs == {(g, e) for g in ("M", "F") for e in mod.EDUCATION}
    # every pair holds the same share of the rows
    _, n = np.unique(np.char.add(cd["cd_gender"].astype(str),
                                 cd["cd_education_status"].astype(str)),
                     return_counts=True)
    assert len(n) == 14 and (n == 1920800 // 14).all()
    # a fixed table: the small cut of the CPU tests is the same table
    small = mod.make(tiny(), {}, 0)
    for col in cd:
        np.testing.assert_array_equal(small[col], cd[col])


def test_hottest_item_holds_its_zipf_share_at_sf1():
    """Exponent 1.1 itself over SF1's 17,820 items: the hottest item holds
    14.65% of the clicks (the program's rng.zipf(2.1) would put about 64%
    on one item)."""
    scale, spec = CONFIG["scale"], TABLES["web_clickstreams"]
    assert scale["items"] == 17820
    wcs = bench.load_module(REPO, "tables", "web_clickstreams")
    assert wcs.rows(scale, dict(spec, clicks_per_sale=10)) == 6675790
    keys = data.zipf_keys(np.random.default_rng(1), wcs.rows(scale, spec),
                          scale["items"], spec["zipf"])
    n = np.sort(np.bincount(keys))[::-1]
    assert n[0] / len(keys) == pytest.approx(0.1465, abs=1e-3)
    assert n[:10].sum() / len(keys) == pytest.approx(0.393, abs=1e-3)
    assert n[:100].sum() / len(keys) == pytest.approx(0.627, abs=1e-3)


Q05_MEASURE = """
import json, sys
sys.path.insert(0, {repo!r})
import jax
from chipbench import run as bench
cell = bench.Cell({root!r}, {cell!r})
run = {{}}
class Kept(bench.Run):
    def __init__(self, *a, **k):
        super().__init__(*a, **k)
        run["r"] = self
bench.Run = Kept
out = bench.measure(cell, 2**33 + 7, 0.5, False, jax.devices())
skew = bench.load_module({root!r}, "metrics", "shard_skew.4chip").read(run["r"])
print(json.dumps({{"checks": out["checks"], "correct": out["correct"],
                  "failed": out["failed"], "attempted": out["attempted"],
                  "devices": len(jax.devices()), "skew": skew}}))
"""


@pytest.mark.parametrize("devices", [1, 4])
def test_q05_is_correct_at_tiny_through_the_harness(tiny_root, devices):
    """``bench.measure`` of the cell at the tables' ``TINY`` scale, on one
    and four virtual CPU devices: every answer equals the reference, and
    on four the skewed clickstream reads uneven shards."""
    script = Q05_MEASURE.format(repo=REPO, root=tiny_root, cell=CELL)
    env = cpu_env(XLA_FLAGS="--xla_force_host_platform_device_count="
                  f"{devices}", PYTHONPATH=os.path.join(REPO, "src"))
    p = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["devices"] == devices
    assert out["correct"], out["checks"]
    assert out["checks"]["wrong_rows"]["value"] == 0
    assert out["attempted"] > 0 and out["failed"] == 0
    if devices == 1:
        assert out["skew"] == 1.0
    else:
        # the registered shards of the clickstream at TINY: 1569, 1053,
        # 551 and 577 rows, a max/mean of 1.67
        assert out["skew"] == pytest.approx(1.6736, abs=1e-3)


def test_q05_reference_is_not_degenerate():
    """At ``TINY`` every output column of the reference varies: the
    comparison can see a wrong count or flag."""
    q = bench.load_module(REPO, "queries", "q05")
    want = q.reference(data.make_tables(REPO, TABLES, tiny(), 2**31 + 5))
    assert len(want[q.KEY]) > 0
    np.testing.assert_array_equal(want[q.KEY], np.unique(want[q.KEY]))
    for col in q.EXACT:
        assert len(np.unique(want[col])) > 1, col


def test_q05_stale_control_is_refused(tiny_root):
    """The stale snapshot (the partitioned clickstream without its last
    rows) breaks the exact limit on every seed."""
    cell = bench.Cell(tiny_root, CELL)
    assert control.fact_table(cell) == "web_clickstreams"
    for seed in (1, 2, 3):
        numbers = control.readings(cell, seed)
        ok, _ = check.judge(numbers, cell.limits, 0)
        assert not ok and numbers["wrong_rows"] > 0, (seed, numbers)


def test_shard_skew_reads_the_most_uneven_site_and_nothing_without_it():
    from types import SimpleNamespace as NS
    reader = bench.load_module(REPO, "metrics", "shard_skew.4chip")
    run = bench.Run(bench.Cell(REPO, CELL), 1.0)

    def answered(record):
        return NS(answer={}, record=record)

    run.requests = [answered(NS(shard_rows={"join.2": (6, 2, 2, 2),
                                            "exchange.6": (0, 0, 0, 0)})),
                    answered(NS(shard_rows={"segment_agg.8": (3, 3, 3, 3)}))]
    assert reader.read(run) == 2.0
    # a program whose records predate shard_rows reads as nothing
    run.requests = [answered(NS(exec_s=1.0)), answered(None)]
    assert reader.read(run) is None
