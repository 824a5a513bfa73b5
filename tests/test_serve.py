"""Serving layer tests: plan-cache key correctness, cross-table rebind,
session admission parity, stats sidecar persistence, global ranking, and
on-device resharding (docs/serving.md)."""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import numpy as np
import pandas as pd
import pytest

from repro import hiframes as hf
from repro.core import ir
from repro.core import stats as st
from repro.core.api import ExecConfig
from repro.core.errors import StatsError
from repro.runtime.reshard import reshard
from repro.runtime.session import PlanCache, Session, _CacheEntry, \
    cfg_signature

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sharded(body: str, devices: int):
    script = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={devices}"
        import numpy as np
        import jax
        assert jax.device_count() == {devices}
        from repro import hiframes as hf
        {textwrap.indent(textwrap.dedent(body), '        ').strip()}
        print("SUBPROC_OK")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(ROOT, "src"), os.path.join(ROOT, "tests")])
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, f"stderr:\n{res.stderr[-4000:]}"
    assert "SUBPROC_OK" in res.stdout
    return res.stdout


def _frame(n=160, seed=5):
    rng = np.random.default_rng(seed)
    return {"k": rng.integers(0, 11, n).astype(np.int64),
            "v": rng.normal(size=n).astype(np.float64)}


# -- plan-cache key definition ------------------------------------------------

def test_shape_fingerprint_ignores_table_identity():
    a = hf.table(_frame(seed=1), "a")
    b = hf.table(_frame(seed=2), "b")   # same schema+rows, different data
    qa = a.groupby("k").agg(s=("v", "sum"))
    qb = b.groupby("k").agg(s=("v", "sum"))
    assert st.plan_fingerprint(qa.node, scans="shape") == \
        st.plan_fingerprint(qb.node, scans="shape")
    # the identity mode (stats store keying) keeps them apart
    assert st.plan_fingerprint(qa.node) != st.plan_fingerprint(qb.node)


def test_shape_fingerprint_literal_and_dictionary_miss():
    df = hf.table(_frame(), "t")
    f3 = df[df["k"] > 3]
    f5 = df[df["k"] > 5]
    assert st.plan_fingerprint(f3.node, scans="shape") != \
        st.plan_fingerprint(f5.node, scans="shape")
    # same int32 codes under DIFFERENT dictionaries must not share a key:
    # plan constants are code-space rewrites against the dictionary.
    s1 = hf.table({"c": np.array(["a", "b", "a", "c"], object)}, "s1")
    s2 = hf.table({"c": np.array(["x", "y", "x", "z"], object)}, "s2")
    assert st.plan_fingerprint(s1.node, scans="shape") != \
        st.plan_fingerprint(s2.node, scans="shape")


def test_cfg_signature_levers():
    base = ExecConfig()
    assert cfg_signature(base, 1) == cfg_signature(ExecConfig(), 1)
    assert cfg_signature(base, 1) != cfg_signature(
        ExecConfig(packed_exchange=False), 1)
    assert cfg_signature(base, 1) != cfg_signature(
        ExecConfig(cap_overrides={3: (64, 8)}), 1)
    assert cfg_signature(base, 1) != cfg_signature(base, 2)


def test_plan_cache_lru_eviction():
    pc = PlanCache(capacity=2)
    e = _CacheEntry(lowered=None, scan_ids=(), rebindable=False)
    pc.put("a", e), pc.put("b", e)
    assert pc.get("a") is not None       # refresh a
    pc.put("c", e)                       # evicts b (LRU)
    assert pc.get("b") is None
    assert pc.get("a") is not None and pc.get("c") is not None
    assert pc.evictions == 1


# -- session: cache hits, rebind, fallback ------------------------------------

def test_session_hit_zero_compiles_and_stats():
    with Session(ExecConfig()) as sess:
        sess.register("t", hf.table(_frame(), "t").repartition("k"))
        q = lambda: sess.table("t").groupby("k").agg(s=("v", "sum"))
        t1 = sess.collect(q())
        t2 = sess.collect(q())
        assert t1.query_record.cache == "miss"
        assert t2.query_record.cache == "hit"
        assert t2.query_record.compiles == 0
        stats = sess.stats()
        assert stats["plan_cache"]["hits"] == 1
        assert stats["plan_cache"]["misses"] == 1
        assert stats["queries"] == 2
        assert "HIT" in sess.explain(q())


def test_session_rebind_different_table_returns_its_data():
    f1, f2 = _frame(seed=11), _frame(seed=22)
    with Session(ExecConfig()) as sess:
        sess.register("A", hf.table(f1, "A").repartition("k"))
        sess.register("B", hf.table(f2, "B").repartition("k"))
        la = sess.table("A").node.layout
        lb = sess.table("B").node.layout
        assert la.capacity == lb.capacity      # same persist recipe
        q = lambda t: t.groupby("k").agg(s=("v", "sum"))
        sess.collect(q(sess.table("A")))
        t = sess.collect(q(sess.table("B")))
        assert t.query_record.cache == "hit"
        assert t.query_record.compiles == 0
        got = pd.DataFrame({c: np.asarray(v)
                            for c, v in t.to_numpy().items()})
        got = got.sort_values("k").reset_index(drop=True)
        ref = pd.DataFrame(f2).groupby("k", as_index=False)["v"].sum()
        assert np.allclose(got["s"].values, ref["v"].values)


def test_session_cfg_lever_and_literal_miss():
    with Session(ExecConfig()) as sess:
        sess.register("t", hf.table(_frame(), "t"))
        q = lambda: sess.table("t").groupby("k").agg(s=("v", "sum"))
        sess.collect(q())
        t = sess.collect(q(), ExecConfig(packed_exchange=False))
        assert t.query_record.cache == "miss"
        f = lambda th: sess.table("t")[sess.table("t")["k"] > th] \
            .groupby("k").agg(s=("v", "sum"))
        sess.collect(f(3))
        assert sess.collect(f(5)).query_record.cache == "miss"
        assert sess.collect(f(3)).query_record.cache == "hit"


def test_session_hit_falls_back_on_overflow():
    """A cached plan whose capacities can't fit a bigger rebound table must
    fall back to the miss path (replan), not return truncated rows."""
    small, big = _frame(n=40, seed=1), _frame(n=400, seed=2)
    cfg = ExecConfig(safe_capacities=False, shuffle_slack=1.0,
                     auto_retry=3)
    with Session(cfg) as sess:
        sess.register("S", hf.table(small, "S").repartition("k"))
        q = lambda t: t.groupby("k").agg(s=("v", "sum"))
        sess.collect(q(sess.table("S")))
        # register a table with the same schema but 10x the rows -- persist
        # picks a bigger capacity, so the layout shape differs and the
        # lookup itself misses; parity is what matters.
        sess.register("B", hf.table(big, "B").repartition("k"))
        t = sess.collect(q(sess.table("B")))
        got = pd.DataFrame({c: np.asarray(v)
                            for c, v in t.to_numpy().items()})
        got = got.sort_values("k").reset_index(drop=True)
        ref = pd.DataFrame(big).groupby("k", as_index=False)["v"].sum()
        assert np.allclose(got["s"].values, ref["v"].values)


# -- stats sidecar ------------------------------------------------------------

def test_sidecar_roundtrip(tmp_path):
    d = str(tmp_path)
    cfg = ExecConfig(adaptive_stats=True)
    with Session(cfg, session_dir=d) as sess:
        sess.register("t", hf.table(_frame(), "t"))
        sess.collect(sess.table("t").groupby("k").agg(s=("v", "sum")))
        n_realized = len(sess.store.realized)
    assert os.path.exists(os.path.join(d, "stats.json"))
    assert n_realized > 0
    with Session(cfg, session_dir=d) as s2:
        assert len(s2.store.realized) == n_realized


def test_sidecar_corrupt_raises_and_recovers(tmp_path):
    d = str(tmp_path)
    p = os.path.join(d, "stats.json")
    with open(p, "w") as f:
        f.write('{"version": 1, "realized": {"x": ')   # truncated JSON
    with pytest.raises(StatsError):
        Session(ExecConfig(), session_dir=d)
    with Session(ExecConfig(), session_dir=d, recover_stats=True) as sess:
        assert len(sess.store.realized) == 0
    assert os.path.exists(p + ".corrupt")
    # wrong shape (valid JSON, bad version) also raises
    with open(p, "w") as f:
        f.write('{"version": 99}')
    with pytest.raises(StatsError):
        st.StatsStore.load(p)


def test_sidecar_persists_retry_events(tmp_path):
    d = str(tmp_path)
    store = st.StatsStore()
    from repro.runtime.retry import RetryEvent
    store.events["fp1"] = (RetryEvent("retry", 1, 3, "cap 8 -> 16"),)
    store.realized["fp1"] = {"rows": 10, "max": 4, "mean": 2.5,
                             "nshards": 4}
    p = os.path.join(d, "stats.json")
    store.save(p)
    back = st.StatsStore.load(p)
    assert back.realized == store.realized
    assert back.events["fp1"][0] == store.events["fp1"][0]


# -- global ranking (no partition_by) -----------------------------------------

def test_global_rank_oracle_single_device():
    f = _frame(n=90, seed=8)
    df = hf.table(f, "t")
    s = pd.Series(f["k"])
    for kind, fn, method in [("rank", hf.rank, "min"),
                             ("dense_rank", hf.dense_rank, "dense")]:
        out = fn(df, [], ["k"], out="r").collect()
        got = pd.DataFrame({c: np.asarray(v)
                            for c, v in out.to_numpy().items()})
        got = got.sort_values(["k", "r"]).reset_index(drop=True)
        exp = s.rank(method=method).astype(np.int64)
        ref = pd.DataFrame({"k": s, "r": exp}).sort_values(
            ["k", "r"]).reset_index(drop=True)
        assert (got["r"].values == ref["r"].values).all(), kind
    rn = hf.row_number(df, [], out="rn").collect()
    vals = np.sort(np.asarray(rn.to_numpy()["rn"]))
    assert (vals == np.arange(1, len(f["k"]) + 1)).all()


def test_global_rank_requires_adjacency():
    """Raw-IR users skipping api.rank's sort must get a planner error when
    equal order keys are not adjacent across shards."""
    df = hf.table(_frame(), "t")
    w = ir.Window(df.node, "rank", None, "r", partition_by=(),
                  order_by=("k",))
    with pytest.raises(ValueError, match="adjacent"):
        hf.DataFrame(w).lower(ExecConfig())


def test_global_rank_multidevice_and_desc():
    run_sharded("""
        import pandas as pd
        from repro.core.api import ExecConfig
        rng = np.random.default_rng(4)
        n = 230
        f = {"k": rng.integers(0, 17, n).astype(np.int64),
             "v": rng.normal(size=n)}
        df = hf.table(f, "t")
        s = pd.Series(f["k"])
        for kind, fn, method, asc in [
                ("rank", hf.rank, "min", True),
                ("dense_rank", hf.dense_rank, "dense", True),
                ("rank", hf.rank, "min", False)]:
            out = fn(df, [], ["k"], out="r", ascending=asc).collect()
            got = pd.DataFrame({c: np.asarray(v)
                                for c, v in out.to_numpy().items()})
            got = got.sort_values(["k", "r"]).reset_index(drop=True)
            exp = s.rank(method=method, ascending=asc).astype(np.int64)
            ref = pd.DataFrame({"k": s, "r": exp}).sort_values(
                ["k", "r"]).reset_index(drop=True)
            assert (got["r"].values == ref["r"].values).all(), (kind, asc)
        rn = hf.row_number(df, [], out="rn").collect()
        vals = np.sort(np.asarray(rn.to_numpy()["rn"]))
        assert (vals == np.arange(1, n + 1)).all()
        print("RANKS_OK")
    """, devices=4)


def test_global_rank_census_elides_on_sorted_persist():
    """rank over a persisted globally-sorted table plans 0 exchanges and 0
    sorts: the api-inserted Sort no-ops on the sorted layout."""
    run_sharded("""
        from repro.core.api import ExecConfig
        rng = np.random.default_rng(9)
        f = {"k": rng.integers(0, 9, 120).astype(np.int64),
             "v": rng.normal(size=120)}
        cfg = ExecConfig()
        p = hf.table(f, "t").sort("k").persist(cfg, name="sorted_t")
        lowered = hf.rank(p, [], ["k"], out="r").lower(cfg)
        c = lowered.pplan.counts()
        assert c["hash_exchanges"] == 0, c
        assert c["sample_sorts"] == 0, c
        assert c["local_sorts"] == 0, c
        print("CENSUS_OK")
    """, devices=2)


# -- concurrent admission parity ----------------------------------------------

_PARITY_BODY = """
    import pandas as pd
    from repro.core.api import ExecConfig
    from repro.runtime.session import Session
    rng = np.random.default_rng(2)
    n = 300
    f = {"k": rng.integers(0, 13, n).astype(np.int64),
         "v": rng.normal(size=n)}
    ref = pd.DataFrame(f).groupby("k")["v"].agg(
        ["sum", "count"]).reset_index()
    with Session(ExecConfig(), admission=4, workers=4) as sess:
        sess.register("t", hf.table(f, "t").repartition("k"))
        q = lambda: sess.table("t").groupby("k").agg(
            s=("v", "sum"), c=("v", "count"))
        futs = [sess.submit(q()) for _ in range(6)]
        for fu in futs:
            t = fu.result()
            got = pd.DataFrame({c: np.asarray(v)
                                for c, v in t.to_numpy().items()})
            got = got.sort_values("k").reset_index(drop=True)
            assert np.allclose(got["s"].values, ref["sum"].values)
            assert (got["c"].values == ref["count"].values).all()
        stats = sess.stats()
        assert stats["queries"] == 6
        assert stats["plan_cache"]["hits"] >= 1
    print("PARITY_OK")
"""


@pytest.mark.parametrize("devices", [1, 2, 8])
def test_concurrent_submit_parity(devices):
    run_sharded(_PARITY_BODY, devices=devices)


# -- layout-driven skew salting -----------------------------------------------

def test_layout_skew_lowers_salt_threshold():
    """A registered table whose persisted per-shard counts show hash-key
    skew halves the salting threshold WITHOUT re-sampling (the planner
    consults the ScanLayout counts)."""
    run_sharded("""
        from repro.core.api import ExecConfig
        from repro.core import stats as st
        rng = np.random.default_rng(0)
        n = 4000
        # one hot key -> one shard holds ~half the rows after hash
        k = np.where(rng.random(n) < 0.5, 0,
                     rng.integers(1, 64, n)).astype(np.int64)
        f = {"k": k, "v": rng.normal(size=n)}
        cfg = ExecConfig(adaptive_stats=True)
        p = hf.table(f, "skewed").repartition("k").persist(cfg, name="sk")
        lay = p.node.layout
        occ = lay.counts.max() / max(lay.counts.mean(), 1)
        assert occ >= 2.0, f"fixture not skewed enough: {occ}"
        ctx = st.StatsContext(p.node)
        assert ctx.layout_skewed(p.node, ("k",))
        # an even table does NOT trip it
        e = {"k": np.arange(n).astype(np.int64) % 64,
             "v": rng.normal(size=n)}
        pe = hf.table(e, "even").repartition("k").persist(cfg, name="ev")
        ctx2 = st.StatsContext(pe.node)
        assert not ctx2.layout_skewed(pe.node, ("k",))
        print("SKEW_OK")
    """, devices=4)


# -- resharding ---------------------------------------------------------------

_RESHARD_BODY = """
    import pandas as pd
    from jax.sharding import Mesh
    from repro.core import ir
    from repro.core.api import ExecConfig
    from repro.runtime.reshard import reshard

    calls = {"n": 0}
    orig = ir.ScanLayout.gather_host
    def guard(self, src):
        calls["n"] += 1
        return orig(self, src)
    ir.ScanLayout.gather_host = guard

    rng = np.random.default_rng(6)
    n = 173
    f = {"k": rng.integers(0, 10, n).astype(np.int64),
         "v": rng.normal(size=n)}
    cfg4 = ExecConfig()
    cfg2 = ExecConfig(mesh=Mesh(np.array(jax.devices()[:2]), ("data",)))

    def valid_rows(d):
        lay = d.node.layout
        cols = {c: np.asarray(v) for c, v in d.node.columns.items()}
        keep = np.concatenate([np.arange(r * lay.capacity,
                                         r * lay.capacity + c)
                               for r, c in enumerate(np.asarray(lay.counts))])
        return np.stack([cols["k"][keep], cols["v"][keep]])

    p4 = hf.table(f, "t").repartition("k").sort_within_partitions("k") \\
        .persist(cfg4, name="t4")
    a = valid_rows(p4)

    # merge 4 -> 2, re-establishing the hash claim on the smaller mesh
    r2 = reshard(p4, 2, cfg2)
    l2 = r2.node.layout
    assert l2.device_valid(2)
    assert l2.kind == "hash" and l2.partitioned_by == ("k",), l2
    b = valid_rows(r2)
    assert np.allclose(a[:, np.lexsort(a)], b[:, np.lexsort(b)])

    # split 2 -> 4 and run a query through the re-entered shards
    r4 = reshard(r2, 4, cfg4)
    assert r4.node.layout.device_valid(4)
    t = r4.groupby("k").agg(s=("v", "sum")).collect(cfg4)
    got = pd.DataFrame({c: np.asarray(v) for c, v in t.to_numpy().items()})
    got = got.sort_values("k").reset_index(drop=True)
    ref = pd.DataFrame(f).groupby("k", as_index=False)["v"].sum()
    assert np.allclose(got["s"].values, ref["v"].values)

    # groupby on the re-established hash claim plans 0 exchanges
    lowered = r2.groupby("k").agg(s=("v", "sum")).lower(cfg2)
    assert lowered.pplan.counts()["hash_exchanges"] == 0

    # ordering claims survive an order-preserving reshard
    ps = hf.table(f, "t").sort("k").persist(cfg4, name="ts")
    rs = reshard(ps, 2, cfg2, reestablish=False)
    assert rs.node.layout.sorted_by == ps.node.layout.sorted_by
    assert rs.node.layout.globally_sorted == ps.node.layout.globally_sorted
    d = valid_rows(rs)
    assert (np.diff(d[0]) >= 0).all()

    assert calls["n"] == 0, f"host gather x{calls['n']} during resharding"
    print("RESHARD_OK")
"""


def test_reshard_roundtrip_no_host_gather():
    run_sharded(_RESHARD_BODY, devices=4)


def test_reshard_rejects_host_frames():
    df = hf.table(_frame(), "t")
    with pytest.raises(ValueError, match="persisted"):
        reshard(df, 2)


def test_session_register_reshards_on_P_mismatch():
    run_sharded("""
        from jax.sharding import Mesh
        from repro.core.api import ExecConfig
        from repro.runtime.session import Session
        import pandas as pd
        rng = np.random.default_rng(3)
        f = {"k": rng.integers(0, 8, 140).astype(np.int64),
             "v": rng.normal(size=140)}
        cfg2 = ExecConfig(mesh=Mesh(np.array(jax.devices()[:2]), ("data",)))
        p2 = hf.table(f, "t").repartition("k").persist(cfg2, name="t2")
        assert p2.node.layout.nshards == 2
        with Session(ExecConfig()) as sess:    # 4-device session
            sess.register("t", p2)
            lay = sess.table("t").node.layout
            assert lay.device_valid(4), lay
            t = sess.collect(sess.table("t").groupby("k").agg(
                s=("v", "sum")))
            got = pd.DataFrame({c: np.asarray(v)
                                for c, v in t.to_numpy().items()})
            got = got.sort_values("k").reset_index(drop=True)
            ref = pd.DataFrame(f).groupby("k", as_index=False)["v"].sum()
            assert np.allclose(got["s"].values, ref["v"].values)
        print("REGISTER_RESHARD_OK")
    """, devices=4)


# -- serve smoke entrypoint ---------------------------------------------------

def test_serve_smoke_cli():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    res = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--smoke",
         "--scale", "2e-5", "--repeats", "1"],
        env=env, capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, \
        f"stdout:\n{res.stdout[-2000:]}\nstderr:\n{res.stderr[-2000:]}"
    assert "serve smoke: PASS" in res.stdout
