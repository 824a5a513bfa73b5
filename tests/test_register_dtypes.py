"""Registered and persisted tables keep their logical dtypes: category
dictionaries and nullability survive ``persist()``, ``Session.register``
(hash, replicated and block layouts) and ``reshard``, so string predicates
run on them, ``to_numpy()`` decodes them, and the plan cache keys and
rebinds by dictionary (docs/dtypes.md, docs/serving.md)."""
from __future__ import annotations

import numpy as np
import pandas as pd
import pytest

from repro import hiframes as hf
from repro.core import ir
from repro.core import stats as st
from repro.core.api import ExecConfig
from repro.core.dtypes import dict_decode, is_category, logical_key
from repro.runtime.session import Session

from test_serve import run_sharded

GENDERS = ("F", "M")
EDU = ("Advanced Degree", "College", "4 yr Degree", "2 yr Degree")


def demographics(n=40, genders=GENDERS, seed=3):
    """A small ``customer_demographics``-like table: two string columns
    (one with null holes), a nullable float, an int key."""
    rng = np.random.default_rng(seed)
    edu = np.array(EDU + ("Primary", "Unknown"), object)[rng.integers(0, 6, n)]
    edu[::7] = None
    score = rng.normal(size=n).astype(np.float32)
    score[::5] = np.nan
    return {"cd_demo_sk": np.arange(n, dtype=np.int32),
            "cd_gender": np.array(genders, object)[np.arange(n) % len(genders)],
            "cd_education_status": edu,
            "cd_score": score}


LAYOUTS = {
    "hash": lambda s, df, name: s.register(name, df,
                                           partition_by="cd_demo_sk"),
    "rep": lambda s, df, name: s.register(name, df.replicate()),
    "block": lambda s, df, name: s.register(name, df),
}


def logical(dtypes: dict) -> dict:
    return {c: logical_key(d) for c, d in dtypes.items()}


def answer(sess, q) -> pd.DataFrame:
    """The session's answer to ``q`` with its category columns decoded by
    the query frame's schema."""
    got = sess.submit(q).result().to_numpy()
    for c, dt in q.schema.items():
        if is_category(dt):
            got[c] = dict_decode(got[c], dt.categories)
    return pd.DataFrame(got).sort_values("cd_demo_sk").reset_index(drop=True)


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_registered_tables_keep_dictionaries_and_nullability(layout):
    df = hf.table(demographics(), "cd")
    with Session(ExecConfig()) as sess:
        reg = LAYOUTS[layout](sess, df, "cd")
        assert logical(reg.dtypes) == logical(df.dtypes)
        assert repr(reg.dtypes["cd_gender"]) == "category[str]"
        assert repr(reg.dtypes["cd_education_status"]) == "category[str]?"
        assert repr(reg.dtypes["cd_score"]) == "float32?"


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_string_predicates_on_registered_table_match_pandas(layout):
    host = demographics()
    ref = pd.DataFrame(host)
    with Session(ExecConfig()) as sess:
        t = LAYOUTS[layout](sess, hf.table(host, "cd"), "cd")
        q = t[(t["cd_gender"] == "M") & t["cd_education_status"].isin(EDU)]
        q = q.assign(male=q["cd_gender"] == "M")
        got = answer(sess, q)
    want = ref[(ref.cd_gender == "M") & ref.cd_education_status.isin(EDU)]
    want = want.reset_index(drop=True)
    assert len(got) == len(want) > 0
    np.testing.assert_array_equal(got.cd_demo_sk, want.cd_demo_sk)
    assert list(got.cd_education_status) == list(want.cd_education_status)
    assert got.male.all()


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_registered_table_to_numpy_decodes(layout):
    host = demographics()
    with Session(ExecConfig()) as sess:
        got = LAYOUTS[layout](sess, hf.table(host, "cd"), "cd").to_numpy()
    order = np.argsort(got["cd_demo_sk"])
    assert list(got["cd_gender"][order]) == list(host["cd_gender"])
    assert list(got["cd_education_status"][order]) == \
        list(host["cd_education_status"])
    got_raw = hf.table(host, "cd").persist().to_numpy(decode=False)
    assert got_raw["cd_gender"].dtype == np.int32


def test_reregistered_dictionary_misses_and_same_dictionary_hits():
    """Codes are dictionary positions: "M" is code 1 of (F, M) and code 2
    of (A, F, M).  A plan cached for the first dictionary must not answer
    for the second; the same dictionary over other rows still hits."""
    with Session(ExecConfig()) as sess:
        def run(host):
            t = sess.register("cd", hf.table(host, "cd"))
            rec_got = sess.submit(t[t["cd_gender"] == "M"]).result()
            got = rec_got.to_numpy()
            return rec_got.query_record, np.sort(got["cd_demo_sk"])

        def males(host):
            return np.flatnonzero(host["cd_gender"] == "M")

        first = demographics()
        rec, got = run(first)
        assert rec.cache == "miss"
        np.testing.assert_array_equal(got, males(first))
        other = demographics(genders=("A", "F", "M"))
        rec, got = run(other)
        assert rec.cache == "miss"
        np.testing.assert_array_equal(got, males(other))
        same = demographics(genders=("A", "F", "M"), seed=9)
        rec, got = run(same)
        assert rec.cache == "hit" and rec.compiles == 0
        np.testing.assert_array_equal(got, males(same))


def test_rebind_refuses_a_scan_of_another_dictionary():
    """Below the plan cache's key: the compiled plan itself refuses to run
    over a scan whose dictionary differs from the one it was built for."""
    a = hf.table(demographics(), "cd").persist()
    b = hf.table(demographics(genders=("A", "F", "M")), "cd").persist()
    q = a[a["cd_gender"] == "M"]
    lowered = q.lower()
    (scan,) = lowered.scans
    with pytest.raises(ValueError, match="other logical dtypes"):
        lowered(scan_nodes={str(scan.id): b.node})
    lowered(scan_nodes={str(scan.id): a.node})       # its own dictionary runs


def test_scan_of_bare_arrays_refuses_string_predicates():
    """What ``persist`` built before it kept the schema: a scan typed from
    its arrays alone holds the codes as int32, and a string predicate on it
    is the TypeError a string query on a registered table used to raise."""
    p = hf.table(demographics(), "cd").persist()
    bare = hf.DataFrame(ir.Scan("cd", dict(p.node.columns),
                                layout=p.node.layout))
    assert bare.dtypes["cd_gender"] == np.int32
    assert not is_category(bare.dtypes["cd_gender"])
    with pytest.raises(TypeError, match="non-category column"):
        bare[bare["cd_gender"] == "M"]
    assert is_category(p.dtypes["cd_gender"])
    p[p["cd_gender"] == "M"]                          # plans


def test_integer_tables_persist_as_before():
    """A table without category or nullable columns persists to the scan
    its arrays alone would give: the same schema objects and the same
    plan-cache key, so its plans are unchanged."""
    rng = np.random.default_rng(1)
    host = {"k": rng.integers(0, 9, 64).astype(np.int32),
            "v": rng.normal(size=64).astype(np.float32)}
    p = hf.table(host, "t").repartition("k").persist()
    bare = ir.Scan("t", dict(p.node.columns), layout=p.node.layout)
    assert p.node._schema == bare._schema
    assert all(isinstance(d, np.dtype) for d in p.node._schema.values())
    q = p.groupby("k").agg(s=("v", "sum"))
    q_bare = hf.DataFrame(bare).groupby("k").agg(s=("v", "sum"))
    assert st.plan_fingerprint(q.node, scans="shape") == \
        st.plan_fingerprint(q_bare.node, scans="shape")


def test_query_record_counts_rows_per_shard_at_each_capacity_site():
    host = demographics()
    with Session(ExecConfig()) as sess:
        t = sess.register("cd", hf.table(host, "cd"),
                          partition_by="cd_demo_sk")
        q = t[t["cd_gender"] == "M"].groupby("cd_education_status").agg(
            n=("cd_demo_sk", "count"))
        rec = sess.submit(q).result().query_record
        sites = {f"{kind}.{op_id}"
                 for op_id, kind, _, _ in rec.plan.sites}
    assert set(rec.shard_rows) == sites
    assert all(len(v) == 1 for v in rec.shard_rows.values())
    compact = [v for k, v in rec.shard_rows.items()
               if k.startswith("compact.")]
    # the filter's rows, less the null group keys the group-by drops
    kept = (host["cd_gender"] == "M") & (host["cd_education_status"] != None)  # noqa: E711
    assert compact == [(int(kept.sum()),)]


def test_four_shards_keep_dtypes_through_hash_rep_and_reshard():
    """On four devices: hash, replicated and resharded registrations keep
    their dictionaries, answer string predicates as pandas does, decode,
    miss on another dictionary, and count rows on each of four shards."""
    run_sharded("""
        import numpy as np, pandas as pd, jax
        from jax.sharding import Mesh
        from repro.core.api import ExecConfig
        from repro.core.dtypes import logical_key
        from repro.runtime.session import Session
        from test_register_dtypes import EDU, answer, demographics, logical

        host = demographics(n=64)
        ref = pd.DataFrame(host)
        want = ref[(ref.cd_gender == "M") & ref.cd_education_status.isin(EDU)]
        two = ExecConfig(mesh=Mesh(np.array(jax.devices()[:2]), ("data",)))
        at_two = hf.table(host, "cd").repartition("cd_demo_sk").persist(two)
        sess = Session(ExecConfig())
        frames = {
            "hash": sess.register("h", hf.table(host, "h"),
                                  partition_by="cd_demo_sk"),
            "rep": sess.register("r", hf.table(host, "r").replicate()),
            "reshard": sess.register("s", at_two),
        }
        assert frames["reshard"].node.layout.nshards == 4
        for name, t in frames.items():
            assert logical(t.dtypes) == logical(hf.table(host).dtypes), name
            q = t[(t["cd_gender"] == "M") & t["cd_education_status"].isin(EDU)]
            got = answer(sess, q)
            assert list(got.cd_demo_sk) == list(want.cd_demo_sk), name
            assert list(got.cd_education_status) == \\
                list(want.cd_education_status), name
            back = t.to_numpy()
            order = np.argsort(back["cd_demo_sk"])
            assert list(back["cd_gender"][order]) == list(host["cd_gender"])
        t = frames["hash"]
        rec = sess.submit(t[t["cd_gender"] == "M"]).result().query_record
        assert rec.shard_rows and all(len(v) == 4
                                      for v in rec.shard_rows.values())
        other = demographics(n=64, genders=("A", "F", "M"))
        t = sess.register("h", hf.table(other, "h"), partition_by="cd_demo_sk")
        res = sess.submit(t[t["cd_gender"] == "M"]).result()
        assert res.query_record.cache == "miss"
        assert sorted(res.to_numpy()["cd_demo_sk"]) == \\
            list(np.flatnonzero(other["cd_gender"] == "M"))
        sess.close()
    """, devices=4)
