"""phys.merge_join against a numpy oracle and against the binary-search
formulation it replaced (match ranges from two searchsorteds into the
key-sorted right ranks, output slots from a third into the inclusive
prefix sums), kept here as the reference: same columns, count and flag."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import physical as phys

I32_MAX = np.iinfo(np.int32).max


def searchsorted_merge_join(lcols, lcount, rcols, rcount, lkeys, rkeys, *,
                            cap_out, r_suffix_map, how="inner",
                            null_fill=None):
    lcap = lcols[lkeys[0]].shape[0]
    rcap = rcols[rkeys[0]].shape[0]
    lvalid = phys.valid_mask(lcount, lcap)
    valid = jnp.concatenate([lvalid, phys.valid_mask(rcount, rcap)])
    keycols = []
    for lk, rk in zip(lkeys, rkeys):
        la, ra = lcols[lk], rcols[rk]
        dt = jnp.promote_types(la.dtype, ra.dtype)
        keycols.append(jnp.concatenate([la.astype(dt), ra.astype(dt)]))
    ranks, sidx, rank_sorted = phys.lex_ranks(keycols, valid)
    lrank = ranks[:lcap]
    is_r = (sidx >= lcap).astype(jnp.int32)
    scat = jnp.where(is_r > 0, jnp.cumsum(is_r) - 1, lcap + rcap)
    rsorted_rank = jnp.full((rcap,), I32_MAX, jnp.int32) \
        .at[scat].set(rank_sorted, mode="drop")
    rperm = jnp.zeros((rcap,), jnp.int32) \
        .at[scat].set((sidx - lcap).astype(jnp.int32), mode="drop")
    lo = jnp.searchsorted(rsorted_rank, lrank, side="left")
    hi = jnp.searchsorted(rsorted_rank, lrank, side="right")
    hi = jnp.minimum(hi, rcount)
    lo = jnp.minimum(lo, rcount)
    matches = (hi - lo).astype(jnp.int32)
    cnt = jnp.where(lvalid, matches, 0)
    if how == "left":
        cnt = jnp.where(lvalid & (matches == 0), 1, cnt)
    incl = jnp.cumsum(cnt)
    excl = incl - cnt
    total = incl[-1]
    s = jnp.arange(cap_out, dtype=jnp.int32)
    li_c = jnp.clip(jnp.searchsorted(incl, s, side="right"), 0, lcap - 1)
    matched = matches[li_c] > 0
    ri_c = rperm[jnp.clip(lo[li_c] + (s - excl[li_c]), 0, rcap - 1)]
    out_valid = s < jnp.minimum(total, cap_out)
    r_valid = out_valid & (matched if how == "left" else True)
    out = {name: jnp.where(out_valid, v[li_c], jnp.zeros((), v.dtype))
           for name, v in lcols.items()}
    for name, v in rcols.items():
        if name in rkeys:
            continue
        fill = jnp.asarray((null_fill or {}).get(name, 0), v.dtype)
        out[r_suffix_map.get(name, name)] = jnp.where(r_valid, v[ri_c], fill)
    if how == "left":
        out["_matched"] = (out_valid & matched).astype(jnp.int32)
    return out, jnp.minimum(total, cap_out).astype(jnp.int32), total > cap_out


def oracle_join(l, lcount, r, rcount, lkeys, rkeys, how, smap, null_fill):
    """Rows of the join in output order: left rows in order, each followed
    by its matching right rows in right-row order (the stable key sort)."""
    rkt = [tuple(r[k][j] for k in rkeys) for j in range(rcount)]
    rows = []
    for i in range(lcount):
        key = tuple(l[k][i] for k in lkeys)
        js = [j for j in range(rcount) if rkt[j] == key]
        for j in js or ([None] if how == "left" else []):
            row = {name: v[i] for name, v in l.items()}
            for name, v in r.items():
                if name not in rkeys:
                    row[smap.get(name, name)] = (
                        v[j] if j is not None else null_fill.get(name, 0))
            if how == "left":
                row["_matched"] = int(j is not None)
            rows.append(row)
    return rows


def _table(rng, cap, key_names, key_hi, extra):
    """Keys from one small range, padding included, so padding rows hold
    keys that would match if the join read them."""
    t = {k: rng.integers(0, key_hi, cap).astype(np.int32) for k in key_names}
    t.update({k: rng.normal(size=cap).astype(np.float32) for k in extra})
    return t


def _run(fn, l, lcount, r, rcount, lkeys, rkeys, cap_out, how, smap,
         null_fill):
    f = jax.jit(functools.partial(
        fn, lkeys=lkeys, rkeys=rkeys, cap_out=cap_out, r_suffix_map=smap,
        how=how, null_fill=null_fill))
    out, cnt, ovf = f({k: jnp.asarray(v) for k, v in l.items()},
                      jnp.int32(lcount),
                      {k: jnp.asarray(v) for k, v in r.items()},
                      jnp.int32(rcount))
    return {k: np.asarray(v) for k, v in out.items()}, int(cnt), bool(ovf)


def _check(l, lcount, r, rcount, lkeys, rkeys, cap_out, how,
           null_fill=None):
    smap = {c: c + "_r" if c in l else c for c in r if c not in rkeys}
    null_fill = null_fill or {}
    args = (l, lcount, r, rcount, lkeys, rkeys, cap_out, how, smap, null_fill)
    got = _run(phys.merge_join, *args)
    ref = _run(searchsorted_merge_join, *args)
    # the replaced formulation: identical columns, padding included
    assert got[1:] == ref[1:]
    assert got[0].keys() == ref[0].keys()
    for k in ref[0]:
        np.testing.assert_array_equal(got[0][k], ref[0][k], err_msg=k)

    out, cnt, ovf = got
    rows = oracle_join(l, lcount, r, rcount, lkeys, rkeys, how, smap,
                       null_fill)
    assert ovf == (len(rows) > cap_out)
    assert cnt == min(len(rows), cap_out)
    kept = rows[:cnt]
    names = sorted(out)
    got_rows = [tuple(out[k][i] for k in names) for i in range(cnt)]
    want_rows = [tuple(row[k] for k in names) for row in kept]
    np.testing.assert_array_equal(np.array(got_rows, np.float64),
                                  np.array(want_rows, np.float64))
    source = {v: k for k, v in smap.items()}
    for k in names:         # past the count: zero, or a right column's fill
        np.testing.assert_array_equal(
            out[k][cnt:], null_fill.get(source.get(k), 0), err_msg=k)
    return rows


# (how, key columns, lcap, lcount, rcap, rcount, key range, cap_out)
CASES = {
    "inner-1key-dups": ("inner", 1, 40, 33, 24, 20, 6, 400),
    "left-1key-dups": ("left", 1, 40, 33, 24, 20, 9, 400),
    "inner-2keys-dups": ("inner", 2, 48, 48, 32, 27, 3, 500),
    "left-2keys-dups": ("left", 2, 48, 41, 32, 32, 4, 500),
    "inner-empty-left": ("inner", 1, 16, 0, 12, 12, 4, 64),
    "left-empty-left": ("left", 1, 16, 0, 12, 12, 4, 64),
    "inner-empty-right": ("inner", 1, 16, 16, 12, 0, 4, 64),
    "left-empty-right": ("left", 2, 16, 11, 12, 0, 4, 64),
    "inner-both-empty": ("inner", 1, 16, 0, 12, 0, 4, 64),
    "left-both-empty": ("left", 1, 16, 0, 12, 0, 4, 64),
    "inner-padded-unique": ("inner", 1, 64, 17, 64, 9, 1000, 128),
    "left-padded-1key": ("left", 1, 64, 30, 64, 5, 12, 128),
    "inner-cap-below-total": ("inner", 1, 40, 40, 30, 30, 3, 150),
    "left-cap-below-total": ("left", 2, 40, 35, 30, 26, 2, 97),
    "inner-cap-one": ("inner", 1, 8, 8, 8, 8, 2, 1),
}


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("case", list(CASES))
def test_merge_join_matches_oracle_and_searchsorted(case, seed):
    how, nk, lcap, lcount, rcap, rcount, key_hi, cap_out = CASES[case]
    rng = np.random.default_rng(seed)
    lkeys = ("a", "b")[:nk]
    rkeys = ("ra", "rb")[:nk]
    l = _table(rng, lcap, lkeys, key_hi, ("x", "y"))
    r = _table(rng, rcap, rkeys, key_hi, ("x", "w"))
    null_fill = {"w": np.float32(np.nan)} if how == "left" else None
    rows = _check(l, lcount, r, rcount, lkeys, rkeys, cap_out, how,
                  null_fill)
    if "cap-below" in case or "cap-one" in case:
        assert len(rows) > cap_out
    if "dups" in case:      # many-to-many: some left row matches twice
        assert len(rows) > lcount


@pytest.mark.parametrize("how", ["inner", "left"])
@pytest.mark.parametrize("dtype", [np.int32, np.float32])
def test_merge_join_sentinel_valued_keys(how, dtype):
    """Valid keys equal to the dtype's max (the padding sentinel) match
    each other and never the padding rows, which carry the same value."""
    big = np.iinfo(dtype).max if dtype == np.int32 else np.finfo(dtype).max
    lk = np.array([big, 1, big, 2, 3, big, big, big], dtype)
    rk = np.array([2, big, big, 1, big, big, big], dtype)
    l = {"k": lk, "x": np.arange(8, dtype=np.float32)}
    r = {"rk": rk, "w": np.arange(7, dtype=np.float32) + 10}
    rows = _check(l, 6, r, 4, ("k",), ("rk",), 40, how)
    # left rows 0, 2, 5 hold big; right rows 1, 2 do (rows 4.. are padding)
    assert sum(row["k"] == big for row in rows) == 6


@pytest.mark.parametrize("how", ["inner", "left"])
def test_merge_join_salted_keys(how):
    """A salted skew join (keys + salt column from salt_probe/salt_build)
    returns the unsalted join's rows."""
    rng = np.random.default_rng(7)
    lk = np.where(rng.random(60) < 0.6, 3, rng.integers(0, 8, 60))
    l = {"k": lk.astype(np.int32), "x": rng.normal(size=60).astype(np.float32)}
    r = {"rk": rng.integers(0, 8, 20).astype(np.int32),
         "w": rng.normal(size=20).astype(np.float32)}
    r["rk"][:3] = 3
    R, hot = 4, [(3,)]
    lsalt, _ = phys.salt_probe({k: jnp.asarray(v) for k, v in l.items()},
                               jnp.int32(55), ("k",), hot, R)
    rsalt, rcnt, ovf = phys.salt_build(
        {k: jnp.asarray(v) for k, v in r.items()}, jnp.int32(18), ("rk",),
        hot, R, cap_out=80)
    assert not bool(ovf)
    ls = {k: np.asarray(v) for k, v in lsalt.items()}
    rs = {k: np.asarray(v) for k, v in rsalt.items()}
    keys_l, keys_r = ("k", phys.SALT_COL), ("rk", phys.SALT_COL)
    got = _run(phys.merge_join, ls, 55, rs, int(rcnt), keys_l, keys_r, 400,
               how, {"w": "w"}, {})
    ref = _run(searchsorted_merge_join, ls, 55, rs, int(rcnt), keys_l,
               keys_r, 400, how, {"w": "w"}, {})
    assert got[1:] == ref[1:]
    for k in ref[0]:
        np.testing.assert_array_equal(got[0][k], ref[0][k], err_msg=k)
    out, cnt, _ = got
    want = oracle_join(l, 55, r, 18, ("k",), ("rk",), how, {"w": "w"}, {})
    names = ["k", "w", "x"] + (["_matched"] if how == "left" else [])
    assert cnt == len(want)
    assert sorted(tuple(float(out[n][i]) for n in names) for i in range(cnt)) \
        == sorted(tuple(float(row[n]) for n in names) for row in want)


def _primitives(jaxpr):
    names = set()
    for eqn in jaxpr.eqns:
        names.add(eqn.primitive.name)
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else [p]):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    names |= _primitives(sub.jaxpr)
                elif isinstance(sub, jax.extend.core.Jaxpr):
                    names |= _primitives(sub)
    return names


@pytest.mark.parametrize("how", ["inner", "left"])
def test_merge_join_has_no_loop(how):
    """The match ranges and the output expansion are prefix sums and
    scatters: no ``while`` or ``scan`` (a binary search) in the program."""
    l = {"a": jnp.zeros((64,), jnp.int32), "x": jnp.zeros((64,))}
    r = {"ra": jnp.zeros((16,), jnp.int32), "w": jnp.zeros((16,))}

    def prims(fn):
        f = functools.partial(fn, lkeys=("a",), rkeys=("ra",), cap_out=256,
                              r_suffix_map={}, how=how)
        return _primitives(jax.make_jaxpr(f)(l, 60, r, 9).jaxpr)

    assert prims(searchsorted_merge_join) & {"while", "scan"}  # probe works
    found = prims(phys.merge_join)
    assert not found & {"while", "scan"}, found
    assert {"cumsum", "scatter", "scatter-add"} <= found, found
