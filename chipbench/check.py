"""The comparison that decides ``correct``: each answer the timed path
produced against the query's plain numpy reference over the same tables.

Two kinds of number are compared, each against its own limit
(``chipbench/limits/<config>.<query>.json``):

* ``wrong_rows``: rows of all answers together whose key is missing,
  extra or repeated, or whose exact (integer) columns differ; limit 0;
* ``<col>_rel_err``: for each float column, the largest relative gap
  ``|got - want| / |want|`` over all rows of all answers.
"""
from __future__ import annotations

import numpy as np


def sorted_by_key(key: str, got: dict) -> dict:
    order = np.argsort(got[key], kind="stable")
    return {k: np.asarray(v)[order] for k, v in got.items()}


def compare_one(q, got: dict, want: dict) -> dict:
    """Numbers of one answer (column dict in any row order) against the
    reference ``want`` (sorted by key)."""
    got = sorted_by_key(q.KEY, got)
    gk, wk = got[q.KEY], want[q.KEY]
    uniq, first = np.unique(gk, return_index=True)
    wrong = len(gk) - len(uniq)                        # repeated keys
    _, gi, wi = np.intersect1d(uniq, wk, assume_unique=True,
                               return_indices=True)
    gi = first[gi]
    wrong += (len(uniq) - len(gi)) + (len(wk) - len(wi))  # extra + missing
    bad = np.zeros(len(gi), bool)
    for c in q.EXACT:
        bad |= (got[c][gi].astype(np.int64)
                != np.asarray(want[c])[wi].astype(np.int64))
    wrong += int(bad.sum())
    if hasattr(q, "extra_wrong"):
        wrong += q.extra_wrong(got, want)
    out = {"wrong_rows": wrong}
    for c in q.FLOAT:
        w = np.asarray(want[c], np.float64)[wi]
        g = got[c][gi].astype(np.float64)
        gap = np.abs(g - w) / np.maximum(np.abs(w), np.finfo(np.float64).tiny)
        out[f"{c}_rel_err"] = float(gap.max()) if len(gap) else 0.0
    return out


def compare(q, answers: list, want: dict, limits: dict) -> tuple[dict, int]:
    """``(numbers, bad)``: the numbers over all ``answers`` (wrong rows
    summed, each relative gap the largest of any answer) and how many
    answers break a limit on their own."""
    total = {"wrong_rows": 0, **{f"{c}_rel_err": 0.0 for c in q.FLOAT}}
    bad = 0
    for got in answers:
        one = compare_one(q, got, want)
        bad += any(v > limits[k] for k, v in one.items())
        total["wrong_rows"] += one["wrong_rows"]
        for c in q.FLOAT:
            total[f"{c}_rel_err"] = max(total[f"{c}_rel_err"],
                                        one[f"{c}_rel_err"])
    return total, bad


def judge(numbers: dict, limits: dict, missing: int) -> tuple[bool, dict]:
    """``(correct, {name: {"value", "limit"}})``: every number at or under
    its limit and no answer missing.  A number without a limit is an error
    of the benchmark's files, not a pass."""
    table = {"missing_answers": {"value": missing, "limit": 0}}
    for name, v in numbers.items():
        if name not in limits:
            raise KeyError(f"no limit for compared number {name!r}")
        table[name] = {"value": v, "limit": limits[name]}
    ok = all(e["value"] <= e["limit"] for e in table.values())
    return ok, table
