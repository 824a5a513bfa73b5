"""TPCx-BB Q05 with the kit's default parameters, up to the logistic
regression: clicks per user on the category 'Books' and on categories 1-7
(``web_clickstreams`` joined with ``item``, grouped by user), joined with
``customer`` and ``customer_demographics`` for the user's college
education and gender."""
from __future__ import annotations

import numpy as np

KEY = "wcs_user_sk"
CATEGORIES = tuple(range(1, 8))
EXACT = ("clicks_in_category",
         *(f"clicks_in_{k}" for k in CATEGORIES),
         "college_education", "male")
FLOAT = ()
BOOKS = 8           # the i_category_id that stands for i_category = 'Books'
COLLEGE = ("Advanced Degree", "College", "4 yr Degree", "2 yr Degree")


def build(hf, t: dict):
    wcs, it = t["web_clickstreams"], t["item"]
    c, cd = t["customer"], t["customer_demographics"]
    j = wcs.merge(it, on=("wcs_item_sk", "i_item_sk"))
    cat = j["i_category_id"]
    clicks = j.groupby("wcs_user_sk").agg(
        clicks_in_category=hf.sum_(cat == BOOKS),
        **{f"clicks_in_{k}": hf.sum_(cat == k) for k in CATEGORIES})
    u = clicks.merge(c, on=("wcs_user_sk", "c_customer_sk"))
    u = u.merge(cd, on=("c_current_cdemo_sk", "cd_demo_sk"))
    u = u.assign(college_education=u["cd_education_status"].isin(COLLEGE),
                 male=u["cd_gender"] == "M")
    return u[[KEY, *EXACT]]


def probe(build: np.ndarray, keys: np.ndarray) -> np.ndarray:
    """Row of ``build`` (unique keys) that holds each of ``keys``; -1 for
    a key it lacks."""
    pos = np.full(max(int(build.max()), int(keys.max())) + 1, -1, np.int64)
    pos[build] = np.arange(len(build))
    return pos[keys]


def reference(h: dict) -> dict:
    wcs, it = h["web_clickstreams"], h["item"]
    c, cd = h["customer"], h["customer_demographics"]
    ii = probe(it["i_item_sk"], wcs["wcs_item_sk"])
    user = wcs["wcs_user_sk"][ii >= 0]
    k = it["i_category_id"][ii[ii >= 0]]
    users, inv = np.unique(user, return_inverse=True)

    def clicks(on):
        return np.bincount(inv, weights=on, minlength=len(users)).astype(
            np.int64)

    counts = {"clicks_in_category": clicks(k == BOOKS),
              **{f"clicks_in_{v}": clicks(k == v) for v in CATEGORIES}}
    ci = probe(c["c_customer_sk"], users)
    di = np.full(len(users), -1, np.int64)
    di[ci >= 0] = probe(cd["cd_demo_sk"], c["c_current_cdemo_sk"][ci[ci >= 0]])
    keep = di >= 0
    r = di[keep]
    out = {KEY: users[keep], **{col: v[keep] for col, v in counts.items()}}
    out["college_education"] = np.isin(cd["cd_education_status"][r],
                                       COLLEGE).astype(np.int64)
    out["male"] = (cd["cd_gender"][r] == "M").astype(np.int64)
    return out
