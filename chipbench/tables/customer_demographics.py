"""TPCx-BB ``customer_demographics``, which the kit takes over from TPC-DS:
the fixed cross product of 2 genders x 5 marital statuses x 7 education
levels x 20 purchase estimates x 4 credit ratings x 7 x 7 x 7 dependant
counts, ``ROWS`` = 1,920,800 rows at every scale factor, so it reads no
scale key and the CPU tests' cut holds the whole table too.

Row ``i`` is the cross product's row with gender varying fastest, then
education: ``cd_gender`` is ``GENDERS[i % 2]``, ``cd_education_status``
``EDUCATION[(i // 2) % 7]``, so any 14 consecutive rows hold every pair.
Only the columns Q05 reads are made: the marital status, purchase
estimate, credit rating and dependant counts are left out, and so the
table is the same on every seed.
"""
from __future__ import annotations

import numpy as np

SEED_OFFSET = 4
TINY: dict = {}
GENDERS = ("M", "F")
EDUCATION = ("Primary", "Secondary", "College", "2 yr Degree",
             "4 yr Degree", "Advanced Degree", "Unknown")
ROWS = 2 * 5 * 7 * 20 * 4 * 7 * 7 * 7


def make(scale: dict, spec: dict, seed: int) -> dict:
    i = np.arange(ROWS)
    return {
        "cd_demo_sk": i.astype(np.int32),
        "cd_gender": np.array(GENDERS, object)[i % len(GENDERS)],
        "cd_education_status": np.array(EDUCATION, object)[
            (i // len(GENDERS)) % len(EDUCATION)],
    }
