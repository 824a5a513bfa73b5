"""Execution (core/lower.py ``Lowered.__call__``): how unevenly a query's
rows fall over the shards.  For each capacity site of each answered
query, ``QueryRecord.shard_rows`` holds every shard's requirement
estimate: rows, or for a join the product of its inputs' rows, whose
max/mean is the probe side's where the build side is replicated.  The
reading is the largest max/mean over the sites and queries of the window
(1 is even; 4 on four chips is every row on one).  A program whose
records lack ``shard_rows`` reads as nothing."""


def read(run):
    worst = None
    for r in run.answered:
        for rows in (getattr(r.record, "shard_rows", None) or {}).values():
            mean = sum(rows) / len(rows)
            if mean > 0:
                worst = max(worst or 0.0, max(rows) / mean)
    return worst
