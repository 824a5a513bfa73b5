"""Serving layer (runtime/session.py): the mean ``QueryRecord.plan_s``;
on a plan-cache hit the fingerprint, cache key, lookup and scan rebind."""
from chipbench import phases


def read(run):
    return phases.mean_ms(run, "plan_s")
