"""Serving layer (runtime/session.py ``Session._execute``): the mean
``QueryRecord.lock_s``, the wait for the session's mesh lock."""
from chipbench import phases


def read(run):
    return phases.mean_ms(run, "lock_s")
