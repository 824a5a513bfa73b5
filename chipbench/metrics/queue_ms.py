"""Serving layer (runtime/session.py ``Session.submit``): the mean
``QueryRecord.queue_s``, from ``submit()`` to a worker's start (the
admission semaphore and the worker pool's queue)."""
from chipbench import phases


def read(run):
    return phases.mean_ms(run, "queue_s")
