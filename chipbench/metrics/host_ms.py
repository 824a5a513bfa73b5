"""Serving layer (runtime/session.py): per request, the latency minus the
session's ``QueryRecord.exec_s``, as a mean: queueing, admission,
fingerprint, rebind, readback and ``to_numpy``."""
import numpy as np


def read(run):
    rs = [r for r in run.answered if r.record is not None]
    if not rs:
        return None
    return float(np.mean([r.latency - r.record.exec_s for r in rs])) * 1e3
