"""Execution (core/lower.py ``Lowered.__call__``): the mean
``QueryRecord.exec_s`` of the window's requests."""
import numpy as np


def read(run):
    rs = [r.record.exec_s for r in run.answered if r.record is not None]
    return float(np.mean(rs)) * 1e3 if rs else None
