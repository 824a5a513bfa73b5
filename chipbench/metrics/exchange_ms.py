"""Operators (core/physical.py ``exchange``): device time of the trace's
all-to-all ops per query of the traced window, averaged over the cell's
chips."""


def read(run):
    if run.trace is None or not run.answered:
        return None
    s = run.trace.class_s.get("all_to_all", 0.0)
    return s / len(run.answered) * 1e3 if s > 0 else None
