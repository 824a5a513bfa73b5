"""Operators (core/physical.py): device time of the trace's ``while`` ops
(the join's ``searchsorted`` binary searches, with the ops they contain)
per query of the traced window, averaged over the cell's chips."""


def read(run):
    if run.trace is None or not run.answered:
        return None
    s = run.trace.class_s.get("loop", 0.0)
    return s / len(run.answered) * 1e3 if s > 0 else None
