"""Operators (core/physical.py ``merge_join``): device time of the ops under
the named scopes of ``MergeJoin`` operators per query of the traced window,
averaged over the cell's chips."""
from chipbench import phases


def read(run):
    return phases.operator_ms(run, "MergeJoin")
