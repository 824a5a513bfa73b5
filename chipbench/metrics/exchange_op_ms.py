"""Operators (core/physical.py ``exchange``): device time of the ops under
the named scopes of ``HashExchange`` operators (routing hashes, the scatter
into the send buffer, the all-to-alls) per query of the traced window,
averaged over the cell's chips."""
from chipbench import phases


def read(run):
    return phases.operator_ms(run, "HashExchange")
