"""Serving layer (core/table.py ``DTable.to_numpy``): the mean
``QueryRecord.readback_s``, every padded column of the answer read to the
host."""
from chipbench import phases


def read(run):
    return phases.mean_ms(run, "readback_s")
