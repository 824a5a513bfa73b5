"""Execution (core/lower.py ``Lowered.__call__``): the mean
``QueryRecord.run_s``, from the program's dispatch until its overflow flags
are on the host."""
from chipbench import phases


def read(run):
    return phases.mean_ms(run, "run_s")
