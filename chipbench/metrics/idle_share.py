"""Device (TPU v5e): the share of the traced window in which no XLA op ran,
averaged over the cell's chips."""


def read(run):
    return None if run.trace is None else 100.0 * run.trace.idle_share
