"""The highest ``peak_bytes_in_use`` over the cell's devices after the
window, in GiB."""


def read(run):
    return run.peak_bytes / 2**30 if run.peak_bytes else None
