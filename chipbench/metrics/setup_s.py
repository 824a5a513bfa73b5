"""Process start to the first request of the window: imports, tables,
registration, compile or cache load, warm-up."""


def read(run):
    return run.setup_s
