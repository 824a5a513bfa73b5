"""Closed loop: seconds from the window's start to the last answer on the
host inside the window, over the answers inside the window."""


def read(run):
    done = [r.done for r in run.in_window]
    return max(done) / len(done) if done else None
