"""Time-weighted mean difference to balance of the applied placement under
the true demand, replayed by the reference after the window."""


def read(run):
    return run.d2b_mean
