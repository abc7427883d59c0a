"""Set-up seconds: process start -> window start, compiles and warm-up included."""


def read(run):
    return run.setup_s
