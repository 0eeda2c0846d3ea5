"""Process start -> the window opens: imports, weights, compilation or cache reads, warm-up, the correctness checks."""


def read(run):
    return run.setup_s
