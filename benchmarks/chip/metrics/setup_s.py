"""Set-up: process start until the window can begin -- weights from the
seed, state, compilation (from the persistent cache after a cell's first
run), warm-up and, for training, the check steps. The check's own
bookkeeping (norms read from the state) is left out."""


def read(run):
    return run.extra["setup_s"]
