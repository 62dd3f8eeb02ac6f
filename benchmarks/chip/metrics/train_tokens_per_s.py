"""Tokens trained in the window over the window's wall time, summed over
the cell's chips. The window ends in ``block_until_ready`` of the last
state."""


def read(run):
    if run.kind != "train" or run.window_s <= 0:
        return None
    return run.tokens / run.window_s
