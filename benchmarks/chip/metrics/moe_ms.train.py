"""Model-step layer: device self-time per step of the expert layer, the
ops under the program's ``model/mlp`` scope in every direction (forward,
backward and recompute). In a configuration whose every layer is an
expert layer that is the whole of it: router, dispatch, the experts'
grouped matmuls and combine (``harness.scopes`` reads those sub-scopes
as ``model/mlp``). Read from the trace by ``harness.scopes``; None where
the program names no scope."""
from harness.scopes import of_run

MLP = "model/mlp"


def read(run):
    got = of_run(run)
    if got is None:
        return None
    spent = sum(s for (_, path), s in got.seconds.items() if path == MLP)
    return 1e3 * spent / run.steps
