"""Model-step layer: device self-time per step of the backward pass, the
ops under the program's ``model`` scope that autodiff marks
``transpose(jvp(...))``, less the forward's re-run under remat
(``recompute_ms.train``). Read from the trace by ``harness.scopes``."""
from harness.scopes import BACKWARD, ms_per_step


def read(run):
    return ms_per_step(run, "model", BACKWARD)
