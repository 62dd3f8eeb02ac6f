"""Model-step layer: device self-time per step of the forward pass, the
ops under the program's ``model`` scope that autodiff marks ``jvp(...)``
(embedding, attention, MLP, norms, head and loss; read from the trace by
``harness.scopes``). The forward's re-run under remat is not in it:
that is ``recompute_ms.train``."""
from harness.scopes import FORWARD, ms_per_step


def read(run):
    return ms_per_step(run, "model", FORWARD)
