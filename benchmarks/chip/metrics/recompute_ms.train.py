"""Model-step layer: device self-time per step of the forward's re-run
inside the backward, the ops under the program's ``model`` scope that
``jax.checkpoint`` marks ``rematted_computation``. Read from the trace
by ``harness.scopes``."""
from harness.scopes import RECOMPUTE, ms_per_step


def read(run):
    return ms_per_step(run, "model", RECOMPUTE)
