"""Optimizer layer: device self-time per step of the whole update, every
op under the program's ``optimizer`` scope: the random bits of
stochastic rounding (``sr_bits``), the leaves' relayout into the
kernel's tiles and back (``layout``) and the fused kernel itself
(``fused_adamw``). Read from the trace by ``harness.scopes``."""
from harness.scopes import ms_per_step


def read(run):
    return ms_per_step(run, "optimizer")
