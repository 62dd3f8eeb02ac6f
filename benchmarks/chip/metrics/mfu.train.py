"""Model-step layer: forward + backward FLOPs of the window's steps (no
recomputation) over window x chips x the chip's bf16 peak."""
from harness.counts import train_flops_per_step
from harness.peaks import peak


def read(run):
    if run.kind != "train" or run.window_s <= 0:
        return None
    tr = run.cell.traffic
    flops = run.steps * train_flops_per_step(run.cell.config, tr["batch"],
                                             tr["seq"])
    return 100.0 * flops / (run.window_s * run.chips
                            * peak(run.device_kind)["bf16_flops"])
