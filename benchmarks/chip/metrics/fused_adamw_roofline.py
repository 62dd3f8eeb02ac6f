"""Kernels layer: the fused SR-AdamW update's least time -- the bytes the
algorithm moves (read w, m, v, g, c; write w, m, v, c; bf16) over the
chip's HBM bandwidth -- over the summed device time of the kernel's
events in the trace. The random-bits pass and the padding copies lie
outside the kernel's events and are not counted either way.

On the TPU the kernel's event is named after the jitted step, not the
kernel: it is the Mosaic custom call that takes the update's seven f32
scalars (lr, b1, b2, eps, wd and the two bias corrections) as one
``f32[1,7]`` operand, or any op that names ``fused_adamw``. The update
runs once per parameter leaf and step, so a count of matched events
that differs from that is an error: the pattern then misses part of the
kernel or takes another op for it."""
from harness.counts import adamw_bytes
from harness.peaks import peak

KERNEL = (r'fused_adamw|custom-call\(.*f32\[1,7\].*'
          r'custom_call_target="tpu_custom_call"')


def read(run):
    if run.kind != "train" or run.trace is None or run.steps == 0:
        return None
    spent = run.trace.kernel_s(KERNEL)
    if not spent:
        return None
    want = run.steps * run.extra["param_leaves"] * run.trace.n_devices
    if run.trace.kernel_n(KERNEL) != want:
        raise ValueError(
            f"fused_adamw_roofline: {run.trace.kernel_n(KERNEL)} kernel "
            f"events in the window, expected {want} (steps x parameter "
            f"leaves x devices)")
    least = (run.steps * adamw_bytes(run.cell.config) / run.chips
             / peak(run.device_kind)["hbm_bytes_per_s"])
    return 100.0 * least / spent
