"""Kernels layer: the grouped matmuls of the experts held here -- the
FLOPs the algorithm needs over the chip's bf16 peak -- over the summed
device time of the kernels' events in the trace.

Each expert layer runs SwiGLU's three projections (gate, up: D -> F;
down: F -> D) as grouped matmuls over the R = tokens x top-k x held /
router-width assignment rows routed here (expected: uniform routing;
2 x 8192 in ``sdar-30b-a3b.train.8k``), each 2 R D F FLOPs. A step makes
12 such calls a layer: 3 forward, 3 again in the backward's recompute
of the layer (the combine's gradient needs the down projection's
output), and for each projection one grouped matmul for its input's
gradient and one transposed (``tgmm``) for its weight's. So the window
holds steps x layers x 12 events; another count is an error: the
pattern then misses part of the work or takes another op for it.

On the TPU the events are the Mosaic custom calls named after the
kernels, ``gmm`` and ``tgmm`` (``jax.experimental.pallas.ops.tpu.
megablox``). Rows the kernel computes beyond a group's end to fill a
tile are the implementation's, not counted."""
from harness.arch.moe_gqa import routed_rows_per_token
from harness.peaks import peak

KERNEL = r'%t?gmm(?:\.\d+)? = .*custom_call_target="tpu_custom_call"'
CALLS_PER_LAYER = 12


def read(run):
    if run.kind != "train" or run.trace is None or run.steps == 0:
        return None
    spent = run.trace.kernel_s(KERNEL)
    if not spent:
        return None
    cfg, tr = run.cell.config, run.cell.traffic
    calls = CALLS_PER_LAYER * cfg["num_hidden_layers"]
    want = run.steps * calls * run.trace.n_devices
    if run.trace.kernel_n(KERNEL) != want:
        raise ValueError(
            f"grouped_matmul_roofline: {run.trace.kernel_n(KERNEL)} kernel "
            f"events in the window, expected {want} (steps x layers x "
            f"{CALLS_PER_LAYER} x devices)")
    rows = tr["batch"] * tr["seq"] * routed_rows_per_token(cfg)
    flops = (run.steps * calls * 2 * rows * cfg["hidden_size"]
             * cfg["moe_intermediate_size"])
    least = flops / run.chips / peak(run.device_kind)["bf16_flops"]
    return 100.0 * least / spent
