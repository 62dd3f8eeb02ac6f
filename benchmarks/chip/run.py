"""Run one benchmark cell once on the chips of this machine.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` -> ``workloads``) names a configuration
file, a traffic file and, through ``limits/<cell>.json``, the limits
that decide ``correct``. Set-up (weights from the seed, compilation
from the persistent cache, warm-up) is timed as ``setup_s``; then the
window runs for ``--seconds``; then the check runs against the plain
float32 reference. ``--trace 1`` traces the window with the JAX
profiler and reports the per-layer metrics instead of the end-to-end
ones, and under ``scopes`` the step's largest parts by direction and
scope path, in ms per step. The last line of standard output is one
JSON object.

Every run prints, under ``readings``, each number the check read, also
those without a limit. ``--control fp8`` or ``--control half_batch``
(not used by the benchmark's own runs) runs no program: the fp8
control, or the half-batch fault, takes its place in the same check,
and the run prints its readings, never ``correct``: the runs that set
the limits.

A TPU is required; on any other platform the run fails and prints no
result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
TRACE_DIR = HERE / "out" / "trace"


def _args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("fp8", "half_batch"))
    return ap.parse_args(argv)


def measure(cell, seed: int, seconds: float, *, trace: bool, devices,
            t_start: float, control: str | None = None) -> dict:
    """Set-up, window and check of ``cell``; the result's JSON object."""
    import numpy as np

    from harness import check, device, spec, train
    from harness.trace import Tracer

    tracer = Tracer(str(TRACE_DIR)) if trace else None
    if cell.traffic["kind"] != "train":
        raise ValueError(f"{cell.name}: no harness for traffic of kind "
                         f"{cell.traffic['kind']!r}")
    rec = train.run(cell, seed, seconds, devices=devices, t_start=t_start,
                tracer=tracer, control=control)
    rec.device_kind = devices[0].device_kind
    if tracer:
        rec.trace = tracer.reduce()
    readings = rec.extra["readings"]
    # a control run reads no program and is never correct
    correct, shown = (check.judge(readings["program"], cell.limits)
                      if "program" in readings else (False, {}))
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    if not control:
        for m in wanted:
            value = spec.metric_reader(m["name"])(rec)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    dev = device.describe(devices)
    dev["memory_peak_bytes"] = rec.extra["memory_peak_bytes"]
    out = {"correct": correct, "setup_s": rec.extra["setup_s"],
           "attempted": rec.extra.get("attempted", rec.steps),
           "failed": rec.extra.get("failed", 0),
           "metrics": metrics, "device": dev,
           "window": rec.extra.get("intervals", {})}
    if rec.trace is not None:
        dev["busy_s"] = rec.trace.busy_s
        dev["window_s"] = rec.trace.window_s
        out["breakdown"] = rec.trace.breakdown()
    if rec.extra.get("scopes") is not None:
        out["scopes"] = rec.extra["scopes"].top(rec.steps)
    # every number read, compared or not (the control's with --control)
    out["readings"] = dict(readings)
    out["checks"] = shown
    return json.loads(json.dumps(out, default=lambda o: (
        o.tolist() if isinstance(o, np.ndarray) else float(o))))


def main(argv=None) -> int:
    args = _args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print("benchmark: the program under test (src/repro) is not in "
              "this checkout", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    from harness import device, spec
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = spec.load_cell(args.workload)
    try:
        devices = device.require(cell.chips)
    except device.NoChip as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    out = measure(cell, args.seed, args.seconds, trace=bool(args.trace),
                  devices=devices, t_start=T_START, control=args.control)
    import resource
    rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    print(f"set-up: {out['setup_s']} s; host memory peak: {rss} bytes",
          file=sys.stderr)
    print(f"window: {json.dumps(out['window'])}", file=sys.stderr)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(f"correct: {out['correct']}", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
