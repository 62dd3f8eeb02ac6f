"""Benchmark runner — one section per paper table/figure + roofline.

Prints ``name,us_per_call,derived`` CSV rows (shared convention).
Usage: ``PYTHONPATH=src python -m benchmarks.run [--only fig2,table4]
[--profile [DIR]] [--smoke]``

``--smoke`` asks each section for its shrunken CI variant; sections
whose ``run()`` takes no ``smoke`` parameter run at full size as before.

``--profile`` wraps every section in a :class:`repro.profile.
ProfileSession` and writes one ``repro.profile/v1`` JSON artifact per
section to DIR (default ``profiles/``): per-step wall timers (every
``row`` the bench printed), memory high-water, and per-dtype collective
bytes recovered from the optimized HLO of each jitted callable the bench
timed — including the CPU reduce-scatter→all-reduce+slice fallback
count. Validate artifacts with ``python tools/check_profile.py DIR/*.json``.
"""
from __future__ import annotations

import argparse
import inspect
import os
import sys
import time

SECTIONS = [
    ("fig2_theory", "benchmarks.bench_theory"),
    ("table3_bottleneck", "benchmarks.bench_bottleneck"),
    ("table4_accuracy", "benchmarks.bench_accuracy"),
    ("fig5_tradeoff", "benchmarks.bench_tradeoff"),
    ("fig9_cancellation", "benchmarks.bench_cancellation"),
    ("fig10_sub16", "benchmarks.bench_sub16"),
    ("fig11_combined", "benchmarks.bench_combined"),
    ("fig12_fp16", "benchmarks.bench_fp16"),
    ("appB_kernels", "benchmarks.bench_kernels"),
    ("roofline", "benchmarks.bench_roofline"),
    ("fsdp_memory", "benchmarks.bench_fsdp"),
    ("serve_batching", "benchmarks.bench_serve"),
    ("grad_wire", "benchmarks.bench_grad_wire"),
    ("grad_wire_sweep", "benchmarks.bench_grad_wire_sweep"),
    ("decode_attn", "benchmarks.bench_decode_attention"),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated section prefixes to run")
    ap.add_argument("--profile", nargs="?", const="profiles", default=None,
                    metavar="DIR",
                    help="emit one repro.profile/v1 JSON per section "
                         "into DIR (default: profiles/)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized runs for sections that support it")
    args = ap.parse_args()
    only = args.only.split(",") if args.only else None
    print("name,us_per_call,derived")
    failed = []
    for name, module in SECTIONS:
        if only and not any(name.startswith(o) for o in only):
            continue
        t0 = time.time()
        mod = __import__(module, fromlist=["run"])
        sess = None
        if args.profile is not None:
            from repro.profile import ProfileSession
            sess = ProfileSession(name)
            sess.__enter__()
        kwargs = {}
        if args.smoke and "smoke" in inspect.signature(mod.run).parameters:
            kwargs["smoke"] = True
        try:
            mod.run(**kwargs)
        except Exception as e:  # finish the other sections, then exit 1
            failed.append(name)
            if sess is not None:
                sess.error = f"{type(e).__name__}: {e}"
            print(f"{name}_ERROR,0.0,{type(e).__name__}:{e}", file=sys.stderr)
            print(f"{name}_ERROR,0.0,{type(e).__name__}")
        finally:
            if sess is not None:
                sess.__exit__(None, None, None)
                path = os.path.join(args.profile, f"{name}.json")
                sess.write(path)
                print(f"# profile -> {path}", file=sys.stderr)
        print(f"# section {name} took {time.time() - t0:.1f}s", file=sys.stderr)
    if failed:
        sys.exit(f"sections failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
