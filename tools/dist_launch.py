#!/usr/bin/env python
"""Spawn an N-process ``jax.distributed`` run on one machine (CPU/gloo).

The local stand-in for a multi-host cluster: one subprocess per
simulated host, each with its own jax process id and (by default) one
CPU device, coordinated over a loopback TCP port. Used by
``tests/test_multihost.py`` to rehearse host death, preemption, and
elastic resume; usable directly for manual runs::

    PYTHONPATH=src python tools/dist_launch.py -n 2 -- \
        python -m repro.launch.train --arch qwen2.5-3b --reduced \
        --steps 20 --batch 4 --seq 16 --ckpt-dir /tmp/run1

Every child gets the ``REPRO_COORDINATOR`` / ``REPRO_NUM_PROCESSES`` /
``REPRO_PROCESS_ID`` triple (consumed by
``repro.dist.multihost.initialize``) plus ``JAX_NUM_CPU_DEVICES`` so the
global device count is ``nprocs × devices_per_proc``. A stray
``XLA_FLAGS`` device-count override from the parent is dropped — it
would multiply devices per process and break the simulated topology.
"""
from __future__ import annotations

import argparse
import os
import signal
import socket
import subprocess
import sys
import time
from pathlib import Path

SRC = str(Path(__file__).resolve().parent.parent / "src")


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def launch(argv: list[str], nprocs: int, *, devices_per_proc: int = 1,
           env: dict | None = None, log_dir: str | Path | None = None,
           coordinator: str | None = None) -> list[subprocess.Popen]:
    """Start ``nprocs`` copies of ``argv``; returns live Popen handles.

    ``log_dir`` redirects each rank's stdout+stderr to ``rank<i>.log``
    (otherwise children inherit this process's streams, interleaved).
    """
    coordinator = coordinator or f"127.0.0.1:{free_port()}"
    base = dict(os.environ if env is None else env)
    base.pop("XLA_FLAGS", None)
    base["JAX_PLATFORMS"] = "cpu"       # loopback gloo ranks; never the chip
    base["JAX_NUM_CPU_DEVICES"] = str(devices_per_proc)
    base["REPRO_COORDINATOR"] = coordinator
    base["REPRO_NUM_PROCESSES"] = str(nprocs)
    pypath = base.get("PYTHONPATH", "")
    if SRC not in pypath.split(os.pathsep):
        base["PYTHONPATH"] = SRC + (os.pathsep + pypath if pypath else "")
    if log_dir is not None:
        log_dir = Path(log_dir)
        log_dir.mkdir(parents=True, exist_ok=True)
    procs = []
    for i in range(nprocs):
        env_i = dict(base)
        env_i["REPRO_PROCESS_ID"] = str(i)
        if log_dir is not None:
            out = open(log_dir / f"rank{i}.log", "wb")
        else:
            out = None
        procs.append(subprocess.Popen(
            argv, env=env_i, stdout=out, stderr=subprocess.STDOUT if out else None))
        if out is not None:
            out.close()  # child holds its own descriptor
    return procs


def wait(procs: list[subprocess.Popen], timeout: float = 600.0,
         *, kill_stragglers: bool = True) -> list[int]:
    """Wait for every child; returns per-rank exit codes. After the
    deadline (or once any rank fails, if ``kill_stragglers``) remaining
    ranks are SIGKILLed — a dead peer leaves survivors blocked in a
    gloo collective, there is nothing to wait politely for."""
    deadline = time.time() + timeout
    codes: list[int | None] = [None] * len(procs)
    while any(c is None for c in codes):
        for i, p in enumerate(procs):
            if codes[i] is None:
                codes[i] = p.poll()
        pending = [i for i, c in enumerate(codes) if c is None]
        if not pending:
            break
        failed = any(c not in (None, 0) for c in codes)
        if time.time() > deadline or (kill_stragglers and failed):
            for i in pending:
                procs[i].kill()
            for i in pending:
                procs[i].wait()
                codes[i] = procs[i].returncode
            break
        time.sleep(0.2)
    return [int(c) for c in codes]


def terminate(procs: list[subprocess.Popen], sig=signal.SIGTERM) -> None:
    for p in procs:
        if p.poll() is None:
            p.send_signal(sig)


def main() -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("-n", "--nprocs", type=int, default=2)
    ap.add_argument("--devices-per-proc", type=int, default=1)
    ap.add_argument("--timeout", type=float, default=600.0)
    ap.add_argument("--log-dir", default=None,
                    help="write per-rank logs here instead of interleaving")
    ap.add_argument("cmd", nargs=argparse.REMAINDER,
                    help="command to run (prefix with --)")
    args = ap.parse_args()
    cmd = args.cmd[1:] if args.cmd and args.cmd[0] == "--" else args.cmd
    if not cmd:
        ap.error("no command given (append: -- python -m repro.launch.train ...)")
    procs = launch(cmd, args.nprocs, devices_per_proc=args.devices_per_proc,
                   log_dir=args.log_dir)
    codes = wait(procs, timeout=args.timeout)
    for i, c in enumerate(codes):
        if c != 0:
            print(f"[dist_launch] rank {i} exited {c}", file=sys.stderr)
    return max(codes)


if __name__ == "__main__":
    sys.exit(main())
