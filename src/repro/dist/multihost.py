"""Multi-host process lifecycle under ``jax.distributed``.

One process per host, gloo (CPU/DCN) or the platform's native
collectives. The launcher calls :func:`initialize` before touching any
device; :mod:`tools.dist_launch` spawns N such processes on one machine
for tests and local rehearsal, passing the coordination triple through
environment variables:

======================  =======================================
``REPRO_COORDINATOR``   ``host:port`` of process 0's coordinator
``REPRO_NUM_PROCESSES`` total process count
``REPRO_PROCESS_ID``    this process's rank
======================  =======================================

Everything here degrades to a no-op in a single-process run, so the
same entry points work unmodified on a laptop and on a pod.

Process-0 semantics elsewhere in the stack key off
``jax.process_index()`` (checkpoint commits, LATEST repair, logging);
this module only owns initialization and barriers.
"""
from __future__ import annotations

import os

import jax

__all__ = ["ENV_COORDINATOR", "ENV_NUM_PROCESSES", "ENV_PROCESS_ID",
           "initialize", "active", "process_index", "process_count",
           "is_primary", "barrier"]

ENV_COORDINATOR = "REPRO_COORDINATOR"
ENV_NUM_PROCESSES = "REPRO_NUM_PROCESSES"
ENV_PROCESS_ID = "REPRO_PROCESS_ID"

_initialized = False


def initialize(coordinator: str | None = None,
               num_processes: int | None = None,
               process_id: int | None = None, *,
               timeout_secs: int = 120) -> bool:
    """Join the ``jax.distributed`` cluster, if one is configured.

    Arguments default to the ``REPRO_*`` environment variables; with
    neither flags nor env set (or ``num_processes <= 1``) this is a
    no-op returning False — the single-process path. A partial triple
    (coordinator + num_processes but no rank) raises ``ValueError``
    naming the missing flag/env var. Must run before the first
    device/backend use in the process.
    """
    global _initialized
    if _initialized:
        return True
    coordinator = coordinator or os.environ.get(ENV_COORDINATOR)
    if num_processes is None and os.environ.get(ENV_NUM_PROCESSES):
        num_processes = int(os.environ[ENV_NUM_PROCESSES])
    if process_id is None and os.environ.get(ENV_PROCESS_ID):
        process_id = int(os.environ[ENV_PROCESS_ID])
    if not coordinator or not num_processes or num_processes <= 1:
        return False
    if process_id is None:
        # jax.distributed.initialize(process_id=None) only works inside
        # auto-detecting cluster environments; anywhere else it dies
        # with an opaque backend error. Fail early and name the knob.
        raise ValueError(
            "multihost.initialize: coordinator and num_processes are set "
            "but process_id is not — pass process_id= (--process-id) or "
            f"set {ENV_PROCESS_ID}")
    # the CPU client ships cross-process collectives only via gloo;
    # harmless when another backend ends up selected
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=num_processes,
                               process_id=process_id,
                               initialization_timeout=timeout_secs)
    _initialized = True
    return True


def active() -> bool:
    """True when this process is part of a multi-process run."""
    return jax.process_count() > 1


def process_index() -> int:
    return jax.process_index()


def process_count() -> int:
    return jax.process_count()


def is_primary() -> bool:
    """Process-0 semantics: the one process that writes checkpoints,
    repairs LATEST, and logs."""
    return jax.process_index() == 0


def barrier(tag: str) -> None:
    """Block until every process reaches this point (no-op when
    single-process). ``tag`` must match across processes."""
    if active():
        from jax.experimental import multihost_utils
        multihost_utils.sync_global_devices(tag)
