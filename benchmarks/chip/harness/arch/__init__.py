"""The architectures the benchmark knows, each found by the name in a
configuration file's ``reference`` key, and nothing else.

* ``harness/arch/<name>.py`` -- ``model_config(config)``, the program's
  ``ModelConfig`` for the configuration (raising where the program
  would run something else), and the counts of the algorithm's work:
  ``layer_matmul_params``, ``n_params``, ``attention_flops`` and
  ``train_flops_per_step`` (``harness.counts`` says what each counts);
* ``reference/<name>.py`` -- the plain float32 model: ``loss``,
  ``hidden``, ``logits_rows``, ``frozen`` and ``MATMULS``.

A new architecture is those two new files, with no edit to this one or
to any other.
"""
from __future__ import annotations

import importlib
import pkgutil


def known() -> list[str]:
    """The names of the architectures that have a module here."""
    return sorted(m.name for m in pkgutil.iter_modules(__path__))


def module(config: dict, package: str = __name__):
    """The module of ``package`` (this one, or ``"reference"`` for the
    plain model) for the architecture that ``config["reference"]``
    names. An unknown name raises ValueError, which lists the known."""
    name = config.get("reference")
    if name not in known():
        raise ValueError(f"{config.get('name')}: unknown architecture "
                         f"{name!r} in the configuration's 'reference' "
                         f"key; known: {', '.join(known())}")
    return importlib.import_module(f"{package}.{name}")
