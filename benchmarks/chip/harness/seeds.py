"""Keys from ``--seed``. A seed may exceed 32 bits; every key is drawn
through ``numpy.random.SeedSequence``, so any whole number works."""
from __future__ import annotations

import jax
import numpy as np

PURPOSES = {"weights": 1, "data": 2}


def key(seed: int, purpose: str) -> jax.Array:
    words = np.random.SeedSequence([int(seed), PURPOSES[purpose]]
                                   ).generate_state(2, dtype=np.uint32)
    return jax.random.wrap_key_data(words.astype(np.uint32),
                                    impl="threefry2x32")


def program_seed(seed: int) -> int:
    """The seed handed to the program's step (a traced int32 there)."""
    return int(seed) % (2 ** 31)
