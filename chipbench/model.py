"""Seeds: every bit of ``--seed`` goes into what a run draws.

What builds a configuration's model lies in the module of its
architecture, ``architectures/<architectures[0]>.py``, found by
`chipbench.bench.architecture`.
"""
from __future__ import annotations

import numpy as np


def seed_words(seed: int, n: int = 2) -> np.ndarray:
    """``n`` uint32 words drawn from any non-negative whole ``seed``
    (more than 32 bits welcome)."""
    if seed < 0:
        raise ValueError(f"--seed {seed}: must be >= 0")
    return np.random.SeedSequence(seed).generate_state(n, np.uint32)


def prng_key(seed: int, stream: int = 0):
    """A JAX key from all bits of ``seed`` (``PRNGKey`` keeps only 32)."""
    import jax
    words = seed_words(seed, 2 * (stream + 1))[2 * stream:2 * stream + 2]
    return jax.random.wrap_key_data(np.asarray(words, np.uint32),
                                    impl="threefry2x32")
