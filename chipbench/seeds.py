"""Streams of random numbers drawn from the run's ``--seed``.

The seed may be any whole number, larger than 32 bits hold.  Each purpose
gets a stream of its own, so adding one draw never shifts another."""
from __future__ import annotations

import zlib

import numpy as np


def numpy_seed(seed: int, purpose: str) -> np.random.SeedSequence:
    return np.random.SeedSequence([int(seed) % 2**64,
                                   zlib.crc32(purpose.encode())])


def jax_key(seed: int, purpose: str):
    import jax
    word = numpy_seed(seed, purpose).generate_state(1, np.uint32)[0]
    return jax.random.PRNGKey(int(word))
