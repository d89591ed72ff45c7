"""Seeds: any whole number (wider than 32 bits too) to a 32-bit seed
for JAX and to NumPy generators, deterministically."""
from __future__ import annotations

import numpy as np


def seed32(seed: int, *path: int) -> int:
    """A 32-bit seed mixed from ``seed`` and an optional path of ints."""
    return int(np.random.SeedSequence([int(seed) % 2**63, *path])
               .generate_state(1)[0])


def rng(seed: int, *path: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence(
        [int(seed) % 2**63, *path]))


def seed_arg(seed: int) -> np.uint32:
    """The 32-bit seed that jitted code takes as an argument, so that one
    compiled program serves every seed."""
    return np.uint32(seed32(seed))
