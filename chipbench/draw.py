"""Seeded draws shared by the query kinds. No program import, no jax."""

from __future__ import annotations

import numpy as np


def stream(seed: int, phase: int, client: int) -> np.random.Generator:
    """One client's request stream: a function of the seed, the phase
    (0 warm-up, 1 window) and the client's number alone."""
    return np.random.default_rng([seed, 1000 + phase, client])
