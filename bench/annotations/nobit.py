"""No annotation: every write carries the program's "no next write"
sentinel, as every scheme but future knowledge (FK) is fed."""

from __future__ import annotations

import numpy as np
from repro.core.placement.jax_schemes import NOBIT


def make(config: dict, traffic):
    block = np.full((traffic.k, traffic.stream.n_volumes), NOBIT, np.int32)

    def annotate(j: int, lbas: np.ndarray) -> np.ndarray:
        return block
    return annotate
