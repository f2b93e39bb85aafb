"""Sequential writes: LBA ``i mod n_lbas`` at the phase's ``i``-th step, on
every volume. A volume being filled, a bulk load or a restore."""

from __future__ import annotations

import numpy as np


def make(params: dict, stream, phase: int):
    def chunk(q: int, k: int) -> np.ndarray:
        col = (q * k + np.arange(k, dtype=np.int64)) % stream.n_lbas
        return np.repeat(col[:, None], stream.n_volumes, axis=1)
    return chunk
