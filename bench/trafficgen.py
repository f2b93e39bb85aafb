"""The one traffic generator: turns a mix's data file into chunks of writes.

A traffic mix (``bench/traffic/<name>.json``) is parameters only::

    {"chunk_steps": 512,
     "setup": [{"kind": "sequential", "writes": "n_lbas"},
               {"kind": "zipf", "alpha": 1.0, "writes": 65536}],
     "window": {"kind": "zipf", "alpha": 1.0}}

Every volume of the fleet writes one LBA per step. The stream is cut into
chunks of ``chunk_steps`` steps, each a ``(chunk_steps, n_volumes)`` int32
array. The set-up phases come first, in order, each a whole number of
chunks (``"n_lbas"`` stands for the volume size, times ``passes`` if
given); the window phase follows and never ends.

A phase's ``kind`` names its generator, ``bench/phases/<kind>.py``, whose
``make(params, stream, phase)`` returns a function of ``(q, k)``: the
phase's ``q``-th chunk of ``k`` steps. It may draw only from the seed, the
volume, the phase and ``q``, so the same seed gives the same chunks however
many of them a run consumes. A new family of traffic is a new file there.
"""

from __future__ import annotations

import dataclasses

import numpy as np

import plugins


@dataclasses.dataclass(frozen=True)
class Stream:
    """What every phase of one mix shares: the fleet and the seed."""
    n_lbas: int
    n_volumes: int
    seed: int               # the run's seed, reduced to 64 bits

    def rng(self, *key: int) -> np.random.Generator:
        return np.random.default_rng([self.seed, *key])


class Traffic:
    """Chunks of one mix for one fleet and seed."""

    def __init__(self, mix: dict, n_lbas: int, n_volumes: int, seed: int):
        self.stream = Stream(n_lbas, n_volumes, seed % (1 << 64))
        self.k = int(mix["chunk_steps"])
        self.phases = []        # (chunk function, first chunk, chunks or None)
        first = 0
        for p, ph in enumerate(mix.get("setup", [])):
            writes = ph["writes"]
            writes = n_lbas * int(ph.get("passes", 1)) if writes == "n_lbas" \
                else int(writes)
            if writes % self.k:
                raise ValueError(f"set-up phase of {writes} writes is not a "
                                 f"whole number of {self.k}-step chunks")
            self.phases.append((self._make(ph, p), first, writes // self.k))
            first += writes // self.k
        self.setup_chunks = first
        self.phases.append((self._make(mix["window"], len(self.phases)),
                            first, None))

    def _make(self, params: dict, phase: int):
        gen = plugins.load("phases", params["kind"])
        return gen.make(params, self.stream, phase)

    def chunk(self, j: int) -> np.ndarray:
        """The ``j``-th chunk of the stream, ``(chunk_steps, n_volumes)``."""
        for fn, first, count in self.phases:
            if count is None or j < first + count:
                out = fn(j - first, self.k)
                assert out.shape == (self.k, self.stream.n_volumes)
                return out.astype(np.int32, copy=False)
        raise AssertionError("unreachable: the window phase never ends")
