"""Zipf-distributed updates, the write model of the SepBIT paper's section
3.2 (arXiv 2104.12425): block ``i`` of ``n_lbas`` is written with
probability proportional to ``i ** -alpha``, independently at every step.

``alpha`` is the phase's only parameter (the paper's analysis, Figures
8(a) and 10(a), takes 1). Which LBA holds which rank is not part of the
model: each stream maps ranks to LBAs by a permutation of its own, the same
in every phase, so a block's heat says nothing of where a sequential fill
put it.

The fleet replays ``n_volumes`` streams drawn from fixed keys, and the seed
chooses which volume replays which stream. The GC work a stream causes
depends on its draws, and a fleet GC tick costs the same whichever volume
needs it, so every seed does the same work (fleet runs of different seeds
differ by no more than runs of one seed), while the seed still changes what
each volume, and so each sampled reference, sees.
"""

from __future__ import annotations

import functools

import numpy as np

STREAM_KEY = 0x5EB17     # fixed root of every stream's draws


@functools.cache
def zipf_cdf(n: int, alpha: float) -> np.ndarray:
    w = np.arange(1, n + 1, dtype=np.float64) ** (-alpha)
    cdf = np.cumsum(w / w.sum())
    cdf[-1] = 1.0
    return cdf


@functools.cache
def rank_to_lba(n_lbas: int, s: int) -> np.ndarray:
    return np.random.default_rng([STREAM_KEY, 1, s]).permutation(n_lbas)


def make(params: dict, stream, phase: int):
    cdf = zipf_cdf(stream.n_lbas, float(params["alpha"]))
    order = stream.rng(1).permutation(stream.n_volumes)

    def chunk(q: int, k: int) -> np.ndarray:
        out = np.empty((k, stream.n_volumes), np.int64)
        for v, s in enumerate(order.tolist()):
            u = np.random.default_rng([STREAM_KEY, 2, s, phase, q]).random(k)
            out[:, v] = rank_to_lba(stream.n_lbas, s)[
                np.searchsorted(cdf, u, side="right")]
        return out
    return chunk
