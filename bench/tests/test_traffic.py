"""The generator: the same seed gives the same chunks, however many the
window consumes, and every seed replays the same streams."""

from __future__ import annotations

import numpy as np
import pytest

from harness import BENCH
from small import N_LBAS, small_mix
from trafficgen import Traffic

MIXES = sorted(p.stem for p in (BENCH / "traffic").glob("*.json"))
VOLUMES = 4
SEED = 2 ** 31 + 12345


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_chunks_whatever_was_consumed(mix):
    a = Traffic(small_mix(mix), N_LBAS, VOLUMES, SEED)
    b = Traffic(small_mix(mix), N_LBAS, VOLUMES, SEED)
    late = a.setup_chunks + 7
    first = [a.chunk(j) for j in range(late + 3)]
    assert np.array_equal(b.chunk(late), first[late])  # b skipped the rest
    assert np.array_equal(b.chunk(0), first[0])
    for c in first:
        assert c.shape == (256, VOLUMES) and c.dtype == np.int32
        assert c.min() >= 0 and c.max() < N_LBAS


def test_zipf_seeds_change_the_order_not_the_work():
    """Every volume draws from Zipf(alpha) of the mix; a seed chooses which
    volume replays which of the fleet's streams, never the streams."""
    a = Traffic(small_mix("zipf_steady"), N_LBAS, VOLUMES, SEED)
    b = Traffic(small_mix("zipf_steady"), N_LBAS, VOLUMES, SEED + 1)
    j = a.setup_chunks
    ca = np.concatenate([a.chunk(j + i) for i in range(64)])
    cb = np.concatenate([b.chunk(j + i) for i in range(64)])
    assert not np.array_equal(ca, cb)
    assert sorted(map(tuple, ca.T)) == sorted(map(tuple, cb.T))
    for v in range(VOLUMES):
        counts = np.sort(np.bincount(ca[:, v], minlength=N_LBAS))[::-1]
        # rank 1 against rank 2 under alpha = 1: twice as often
        assert 1.6 < counts[0] / counts[1] < 2.5


def test_new_phase_kind_is_a_file(tmp_path, monkeypatch):
    """A phase kind the generator has never seen is found by its name."""
    import plugins
    (tmp_path / "phases").mkdir()
    (tmp_path / "phases" / "constant.py").write_text(
        "import numpy as np\n"
        "def make(params, stream, phase):\n"
        "    return lambda q, k: np.full((k, stream.n_volumes), "
        "params['lba'])\n")
    monkeypatch.setattr(plugins, "BENCH", tmp_path)
    plugins.load.cache_clear()
    try:
        t = Traffic({"chunk_steps": 8, "window": {"kind": "constant",
                                                  "lba": 5}}, 64, 3, SEED)
        assert np.array_equal(t.chunk(4), np.full((8, 3), 5, np.int32))
    finally:
        plugins.load.cache_clear()


def test_sequential_phase_fills_in_order():
    t = Traffic(small_mix("zipf_steady"), N_LBAS, 2, SEED)
    fill = np.concatenate([t.chunk(j) for j in range(N_LBAS // 256)])
    assert np.array_equal(fill[:, 0], np.arange(N_LBAS))
    assert np.array_equal(fill[:, 1], np.arange(N_LBAS))
