"""The control: the reference, with one guarantee broken, put in the
system's place and judged by the same comparison.

The configurations state that every acknowledged write is readable at its
latest version. The control breaks that: every ``LOSE_EVERY``-th user write
is acknowledged but never persisted. It replays, for every volume, the
writes a run of the cell feeds (set-up, then ``--window-chunks`` chunks of
the window), and the reference replays the volumes a run samples. A sound
limit passes the system and fails the control. The benchmark's own runs do
not run this; it needs no chip (numpy only).

    python3 bench/control.py --workload <name> --seeds 1,2,3 --window-chunks 160
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent))

import plugins  # noqa: E402
from check import compare, load_limits, verdict  # noqa: E402
from trafficgen import Traffic  # noqa: E402

LOSE_EVERY = 16


def control_numbers(cell, seed: int, window_chunks: int) -> dict:
    """The compared numbers with the control in the system's place."""
    from harness import sample_volumes, volume_config
    cfg = cell.config
    V = int(cfg["volumes"])
    replay = plugins.load("references", cfg["reference"]).replay_volume
    traffic = Traffic(cell.traffic, cfg["n_lbas"], V, seed)
    fed = np.concatenate([traffic.chunk(j) for j in
                          range(traffic.setup_chunks + window_chunks)])
    states = [replay(volume_config(cfg, v), fed[:, v], lose_every=LOSE_EVERY)
              for v in range(V)]
    refs = {v: replay(volume_config(cfg, v), fed[:, v])
            for v in sample_volumes(seed, V)}
    return compare(states, fed, cfg["n_lbas"], refs)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--window-chunks", type=int, required=True,
                    help="window chunks a run of the cell consumes")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))
    from harness import load_cell
    cell = load_cell(args.workload)
    limits = load_limits()
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        numbers = control_numbers(cell, seed, args.window_chunks)
        correct, checks = verdict(numbers, limits)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control_correct": correct, "checks": checks,
                          "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
