"""Cells cut to a size the CPU runs in seconds, steered from the tests."""

from __future__ import annotations

import dataclasses
import json
import math

from harness import BENCH, Cell, load_cell

N_LBAS = 4096
CHUNK = 256
SEGMENT = 64


def small_mix(name: str) -> dict:
    """A traffic mix with its set-up cut to a ``N_LBAS`` volume."""
    mix = json.loads((BENCH / "traffic" / f"{name}.json").read_text())
    mix["chunk_steps"] = CHUNK
    for ph in mix.get("setup", []):
        if ph["writes"] != "n_lbas":
            ph["writes"] = 4 * CHUNK
    return mix


def small_cell(workload: str) -> Cell:
    """``workload`` at ``N_LBAS`` blocks per volume in ``SEGMENT``-block
    segments and at most 2 volumes, the pool sized by the configuration's
    own rule."""
    cell = load_cell(workload)
    cfg = dict(cell.config)
    cfg["volumes"] = min(cfg["volumes"], 2)
    cfg["n_lbas"] = N_LBAS
    cfg["segment_size"] = SEGMENT
    cfg["n_segments"] = 2 * math.ceil(
        N_LBAS / (1 - cfg["gp_threshold"]) / cfg["segment_size"]) + 4 * 6 + 8
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    mix = next(w["traffic"] for w in bench["workloads"]
               if w["name"] == workload)
    return dataclasses.replace(cell, config=cfg, traffic=small_mix(mix))
