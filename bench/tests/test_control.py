"""The control (the reference with acknowledged writes lost, in the
system's place) must come out not correct in every cell, at a small size."""

from __future__ import annotations

import json

import pytest

from check import load_limits, verdict
from control import control_numbers
from harness import BENCH
from small import small_cell

CELLS = [w["name"] for w in
         json.loads((BENCH.parent / "BENCHMARK.json").read_text())
         ["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("seed", [3, 2 ** 31 + 5])
def test_control_is_not_correct(workload, seed):
    numbers = control_numbers(small_cell(workload), seed, window_chunks=8)
    correct, checks = verdict(numbers, load_limits())
    assert not correct, checks
    assert checks["readback_bad"]["value"] > 0
    assert checks["counter_gap"]["value"] > checks["counter_gap"]["limit"]
