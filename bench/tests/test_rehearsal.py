"""Each cell's harness end to end on the CPU at a small size, and the
faults the check must catch.

The harness's look for a chip is skipped (``run_cell`` is called directly);
``check_device`` itself must refuse the CPU, and so must ``run.py``.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from harness import BENCH, NoChip, check_device, run_cell
from small import small_cell

CELLS = [w["name"] for w in
         json.loads((BENCH.parent / "BENCHMARK.json").read_text())
         ["workloads"]]
SEED = 2 ** 31 + 99


def run(cell, trace=False, chunk_fn=None, seed=SEED):
    return run_cell(cell, seed, 1.0, trace, time.perf_counter(),
                    chunk_fn=chunk_fn)


def test_metric_path_refuses_a_cpu():
    with pytest.raises(NoChip):
        check_device(1)


def test_run_py_exits_without_a_result_on_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode == 2
    assert "{" not in p.stdout
    assert "no TPU" in p.stderr


@pytest.mark.parametrize("workload", CELLS)
def test_cell_harness_at_small_size(workload):
    out = run(small_cell(workload))
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"writes_per_s", "peak_hbm_mb", "setup_s"}
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("workload", CELLS)
def test_traced_run_at_small_size(workload):
    out = run(small_cell(workload), trace=True)
    assert out["correct"], out["checks"]
    # the CPU has no device trace: only the readers that need none report
    assert set(out["metrics"]) <= {"gc_blocks_per_kwrite"}
    assert out["device"]["window_s"] > 0


def unchanged(chunk, st, lbas, nxs):
    return st, jnp.sum(st["user_writes"]) + 0


def half_the_fleet(chunk, st, lbas, nxs):
    old = jax.tree_util.tree_map(jnp.copy, st)
    new, token = chunk(st, lbas, nxs)
    half = lbas.shape[1] // 2
    return jax.tree_util.tree_map(
        lambda n, o: n.at[half:].set(o[half:]), new, old), token


def altered_write(chunk, st, lbas, nxs):
    n_lbas = st["loc_seg"].shape[1]
    return chunk(st, lbas.at[3, 0].set((lbas[3, 0] + 1) % n_lbas), nxs)


@pytest.mark.parametrize("fault", [unchanged, half_the_fleet, altered_write])
@pytest.mark.parametrize("workload", CELLS)
def test_check_catches_a_broken_step(workload, fault):
    out = run(small_cell(workload), chunk_fn=fault)
    assert not out["correct"], out["checks"]


def test_per_volume_policies_come_from_the_configuration():
    """A configuration may give each volume its own policy; the program
    and the reference both take volume ``v``'s own values."""
    from harness import build_program, volume_config
    cfg = dict(small_cell(CELLS[0]).config, volumes=2,
               selector=["cost_benefit", "greedy"], gp_threshold=[0.15, 0.25])
    prog = build_program(cfg)
    assert prog.cfg.scheme_group == ("sepbit",)
    assert prog.policies["p_gp"].tolist() == pytest.approx([0.15, 0.25])
    assert prog.policies["p_selector"][0] != prog.policies["p_selector"][1]
    assert volume_config(cfg, 1)["selector"] == "greedy"
    assert volume_config(cfg, 1)["n_lbas"] == cfg["n_lbas"]
