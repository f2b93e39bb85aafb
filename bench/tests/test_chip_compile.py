"""Each cell's programs compiled ahead of time for a described TPU v5e.

The set-up's init program and the window's chunk program, at the cell's
real shapes, for one chip of a described ``v5e:2x2`` with no chip
attached; the chunk program must fit one chip's 16 GB of HBM. Nothing runs,
so this says nothing about results or times. The topology is described in
a fixture, never at import, and the persistent compilation cache is off
around the compiles (entries for a described chip cannot be read back).

    JAX_PLATFORMS=cpu python3 -m pytest bench/tests/test_chip_compile.py
"""

from __future__ import annotations

import json

import jax
import pytest
from jax.sharding import SingleDeviceSharding

from harness import ROOT, build_program, compile_programs, load_cell

HBM_BYTES = 16 * 10 ** 9
CELLS = [w["name"] for w in
         json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler here, or it refused
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


@pytest.mark.parametrize("workload", CELLS)
def test_cell_programs_fit_one_chip(one_chip, workload):
    cell = load_cell(workload)
    prog = build_program(cell.config)
    _, chunk = compile_programs(prog, cell.traffic["chunk_steps"], one_chip)
    mem = chunk.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    print(f"{workload}: arguments {mem.argument_size_in_bytes}, temp "
          f"{mem.temp_size_in_bytes}, total {total} bytes")
    assert total < HBM_BYTES
