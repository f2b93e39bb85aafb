"""Ahead-of-time compiles for a described TPU v5e, with no chip attached.

The TPU compiler is installed beside JAX, so the Mosaic kernels and the
fleet program can be compiled here for a described `v5e:2x2` topology at
deployment sizes. This catches what interpret mode cannot: blocks that
break the (8, 128) tiling rule, scalar stores to VMEM, and programs that do
not fit a chip's 16 GB of HBM. Nothing runs, so nothing here says anything
about results or times.

The topology is described inside a module-scoped fixture (never at import),
so every xdist worker collects the same tests and only the worker given this
file loads the TPU library. The persistent compilation cache is turned off
around the compiles: entries written for a described chip cannot be read
back without one.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import jaxsim
from repro.core.analysis import BLOCKS_PER_GIB, PAPER_N
from repro.core.fleetshard import hetero_config, policy_grid
from repro.kernels import classify, segsel

HBM_BYTES = 16 * 10 ** 9        # one v5e chip
FLEET_VOLUMES = 16              # chip_smoke.py phase (b)
FLEET_UPDATES = 2 ** 16


@pytest.fixture(scope="module")
def topo():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp
        from jax.experimental import topologies
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler installed, or it refused
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield topo


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_segment_select_compiles_for_10gib_volume(one_chip):
    S = jaxsim.JaxSimConfig(n_lbas=PAPER_N).s_max
    assert S == 48_222
    seg, scalar = _spec(one_chip, (S,)), _spec(one_chip, ())
    compiled = jax.jit(
        lambda n, nv, st, state, t, sel: segsel.segment_select(
            n, nv, st, state, t, selector_id=sel, interpret=False)
    ).lower(seg, seg, seg, seg, scalar, scalar).compile()
    _assert_kernel(compiled)


def test_segment_select_batch_compiles_for_fleet(one_chip):
    S = jaxsim.JaxSimConfig(n_lbas=BLOCKS_PER_GIB).s_max
    assert S == 4_852
    seg = _spec(one_chip, (FLEET_VOLUMES, S))
    per_vol = _spec(one_chip, (FLEET_VOLUMES,))
    compiled = jax.jit(
        lambda n, nv, st, state, t, sels: segsel.segment_select_batch(
            n, nv, st, state, t, selector_ids=sels, interpret=False)
    ).lower(seg, seg, seg, seg, per_vol, per_vol).compile()
    _assert_kernel(compiled)


def test_classify_compiles_for_victim_batch(one_chip):
    vec = _spec(one_chip, (128,))       # one segment_size=128 victim
    compiled = jax.jit(
        lambda v, g, c1, gc, ell, sid: classify.classify(
            v, g, c1, gc, ell, scheme_id=sid, interpret=False)
    ).lower(vec, vec, vec, vec, _spec(one_chip, (), jnp.float32),
            _spec(one_chip, ())).compile()
    _assert_kernel(compiled)


def test_fleet_program_fits_one_chip(one_chip):
    """The jnp fleet program for chip_smoke.py's 16 × 1 GiB fleet (its four
    schemes × two selectors, ungrouped: the full dispatch stack) compiles
    and fits one chip's HBM."""
    policy, _ = policy_grid(["sepbit", "nosep", "fk", "warcip"],
                            ["greedy", "cost_benefit"], [0.15],
                            volumes_per_cell=2)
    cfg = hetero_config(jaxsim.JaxSimConfig(n_lbas=BLOCKS_PER_GIB,
                                            segment_size=128), policy)
    steps = BLOCKS_PER_GIB + FLEET_UPDATES      # sequential fill + updates
    trace = _spec(one_chip, (FLEET_VOLUMES, steps))
    pols = {k: _spec(one_chip, v.shape, v.dtype)
            for k, v in policy.as_state_arrays().items()}
    compiled = jaxsim._run_fleet.lower(cfg, trace, trace, False,
                                       pols).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    state_bytes = sum(leaf.size * leaf.dtype.itemsize for leaf in
                      jax.tree_util.tree_leaves(jaxsim.state_spec(cfg)))
    assert mem.output_size_in_bytes >= FLEET_VOLUMES * state_bytes
    assert total < HBM_BYTES


def test_fleet_scopes_leave_the_v5e_program_unchanged(one_chip, monkeypatch):
    """`jaxsim.SCOPES` reach the v5e program only as op metadata: without
    them it compiles to the same instructions once that is stripped."""
    import contextlib
    import functools
    import re
    cfg = jaxsim.JaxSimConfig(n_lbas=2 ** 12, segment_size=2 ** 10)
    trace = _spec(one_chip, (2, 256))
    pols = {k: _spec(one_chip, v.shape, v.dtype) for k, v in
            jaxsim.broadcast_policies(cfg, 2).items()}

    def stripped():
        body = jax.jit(functools.partial(jaxsim.fleet_body, cfg, False))
        text = body.lower(trace, trace, pols).compile().as_text()
        assert ("gc_tick/while/body/rewrite" in text) == (
            jax.named_scope is not null_scope)
        text = re.sub(r"^(?:FileNames|FunctionNames|FileLocations|"
                      r"StackFrames)\n(?:.+\n)*?\n", "", text, flags=re.M)
        return re.sub(r", metadata=\{[^}]*\}", "", text)

    def null_scope(name):
        return contextlib.nullcontext()

    scoped = stripped()
    monkeypatch.setattr(jax, "named_scope", null_scope)
    assert stripped() == scoped


def test_gc_rewrite_gathers_from_no_class_table(one_chip):
    """The v5e fleet program's GC rewrite reads each victim slot's class
    table entries by select: no gather under `gc_tick/.../rewrite` reads an
    operand whose last dimension is the class axis."""
    import functools
    import re
    cfg = jaxsim.JaxSimConfig(n_lbas=2 ** 12, segment_size=2 ** 10)
    trace = _spec(one_chip, (2, 256))
    pols = {k: _spec(one_chip, v.shape, v.dtype) for k, v in
            jaxsim.broadcast_policies(cfg, 2).items()}
    body = jax.jit(functools.partial(jaxsim.fleet_body, cfg, False))
    text = body.lower(trace, trace, pols).compile().as_text()
    shapes = dict(re.findall(r"^\s*(?:ROOT )?(%[\w.-]+) = \w+\[([\d,]*)\]",
                             text, flags=re.M))
    rewrite = []
    for line in text.splitlines():
        op = re.search(r"= \S+ gather\((%[\w.-]+)", line)
        name = re.search(r'op_name="([^"]*)"', line)
        if op and name and re.search(r"gc_tick/.*/rewrite/", name.group(1)):
            rewrite.append(shapes[op.group(1)].split(","))
    assert rewrite                       # the victim's rows are still read
    C = cfg.n_class_slots
    assert not [d for d in rewrite if int(d[-1]) == C]
