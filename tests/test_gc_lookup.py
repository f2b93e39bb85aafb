"""The GC rewrite's per-class lookups.

`_gc_once` gives every victim slot its class's rank, room, open segment,
fresh segment and fill from tables with one entry per class slot. It reads
them with `jaxsim._class_lookup`, a static C-way select; these tests pin
that it returns what indexing returns and that `_gc_once` gathers from no
per-class table.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.walker import iter_eqns
from repro.core import jaxsim
from repro.core.fleetshard import hetero_config, policy_grid

N, SEG, V = 96, 8, 2


def _grouped():
    policy, _ = policy_grid(["gw", "sepgc"], ["greedy"], [0.15])
    cfg = hetero_config(jaxsim.JaxSimConfig(n_lbas=N, segment_size=SEG),
                        policy)
    return dataclasses.replace(cfg, scheme="gw", scheme_group=("gw", "sepgc"))


CONFIGS = {                     # class widths 1, 6 and 4
    "nosep": jaxsim.JaxSimConfig(n_lbas=N, segment_size=SEG, scheme="nosep"),
    "sepbit": jaxsim.JaxSimConfig(n_lbas=N, segment_size=SEG),
    "grouped": _grouped(),
}


def _indexed(tbl, cls):
    """``tbl[..., clip(cls)]`` by plain numpy indexing, ``tbl`` broadcast
    over ``cls``'s slots."""
    C = tbl.shape[-1]
    full = np.broadcast_to(tbl, cls.shape + (C,))
    return np.take_along_axis(full, np.clip(cls, 0, C - 1)[..., None],
                              -1)[..., 0]


@pytest.mark.parametrize("name", CONFIGS)
@pytest.mark.parametrize("per_slot", [False, True], ids=["C", "s,C"])
def test_class_lookup_matches_indexing(name, per_slot):
    C = CONFIGS[name].n_class_slots
    rng = np.random.default_rng(C)
    cls = rng.integers(-1, C, size=(V, SEG)).astype(np.int32)
    cls[:, 0] = -1                                   # a dead slot in each
    shape = (V, SEG, C) if per_slot else (V, C)
    tbl = rng.integers(-2 ** 31, 2 ** 31, size=shape, dtype=np.int64
                       ).astype(np.int32)
    clipped = jnp.clip(cls, 0, C - 1)
    one = jaxsim._class_lookup(jnp.asarray(tbl[0]), clipped[0])
    batched = jax.vmap(jaxsim._class_lookup)(jnp.asarray(tbl), clipped)
    np.testing.assert_array_equal(one, _indexed(tbl[0], cls[0]))
    np.testing.assert_array_equal(batched, np.stack(
        [_indexed(tbl[v], cls[v]) for v in range(V)]))
    assert one.dtype == batched.dtype == jnp.int32


def _gather_operands(fn, *args):
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    return [e.invars[0].aval.shape for e in iter_eqns(jaxpr)
            if e.primitive.name == "gather"]


@pytest.mark.parametrize("name", CONFIGS)
def test_gc_once_gathers_from_no_class_table(name):
    cfg = CONFIGS[name]
    C = cfg.n_class_slots
    per_class = {(C,), (V, C), (SEG, C), (V, SEG, C)}
    assert not {cfg.n_rows, cfg.s_max, N} & {C, V, SEG}
    st = jaxsim.init_state(cfg)
    fleet = jax.tree_util.tree_map(lambda x: jnp.stack([x] * V), st)
    once = functools.partial(jaxsim._gc_once, cfg)
    single = _gather_operands(once, st, jnp.int32(0))
    vmapped = _gather_operands(jax.vmap(once), fleet,
                               jnp.zeros(V, jnp.int32))
    assert vmapped                       # the victim's rows are still read
    assert not [s for s in single + vmapped if s in per_class]
