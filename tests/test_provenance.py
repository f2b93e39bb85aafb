"""Batch-axis provenance pass + SA5xx fleet-isolation lints.

Unit tests for the transfer rules (volume-axis tracking through
pjit/cond/switch/scan, batched gather/scatter, reductions, transposes)
and the end-to-end gates: the real fleet engine — vmapped tick, GC loop,
full replay, and the shard_map body — must analyze clean under every
engine variant, while each seeded SA5xx fixture trips with its exact
code set (covered per-fixture in test_static_analysis.py).
"""

import jax
import jax.numpy as jnp
import pytest
from jax import lax

from repro.analysis import lints, tracing
from repro.analysis.provenance import NONE, ProvenanceAnalysis, axis, join, mixed

CFG = tracing.probe_config(n_lbas=64, segment_size=8)
V, N = 4, 16


def _prov(fn, *args, seeds=None):
    closed = jax.make_jaxpr(fn)(*args)
    if seeds is None:
        seeds = [axis(0) if len(v.aval.shape) >= 1 else NONE
                 for v in closed.jaxpr.invars]
    return ProvenanceAnalysis().run(closed, seeds)


def _vec(shape=(V,), dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype)


# -- lattice -------------------------------------------------------------------

def test_join_lattice():
    assert join(NONE, axis(0)) == axis(0)
    assert join(axis(0), NONE) == axis(0)
    assert join(axis(0), axis(0)) == axis(0)
    assert join(axis(0), axis(1)).kind == "mixed"
    assert join(mixed("x"), axis(0)).kind == "mixed"
    assert join(NONE, NONE) == NONE


# -- elementwise / jit ---------------------------------------------------------

def test_elementwise_keeps_axis_through_pjit():
    """jnp.clip lowers to a jit sub-jaxpr; the axis must survive the
    recursion and the implicit broadcasts inside."""
    (p,) = _prov(lambda x: jnp.clip(x * 2 + 1, 0, 100), _vec())
    assert p == axis(0)


def test_scalar_broadcast_stays_none():
    (p,) = _prov(lambda s: jnp.full((V,), s) + 1, _vec(()))
    assert p == NONE


def test_where_with_per_volume_predicate():
    (p,) = _prov(lambda m, a, b: jnp.where(m, a, b),
                 _vec(dtype=jnp.bool_), _vec(), _vec())
    assert p == axis(0)


# -- reductions ----------------------------------------------------------------

def test_reduce_over_volume_axis_mixes():
    (p,) = _prov(lambda x: jnp.sum(x), _vec())
    assert p.kind == "mixed"


def test_reduce_within_volume_keeps_axis():
    (p,) = _prov(lambda x: jnp.sum(x, axis=1), _vec((V, N)))
    assert p == axis(0)


def test_argmax_within_volume_keeps_axis():
    (p,) = _prov(lambda x: jnp.argmax(x, axis=1), _vec((V, N)))
    assert p == axis(0)


def test_cumsum_across_volumes_mixes():
    (p,) = _prov(lambda x: jnp.cumsum(x), _vec())
    assert p.kind == "mixed"


def test_cumsum_within_volume_keeps_axis():
    (p,) = _prov(lambda x: jnp.cumsum(x, axis=1), _vec((V, N)))
    assert p == axis(0)


# -- axis movement -------------------------------------------------------------

def test_transpose_moves_axis():
    (p,) = _prov(lambda x: x.T, _vec((V, V)))
    assert p == axis(1)


def test_reshape_preserving_prefix_keeps_axis():
    (p,) = _prov(lambda x: x.reshape(V, 2, N // 2), _vec((V, N)))
    assert p == axis(0)


def test_reshape_folding_volume_axis_mixes():
    (p,) = _prov(lambda x: x.reshape(V * N), _vec((V, N)))
    assert p.kind == "mixed"


def test_expand_dims_shifts_axis():
    (p,) = _prov(lambda x: x[None], _vec())
    assert p == axis(1)


# -- cond / switch -------------------------------------------------------------

def test_cond_uniform_predicate_keeps_axis():
    def fn(pred, x):
        return lax.cond(pred, lambda v: v + 1, lambda v: v - 1, x)
    (p,) = _prov(fn, _vec((), jnp.bool_), _vec())
    assert p == axis(0)


def test_switch_uniform_index_keeps_axis():
    def fn(i, x):
        return lax.switch(i, [lambda v: v + 1, lambda v: v * 2,
                              lambda v: v - 3], x)
    (p,) = _prov(fn, _vec(()), _vec())
    assert p == axis(0)


def test_grouped_scheme_switch_stack_keeps_axis():
    """The engine's per-volume dispatch shape: vmap over a lax.switch keyed
    by a per-volume scheme id (lowers to all-branches + select_n)."""
    def one(i, x):
        return lax.switch(i, [lambda v: v + 1, lambda v: v * 2], x)

    (p,) = _prov(lambda ids, xs: jax.vmap(one)(ids, xs), _vec(), _vec())
    assert p == axis(0)


# -- scan ----------------------------------------------------------------------

def test_scan_over_time_keeps_axis_in_carry():
    """The fleet replay shape: carry (V,), xs (T, V) — per-volume
    accumulation never crosses volumes."""
    def fn(xs):
        return lax.scan(lambda c, x: (c + x, c), jnp.zeros(V, jnp.int32),
                        xs)
    carry_p, ys_p = _prov(fn, _vec((N, V)), seeds=[axis(1)])
    assert carry_p == axis(0)
    assert ys_p == axis(1)      # stacked under the new leading time dim


def test_scan_over_volume_axis_mixes_carry():
    def fn(xs):
        return lax.scan(lambda c, x: (c + x, None),
                        jnp.zeros((), jnp.int32), xs)[0]
    (p,) = _prov(fn, _vec(), seeds=[axis(0)])
    assert p.kind == "mixed"


# -- gather / scatter ----------------------------------------------------------

def test_vmapped_row_gather_keeps_axis():
    (p,) = _prov(lambda m, i: jax.vmap(lambda row, j: row[j])(m, i),
                 _vec((V, N)), _vec())
    assert p == axis(0)


def test_volume_id_as_gather_coordinate_mixes():
    (p,) = _prov(lambda x, perm: x[perm], _vec(), _vec())
    assert p.kind == "mixed"


def test_vmapped_row_scatter_keeps_axis():
    (p,) = _prov(
        lambda m, i, u: jax.vmap(lambda row, j, w: row.at[j].set(w))(m, i, u),
        _vec((V, N)), _vec(), _vec())
    assert p == axis(0)


def test_uniform_buffer_per_volume_update_rides_window_dim():
    """vmap(init_state)'s `at[:C].set` shape: uniform operand, per-volume
    updates spanning the full mapped dim — stays per-volume."""
    (p,) = _prov(lambda u: jnp.zeros((V, N), jnp.int32).at[:, :4].set(u),
                 _vec((V, 4)))
    assert p == axis(0)


def test_dot_general_contraction_over_volumes_mixes():
    (p,) = _prov(lambda a, b: a @ b, _vec((V, V), jnp.float32),
                 _vec((V,), jnp.float32))
    assert p.kind == "mixed"


# -- SA5xx lints over synthetic traces -----------------------------------------

def _fleet_rec(step):
    fx = type("Fx", (), {"impl": staticmethod(step), "kind": "fleet",
                         "name": "synthetic"})
    return tracing.fleet_fixture_trace(CFG, fx, n_volumes=V)


def test_sa501_on_cross_volume_reduction_into_state():
    rec = _fleet_rec(lambda cfg, st: dict(st, t=st["t"] + jnp.max(st["t"])))
    codes = {f.code for f in lints.lint_volume_isolation(rec)}
    assert codes == {"SA501"}


def test_sa504_on_transposed_square_leaf():
    rec = _fleet_rec(lambda cfg, st: dict(
        st, seg_nvalid=jnp.swapaxes(st["seg_nvalid"], 0, 1)))
    codes = {f.code for f in lints.lint_volume_isolation(rec)}
    assert "SA504" in codes


def test_sa503_on_donated_pjit_read_after():
    """A buffer donated into a jit call and then read afterwards is a
    use-after-free under XLA donation."""
    donating = jax.jit(lambda x: x + 1, donate_argnums=0)

    def fn(x):
        y = donating(x)
        return y + x          # reads x after its buffer was donated

    rec = tracing.trace("synthetic.donate", fn, (_vec(),))
    # the traced jit eqn must actually carry the donation marker,
    # otherwise this test is vacuous
    assert any(e.primitive.name == "jit" and any(
        e.params.get("donated_invars", ()))
        for e in rec.jaxpr.eqns)
    codes = {f.code for f in lints.lint_donation(rec)}
    assert codes == {"SA503"}


def test_clean_step_has_no_findings():
    rec = _fleet_rec(lambda cfg, st: dict(st, t=st["t"] + 1))
    assert lints.lint_volume_isolation(rec) == []
    assert lints.lint_donation(rec) == []
    assert lints.lint_collectives(rec) == []


# -- the real engine analyzes clean, under every variant -----------------------

@pytest.mark.parametrize("kw", [
    {},
    {"timing": True},
    {"timing": True, "gc_sched": "idle_window"},
    {"gc_engine": "legacy"},
    {"scheme_group": ("sepbit", "dac")},
], ids=["default", "timing", "idle_window", "legacy", "grouped"])
def test_fleet_engine_analyzes_clean(kw):
    cfg = tracing.probe_config(n_lbas=64, segment_size=8, **kw)
    findings = lints.analyze_fleet(cfg)
    assert findings == [], [str(f) for f in findings]


def test_shard_body_is_collective_free():
    rec = tracing.fleet_shard_trace(CFG)
    assert lints.lint_collectives(rec) == []


def test_registry_report_has_fleet_section():
    from repro import analysis
    report = analysis.analyze_registry(
        tracing.probe_config(n_lbas=64, segment_size=8),
        schemes=["sepbit"], kernels=False, engine=False)
    assert report["fleet"]["findings"] == []
    assert report["n_findings"] == 0
