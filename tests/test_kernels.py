"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = np.random.default_rng(7)


@pytest.mark.parametrize("S", [17, 1024, 1500, 4096])
@pytest.mark.parametrize("selector", ["greedy", "cost_benefit"])
def test_segsel_sweep(S, selector):
    n = RNG.integers(0, 129, S)
    nv = np.minimum(RNG.integers(0, 129, S), n)
    st = RNG.integers(0, 10_000, S)
    state = RNG.integers(0, 3, S)
    t = jnp.int32(20_000)
    args = tuple(map(jnp.asarray, (n, nv, st, state)))
    i1, s1 = ops.segment_select(*args, t, selector=selector)
    i2, s2 = ref.segment_select_ref(*args, t, selector=selector)
    if int(i2) == -1:
        assert int(i1) == -1
    else:
        assert int(i1) == int(i2)
        np.testing.assert_allclose(float(s1), float(s2), rtol=1e-5)


def test_segsel_no_eligible():
    z = jnp.zeros(64, jnp.int32)
    i, s = ops.segment_select(z, z, z, z, jnp.int32(5))
    assert int(i) == -1


@pytest.mark.parametrize("S", [257, 2048])
def test_segsel_traced_selector_id(S):
    """Per-volume selection: the traced selector_id scalar must reproduce
    both static-selector kernels (heterogeneous fleets vmap over it)."""
    n = RNG.integers(0, 129, S)
    nv = np.minimum(RNG.integers(0, 129, S), n)
    st = RNG.integers(0, 10_000, S)
    state = RNG.integers(0, 3, S)
    t = jnp.int32(20_000)
    args = tuple(map(jnp.asarray, (n, nv, st, state)))
    for sid, name in ((0, "greedy"), (1, "cost_benefit")):
        i1, s1 = ops.segment_select(*args, t, selector_id=jnp.int32(sid))
        i2, s2 = ref.segment_select_ref(*args, t, selector=name)
        assert int(i1) == int(i2)
        if int(i2) != -1:
            np.testing.assert_allclose(float(s1), float(s2), rtol=1e-5)


def test_segsel_vmapped_per_volume_selectors():
    """A batched fleet with mixed selector ids equals the per-volume refs."""
    V, S = 4, 640
    n = RNG.integers(0, 129, (V, S))
    nv = np.minimum(RNG.integers(0, 129, (V, S)), n)
    st = RNG.integers(0, 10_000, (V, S))
    state = RNG.integers(0, 3, (V, S))
    sids = jnp.asarray([0, 1, 0, 1], jnp.int32)
    t = jnp.full((V,), 20_000, jnp.int32)
    batched = jax.vmap(lambda *a: ops.segment_select(
        *a[:-2], a[-2], selector_id=a[-1]))
    i1, s1 = batched(*map(jnp.asarray, (n, nv, st, state)), t, sids)
    for v in range(V):
        i2, _ = ref.segment_select_ref(
            *map(jnp.asarray, (n[v], nv[v], st[v], state[v])), t[v],
            selector="greedy" if int(sids[v]) == 0 else "cost_benefit")
        assert int(i1[v]) == int(i2)


def test_segsel_batch_matches_ref():
    """The fleet-tick batched entry (one pallas_call, volumes × tiles grid)
    must match the per-volume reference for mixed selectors and per-volume
    clocks — including all-ineligible volumes (idx -1)."""
    V, S = 5, 640
    n = RNG.integers(0, 129, (V, S))
    nv = np.minimum(RNG.integers(0, 129, (V, S)), n)
    st = RNG.integers(0, 10_000, (V, S))
    state = RNG.integers(0, 3, (V, S))
    n[4], nv[4], state[4] = 0, 0, 0        # no eligible segment
    sids = jnp.asarray([0, 1, 0, 1, 1], jnp.int32)
    t = jnp.asarray([20_000, 15_000, 9_000, 20_000, 100], jnp.int32)
    i1, s1 = ops.segment_select_batch(
        *map(jnp.asarray, (n, nv, st, state)), t, selector_ids=sids)
    assert i1.shape == (V,)
    for v in range(V):
        i2, s2 = ref.segment_select_ref(
            *map(jnp.asarray, (n[v], nv[v], st[v], state[v])), t[v],
            selector="greedy" if int(sids[v]) == 0 else "cost_benefit")
        assert int(i1[v]) == int(i2)
        if int(i2) != -1:
            np.testing.assert_allclose(float(s1[v]), float(s2), rtol=1e-5)
    assert int(i1[4]) == -1


def test_segsel_batch_matches_single_kernel():
    """Batched and single-volume kernels agree exactly (the tick engine uses
    the batched form, single-volume replay the scalar form)."""
    V, S = 3, 1500
    n = RNG.integers(0, 129, (V, S))
    nv = np.minimum(RNG.integers(0, 129, (V, S)), n)
    st = RNG.integers(0, 10_000, (V, S))
    state = RNG.integers(0, 3, (V, S))
    t = jnp.full((V,), 20_000, jnp.int32)
    sids = jnp.asarray([1, 1, 0], jnp.int32)
    ib, sb = ops.segment_select_batch(
        *map(jnp.asarray, (n, nv, st, state)), t, selector_ids=sids)
    for v in range(V):
        i1, s1 = ops.segment_select(
            *map(jnp.asarray, (n[v], nv[v], st[v], state[v])), t[v],
            selector_id=sids[v])
        assert int(ib[v]) == int(i1)
        np.testing.assert_array_equal(np.asarray(sb[v]), np.asarray(s1))


@pytest.mark.slow
def test_segsel_int32_index_edge():
    """Indices above 2^24 must carry exactly (PR 1: a float32 argmax carry
    rounded them to even neighbors). A full 2^24-segment interpret-mode scan
    is infeasible (one python step per (8,128) tile), so the tile is
    temporarily raised to (4096,128): the grid still spans >2^24 flat
    indices and the victim sits at an odd index float32 cannot represent."""
    from repro.kernels import segsel
    orig = segsel.TILE_ROWS
    segsel.TILE_ROWS = 4096
    try:
        S = (1 << 24) + (1 << 19)
        hot = (1 << 24) + 1029          # odd => float32 (spacing 2) rounds it
        n = np.zeros(S, np.int32)
        nv = np.zeros(S, np.int32)
        st = np.zeros(S, np.int32)
        state = np.zeros(S, np.int32)
        n[hot], nv[hot], state[hot] = 8, 2, 2
        idx, score = segsel.segment_select(
            *map(jnp.asarray, (n, nv, st, state)), jnp.int32(10),
            selector="greedy")
        assert int(idx) == hot
        assert float(score) > 0
    finally:
        segsel.TILE_ROWS = orig
        jax.clear_caches()


@pytest.mark.parametrize("B", [5, 1024, 2049])
def test_classify_sweep(B):
    v = RNG.integers(0, 10_000, B)
    g = RNG.integers(0, 100_000, B)
    c1 = RNG.integers(0, 2, B)
    gc = RNG.integers(0, 2, B)
    for ell in (float("inf"), 1234.5, 1.0):
        o1 = ops.classify(*map(jnp.asarray, (v, g, c1, gc)), jnp.float32(ell))
        o2 = ref.classify_ref(*map(jnp.asarray, (v, g, c1, gc)), jnp.float32(ell))
        np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))


def _elementwise_ids():
    """Every registry scheme with an elementwise (kernel-backed) classifier."""
    from repro.core.placement import registry
    return [i for i, (_, jp) in enumerate(registry.jax_schemes())
            if jp.elementwise is not None]


@pytest.mark.parametrize("scheme_id", _elementwise_ids())
def test_classify_traced_scheme_id(scheme_id):
    """Per-volume scheme: every elementwise-registered id (0 collapses to
    class 0, 1 to {0 user, 1 GC}, 2 to the SepBIT Algorithm-1 classes, plus
    the uw/gw ablations) — kernel against the jnp oracle."""
    B = 700
    v = RNG.integers(0, 10_000, B)
    g = RNG.integers(0, 100_000, B)
    c1 = RNG.integers(0, 2, B)
    gc = RNG.integers(0, 2, B)
    args = tuple(map(jnp.asarray, (v, g, c1, gc)))
    o1 = ops.classify(*args, jnp.float32(1234.5), scheme_id=jnp.int32(scheme_id))
    o2 = ref.classify_ref(*args, jnp.float32(1234.5), scheme_id=scheme_id)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
    if scheme_id == 0:
        assert int(np.asarray(o1).max()) == 0
    elif scheme_id == 1:
        np.testing.assert_array_equal(np.asarray(o1), gc)


@pytest.mark.parametrize("scheme_id", _elementwise_ids())
def test_classify_pruned_chain_matches_full(scheme_id):
    """A select chain pruned to one scheme (the grouped-dispatch kernel)
    classifies identically to the full chain for that scheme's id, and
    collapses to class 0 for ids outside the group."""
    B = 300
    v = RNG.integers(0, 10_000, B)
    g = RNG.integers(0, 100_000, B)
    c1 = RNG.integers(0, 2, B)
    gc = RNG.integers(0, 2, B)
    args = tuple(map(jnp.asarray, (v, g, c1, gc)))
    full = ops.classify(*args, jnp.float32(777.5),
                        scheme_id=jnp.int32(scheme_id))
    pruned = ops.classify(*args, jnp.float32(777.5),
                          scheme_id=jnp.int32(scheme_id),
                          scheme_ids=(scheme_id,))
    np.testing.assert_array_equal(np.asarray(full), np.asarray(pruned))
    other = next(i for i in _elementwise_ids() if i != scheme_id)
    out = ops.classify(*args, jnp.float32(777.5), scheme_id=jnp.int32(other),
                       scheme_ids=(scheme_id,))
    assert int(np.asarray(out).max()) == 0


def test_classify_vmapped_per_volume_schemes():
    """Batched classify with a different scheme per volume (the fleet path)."""
    V, B = 3, 256
    v = RNG.integers(0, 10_000, (V, B))
    g = RNG.integers(0, 100_000, (V, B))
    c1 = RNG.integers(0, 2, (V, B))
    gc = RNG.integers(0, 2, (V, B))
    sids = jnp.asarray([0, 1, 2], jnp.int32)
    ells = jnp.asarray([np.inf, 50.0, 1234.5], jnp.float32)
    out = jax.vmap(lambda *a: ops.classify(*a[:-1], scheme_id=a[-1]))(
        *map(jnp.asarray, (v, g, c1, gc)), ells, sids)
    for i in range(V):
        want = ref.classify_ref(*map(jnp.asarray, (v[i], g[i], c1[i], gc[i])),
                                ells[i], scheme_id=int(sids[i]))
        np.testing.assert_array_equal(np.asarray(out[i]), np.asarray(want))


@pytest.mark.parametrize("n", [1000, 1 << 14])
@pytest.mark.parametrize("alpha", [0.0, 1.0])
def test_zipfprob_sweep(n, alpha):
    from repro.core.traces import zipf_probs
    p = jnp.asarray(zipf_probs(n, alpha), jnp.float32)
    got = ops.zipf_bit_sums(p, 100.0, 400.0, 2000.0, 800.0)
    want = ref.zipf_bit_sums_ref(p, 100.0, 400.0, 2000.0, 800.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=1e-6)


@pytest.mark.parametrize("B,Hq,Hkv,D,S", [
    (1, 4, 1, 64, 300),      # MQA, ragged tile
    (2, 8, 2, 64, 700),      # GQA
    (2, 8, 8, 128, 512),     # MHA, aligned
    (1, 16, 2, 128, 1024),   # large G
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode_sweep(B, Hq, Hkv, D, S, dtype):
    q = jnp.asarray(RNG.standard_normal((B, Hq, D)), dtype)
    k = jnp.asarray(RNG.standard_normal((B, S, Hkv, D)), dtype)
    v = jnp.asarray(RNG.standard_normal((B, S, Hkv, D)), dtype)
    kl = jnp.asarray(RNG.integers(1, S + 1, B), jnp.int32)
    o1 = ops.flash_decode(q, k, v, kl, kv_tile=256)
    o2 = ref.flash_decode_ref(q, k, v, kl)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32), atol=tol, rtol=tol)


def test_zipfprob_matches_closed_form():
    """Kernel path reproduces the paper's Fig 8 math (small n for speed)."""
    from repro.core.analysis import pr_user_bit
    from repro.core.traces import zipf_probs
    n = 1 << 15
    p = jnp.asarray(zipf_probs(n, 1.0), jnp.float32)
    got = float(ops.pr_user_bit_kernel(p, 500.0, 2000.0))
    want = pr_user_bit(500, 2000, n=n, alpha=1.0)
    assert got == pytest.approx(want, abs=2e-3)


def _pallas_interpret_flags(fn, *args):
    from repro.analysis.walker import iter_eqns
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    return [e.params["interpret"] for e in iter_eqns(jaxpr)
            if e.primitive.name == "pallas_call"]


def test_interpret_mode_follows_the_platform(monkeypatch):
    """``interpret=None`` interprets on the CPU backend (this suite's) and
    compiles on a TPU; any other backend is refused. There is no config
    field that could run the interpreter on a chip."""
    from repro.core.jaxsim import JaxSimConfig
    from repro.kernels import interpret_mode

    assert jax.default_backend() == "cpu"
    z = jnp.zeros(64, jnp.int32)
    assert _pallas_interpret_flags(
        lambda a: ops.segment_select(a, a, a, a, jnp.int32(3)), z) == [True]
    assert _pallas_interpret_flags(
        lambda a: ops.classify(a, a, a, a, jnp.float32(1.0)), z) == [True]
    assert interpret_mode(False) is False
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert interpret_mode(None) is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        interpret_mode(None)
    with pytest.raises(TypeError, match="kernels_interpret"):
        JaxSimConfig(n_lbas=64, kernels_interpret=True)
