"""Pallas TPU kernel: GC segment selection (Greedy / Cost-Benefit argmax).

At fleet scale (the paper's deployment context: cloud block storage with
thousands of volumes × up to millions of segments) victim selection is a
large masked argmax over segment metadata every GC tick. The kernel streams
segment records HBM→VMEM in (8, 128)-aligned tiles, scores each tile on the
VPU, and folds it *elementwise* into a running (max, argmax) carried as an
(8, 128) tile in the output block (its index map is constant over the tile
axis, so the buffer persists between grid steps). Each lane keeps the best
score it has seen and the lowest flat index that reached it; one small jnp
reduction over the carried tile (:func:`finish_argmax`) then yields the
global argmax. Nothing is stored to memory as a scalar, which Mosaic
refuses in VMEM.

Scores follow core/gc.py exactly:
  greedy:        (n - n_valid) / max(n, 1)
  cost_benefit:  (1-u) * age / (1+u),  u = n_valid/max(n,1), age = t - stime
Ineligible segments (not sealed, or zero garbage) score -inf; ties resolve to
the lowest index (matching jnp.argmax).

The clock ``t`` and the selector id are *runtime* scalars read from SMEM:
heterogeneous fleets run a different selector id per volume, so the choice
cannot be baked into the compiled kernel. Both scores are evaluated on the
VPU and the id picks one — each branch's values are unchanged from the
static formulation.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

LANE = 128
TILE_ROWS = 8  # (8, 128) int32/fp32 tile

GREEDY, COST_BENEFIT = 0, 1   # selector ids (must match jaxsim.SELECTOR_IDS)
_NO_INDEX = jnp.iinfo(jnp.int32).max

_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)   # whole (small) scalar array
# the single-volume kernel's scalars are (1, 1): vmapping it prepends a
# squeezed volume axis, and (1, 1) trailing blocks still meet TPU tiling
_SMEM_1X1 = pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM)


def _score_tile(n, nv, stime, state, t, selector_id):
    nf = n.astype(jnp.float32)
    nvf = nv.astype(jnp.float32)
    garbage = nf - nvf
    greedy = garbage / jnp.maximum(nf, 1.0)
    u = nvf / jnp.maximum(nf, 1.0)
    age = jnp.maximum(t - stime, 0).astype(jnp.float32)
    cost_benefit = (1.0 - u) * age / (1.0 + u)
    score = jnp.where(selector_id == GREEDY, greedy, cost_benefit)
    eligible = (state == 2) & (garbage > 0)
    return jnp.where(eligible, score, -jnp.inf)


def _fold_tile_argmax(tile, score, best_ref, idx_ref):
    """Fold scored tile number ``tile`` of a (rows, LANE) tiling into the
    lane-wise running (max, argmax) held in ``best_ref`` / ``idx_ref``.
    Tiles arrive in increasing index order and a lane takes a new value only
    on a strictly greater score, so each lane keeps the lowest flat index
    of its best score. The argmax carry is exact int32 — a float32 carry
    would round flat indices above 2^24 to a neighboring segment. Shared by
    the single-volume and batched kernels so the tie-break contract can't
    drift between them."""
    @pl.when(tile == 0)
    def _init():
        best_ref[...] = jnp.full(best_ref.shape, -jnp.inf, jnp.float32)
        idx_ref[...] = jnp.full(idx_ref.shape, -1, jnp.int32)

    rows, lanes = score.shape
    r = jax.lax.broadcasted_iota(jnp.int32, score.shape, 0)
    c = jax.lax.broadcasted_iota(jnp.int32, score.shape, 1)
    flat = tile * (rows * lanes) + r * lanes + c
    best = best_ref[...]
    take = score > best
    best_ref[...] = jnp.where(take, score, best)
    idx_ref[...] = jnp.where(take, flat, idx_ref[...])


def finish_argmax(best, best_idx):
    """Reduce the kernels' lane-wise carry (trailing (rows, LANE) axes) to
    ``(idx, score)``: the best score, and the lowest flat index among the
    lanes that hold it — jnp.argmax's first-occurrence tie-break. ``idx`` is
    -1 where no segment is eligible (every score -inf)."""
    top = jnp.max(best, axis=(-2, -1))
    hit = best == top[..., None, None]
    idx = jnp.min(jnp.where(hit, best_idx, _NO_INDEX), axis=(-2, -1))
    return jnp.where(jnp.isfinite(top), idx, -1), top


def _segsel_kernel(t_ref, sel_ref, n_ref, nv_ref, stime_ref, state_ref,
                   best_ref, idx_ref):
    score = _score_tile(n_ref[...], nv_ref[...], stime_ref[...], state_ref[...],
                        t_ref[0, 0], sel_ref[0, 0])
    _fold_tile_argmax(pl.program_id(0), score, best_ref, idx_ref)


def _padded_tiles(x, lead=()):
    """(..., S) int32 -> (..., Sp // LANE, LANE), Sp a whole number of
    (TILE_ROWS, LANE) tiles; padding is zero, which scores -inf (state 0)."""
    tile = TILE_ROWS * LANE
    S = x.shape[-1]
    Sp = -(-S // tile) * tile
    x = jnp.pad(x.astype(jnp.int32), [(0, 0)] * len(lead) + [(0, Sp - S)])
    return x.reshape(*lead, Sp // LANE, LANE)


@functools.partial(jax.jit, static_argnames=("selector", "interpret"))
def segment_select(seg_n: jax.Array, seg_nvalid: jax.Array, seg_stime: jax.Array,
                   seg_state: jax.Array, t: jax.Array, *,
                   selector: str = "cost_benefit",
                   selector_id: jax.Array | None = None,
                   interpret: bool | None = None):
    """Victim segment argmax. 1-D int32 inputs of equal length (padded to a
    multiple of 1024 internally; padding scores -inf). Returns (idx, score);
    idx == -1 when no segment is eligible.

    ``selector_id`` (traced int32 scalar, 0 = greedy / 1 = cost-benefit)
    overrides the static ``selector`` string — per-volume selection for
    heterogeneous fleets, where this kernel is vmapped over volumes.
    ``interpret`` follows the platform (see `repro.kernels.interpret_mode`)."""
    if selector_id is None:
        selector_id = jnp.int32({"greedy": GREEDY, "cost_benefit": COST_BENEFIT}
                                [selector])
    n2, nv2, st2, state2 = map(_padded_tiles,
                               (seg_n, seg_nvalid, seg_stime, seg_state))
    spec = pl.BlockSpec((TILE_ROWS, LANE), lambda i: (i, 0))
    carry = pl.BlockSpec((TILE_ROWS, LANE), lambda i: (0, 0))
    best, best_idx = pl.pallas_call(
        _segsel_kernel,
        grid=(n2.shape[0] // TILE_ROWS,),
        in_specs=[_SMEM_1X1, _SMEM_1X1, spec, spec, spec, spec],
        out_specs=[carry, carry],
        out_shape=[jax.ShapeDtypeStruct((TILE_ROWS, LANE), jnp.float32),
                   jax.ShapeDtypeStruct((TILE_ROWS, LANE), jnp.int32)],
        interpret=interpret_mode(interpret),
    )(jnp.asarray(t, jnp.int32).reshape(1, 1),
      jnp.asarray(selector_id, jnp.int32).reshape(1, 1), n2, nv2, st2, state2)
    return finish_argmax(best, best_idx)


def _segsel_batch_kernel(t_ref, sel_ref, n_ref, nv_ref, stime_ref, state_ref,
                         best_ref, idx_ref):
    v = pl.program_id(0)          # volume; its carry resets at its tile 0
    score = _score_tile(n_ref[...], nv_ref[...], stime_ref[...], state_ref[...],
                        t_ref[v], sel_ref[v])
    _fold_tile_argmax(pl.program_id(1), score, best_ref, idx_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def segment_select_batch(seg_n: jax.Array, seg_nvalid: jax.Array,
                         seg_stime: jax.Array, seg_state: jax.Array,
                         t: jax.Array, *, selector_ids: jax.Array,
                         interpret: bool | None = None):
    """Victim argmax for a whole fleet in one kernel launch: (V, S) int32
    segment metadata, per-volume clocks ``t`` and ``selector_ids`` (both
    (V,), read from SMEM). The fleet GC tick's entry point — one pallas_call
    with a (volumes × tiles) grid instead of V separate (vmapped) launches;
    each volume's lane-wise carry is its own (TILE_ROWS, LANE) output block.
    Returns ((V,) idx, (V,) score); idx == -1 where no segment is eligible.
    Scores/tie-breaks are identical to :func:`segment_select` and the jnp
    oracle."""
    V = seg_n.shape[0]
    n2, nv2, st2, state2 = (_padded_tiles(x, (V,)) for x in
                            (seg_n, seg_nvalid, seg_stime, seg_state))
    spec = pl.BlockSpec((None, TILE_ROWS, LANE), lambda v, i: (v, i, 0))
    carry = pl.BlockSpec((None, TILE_ROWS, LANE), lambda v, i: (v, 0, 0))
    best, best_idx = pl.pallas_call(
        _segsel_batch_kernel,
        grid=(V, n2.shape[1] // TILE_ROWS),
        in_specs=[_SMEM, _SMEM, spec, spec, spec, spec],
        out_specs=[carry, carry],
        out_shape=[jax.ShapeDtypeStruct((V, TILE_ROWS, LANE), jnp.float32),
                   jax.ShapeDtypeStruct((V, TILE_ROWS, LANE), jnp.int32)],
        interpret=interpret_mode(interpret),
    )(jnp.asarray(t, jnp.int32).reshape(V),
      jnp.asarray(selector_ids, jnp.int32).reshape(V), n2, nv2, st2, state2)
    return finish_argmax(best, best_idx)


def analysis_entries(n_segments: int = 1024, n_volumes: int = 4):
    """Traceable entry points for the static analyzer (`repro.analysis`).
    The int32 argmax carry inside ``_fold_tile_argmax`` is exactly what its
    float-index-carry lint (SA201) guards."""
    seg = jax.ShapeDtypeStruct((n_segments,), jnp.int32)
    fleet = jax.ShapeDtypeStruct((n_volumes, n_segments), jnp.int32)
    per_vol = jax.ShapeDtypeStruct((n_volumes,), jnp.int32)
    scalar = jax.ShapeDtypeStruct((), jnp.int32)
    return {
        "kernels.segment_select": (
            lambda n, nv, st, state, t, sel: segment_select(
                n, nv, st, state, t, selector_id=sel),
            (seg, seg, seg, seg, scalar, scalar)),
        "kernels.segment_select_batch": (
            lambda n, nv, st, state, t, sels: segment_select_batch(
                n, nv, st, state, t, selector_ids=sels),
            (fleet, fleet, fleet, fleet, per_vol, per_vol)),
    }
