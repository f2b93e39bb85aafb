"""Heterogeneous-config fleet sweeps, sharded across devices.

The paper's deployment context is a cloud block store running thousands of
volumes with differing workloads *and differing tuning*; reproducing its WA
claims at that scale means sweeping scheme × selector × GP-threshold over a
fleet in one compiled program. This module supplies the three pieces on top
of `jaxsim.fleet_body`:

1. **Policy encoding** — `FleetPolicy` holds the per-volume traced knobs
   (scheme id, selector id, GP threshold, nc window, GC scheduling policy)
   as (V,) numpy arrays;
   `policy_grid` lays a (scheme × selector × gp) grid over a fleet,
   cell-major, so `tracegen.tiled_fleet` can replay identical workloads
   under every cell for a fair comparison.
2. **Capacity sizing** — `hetero_config` pads the class axis to the widest
   scheme present and sizes the segment pool from the sweep's maximum GP
   threshold (the maximum-capacity cell: steady occupancy ~ live/(1-gp)),
   so a mixed-threshold fleet never exhausts the free pool spuriously.
3. **Device sharding** — `simulate_fleet_hetero` runs the fleet axis under
   `shard_map` over a 1-D "fleet" mesh (volumes are independent: no
   collectives, embarrassingly parallel), with a plain `jax.jit` fallback on
   a single device. The fleet is padded to a multiple of the device count by
   replicating the last volume; pad rows are dropped before summarizing.
4. **Scheme-grouped dispatch** — a vmapped `lax.switch` evaluates every
   registered scheme's branch per step and selects per volume, so a mixed
   fleet pays the whole zoo. `simulate_fleet_hetero(group=True)` (default)
   sorts volumes into per-scheme groups, replays each group under a config
   whose branch stack is pruned to that scheme (`JaxSimConfig.scheme_group`),
   and reassembles results in input order — bit-identical to the ungrouped
   replay because every group shares the full fleet's static shapes.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .jaxsim import (
    GCSCHED_IDS,
    GCSCHED_NAMES,
    JaxSimConfig,
    SCHEME_CLASSES,
    SCHEME_IDS,
    SCHEME_NAMES,
    SELECTOR_IDS,
    SELECTOR_NAMES,
    _run_fleet,
    coerce_fleet,
    coerce_fleet_annotations,
    fleet_annotations,
    fleet_body,
    hist_quantile,
    summarize_fleet,
)


@dataclasses.dataclass(frozen=True)
class FleetPolicy:
    """Per-volume placement policy arrays, all shaped (V,)."""
    scheme_id: np.ndarray      # int32, jaxsim.SCHEME_IDS
    selector_id: np.ndarray    # int32, jaxsim.SELECTOR_IDS
    gp_threshold: np.ndarray   # float32
    nc_window: np.ndarray      # int32
    gcsched_id: np.ndarray | None = None
    #                          # int32, jaxsim.GCSCHED_IDS (None = all greedy)

    def __post_init__(self):
        if self.gcsched_id is None:
            object.__setattr__(self, "gcsched_id",
                               np.zeros_like(self.scheme_id))
        v = len(self.scheme_id)
        for f in dataclasses.fields(self):
            if len(getattr(self, f.name)) != v:
                raise ValueError("policy arrays must share one fleet length")

    @property
    def n_volumes(self) -> int:
        return len(self.scheme_id)

    @property
    def n_classes(self) -> np.ndarray:
        """Per-volume live class count (scheme-derived)."""
        return np.asarray(SCHEME_CLASSES, np.int32)[self.scheme_id]

    @property
    def max_classes(self) -> int:
        return int(self.n_classes.max())

    def as_state_arrays(self, device=None) -> dict:
        """The (V,) traced-policy arrays `jaxsim.fleet_body` vmaps over,
        placed on ``device`` (a device or sharding; None = default)."""
        put = functools.partial(jnp.asarray, device=device)
        return {
            "p_scheme": put(self.scheme_id, jnp.int32),
            "p_selector": put(self.selector_id, jnp.int32),
            "p_gp": put(self.gp_threshold, jnp.float32),
            "p_ncw": put(self.nc_window, jnp.int32),
            "p_classes": put(self.n_classes, jnp.int32),
            "p_gcsched": put(self.gcsched_id, jnp.int32),
        }

    def volume(self, i: int) -> dict:
        """Scalar policy dict for volume ``i`` (simulate_jax's ``policy=``)."""
        return {k: v[i] for k, v in self.as_state_arrays().items()}

    def describe(self, i: int) -> tuple[str, str, float]:
        return (SCHEME_NAMES[int(self.scheme_id[i])],
                SELECTOR_NAMES[int(self.selector_id[i])],
                float(self.gp_threshold[i]))

    def gcsched(self, i: int) -> str:
        return GCSCHED_NAMES[int(self.gcsched_id[i])]


def _coerce(values, v, ids=None, dtype=np.int32):
    """Broadcast a scalar / name / sequence to a (V,) policy array."""
    if isinstance(values, (str, int, float)):
        values = [values] * v
    if ids is not None:
        values = [ids[x] if isinstance(x, str) else x for x in values]
    out = np.asarray(values, dtype)
    if out.shape != (v,):
        raise ValueError(f"expected {v} per-volume values, got {out.shape}")
    return out


def encode_policies(n_volumes: int, *, schemes="sepbit",
                    selectors="cost_benefit", gp_thresholds=0.15,
                    nc_windows=16, gcscheds="greedy") -> FleetPolicy:
    """Build a FleetPolicy from names/scalars (broadcast) or sequences."""
    return FleetPolicy(
        scheme_id=_coerce(schemes, n_volumes, SCHEME_IDS),
        selector_id=_coerce(selectors, n_volumes, SELECTOR_IDS),
        gp_threshold=_coerce(gp_thresholds, n_volumes, dtype=np.float32),
        nc_window=_coerce(nc_windows, n_volumes),
        gcsched_id=_coerce(gcscheds, n_volumes, GCSCHED_IDS),
    )


def policy_grid(schemes, selectors, gp_thresholds, *, volumes_per_cell: int = 1,
                nc_window: int = 16,
                gcsched: str = "greedy") -> tuple[FleetPolicy, list[tuple]]:
    """Cartesian (scheme × selector × gp) grid, ``volumes_per_cell`` volumes
    per cell, laid out cell-major (cell 0's volumes first). Returns the
    policy plus the cell list ``[(scheme, selector, gp), ...]`` in order.
    ``gcsched`` applies fleet-wide (the latbench mode sweeps scheduling ×
    scheme via `encode_policies` directly)."""
    cells = list(itertools.product(schemes, selectors, gp_thresholds))
    v = len(cells) * volumes_per_cell
    sch, sel, gp = zip(*(c for c in cells for _ in range(volumes_per_cell)))
    return encode_policies(v, schemes=list(sch), selectors=list(sel),
                           gp_thresholds=list(gp), nc_windows=nc_window,
                           gcscheds=gcsched), cells


def hetero_config(cfg: JaxSimConfig, policy: FleetPolicy) -> JaxSimConfig:
    """Static config shared by every volume of a heterogeneous fleet.

    The class axis is padded to the widest scheme present. The segment pool
    (s_max) was previously derived from the single ``cfg.gp_threshold``; for
    a mixed-threshold sweep it must be sized from the threshold whose cell
    needs the *most* capacity. GC triggers when the garbage proportion
    exceeds the threshold, so steady-state occupancy grows as
    live/(1 - gp): the sweep's **maximum** threshold tolerates the most
    resident garbage and bounds the pool. Sizing from ``cfg.gp_threshold``
    (or the sweep minimum) would let a high-threshold volume exhaust the
    free pool spuriously (regression-tested in tests/test_fleet.py)."""
    slots = max(policy.max_classes, cfg.class_slots or 0)
    base = dataclasses.replace(cfg, class_slots=slots)
    if cfg.n_segments is None:
        sized = dataclasses.replace(base, gp_threshold=float(
            np.max(policy.gp_threshold)))
        base = dataclasses.replace(base, n_segments=sized.s_max)
    return base


def matching_single_config(cfg: JaxSimConfig, policy: FleetPolicy,
                           i: int) -> JaxSimConfig:
    """The plain single-volume config that volume ``i`` of a heterogeneous
    fleet must be bit-identical to: its own scheme/selector/gp knobs, with
    only the segment-pool size pinned to the fleet's shared value (array
    shapes must agree for replay parity; class padding need not — padded
    slots are exact no-ops)."""
    scheme, selector, gp = policy.describe(i)
    fleet_cfg = hetero_config(cfg, policy)
    return dataclasses.replace(
        cfg, scheme=scheme, selector=selector, gp_threshold=gp,
        nc_window=int(policy.nc_window[i]), n_segments=fleet_cfg.s_max,
        gc_sched=policy.gcsched(i), class_slots=None)


# -- device sharding ----------------------------------------------------------

def fleet_mesh(min_devices: int = 2) -> Mesh | None:
    """1-D mesh over every local device, or None when sharding is pointless
    (single device). CPU hosts expose >1 device only under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``."""
    devices = jax.devices()
    if len(devices) < min_devices:
        return None
    return Mesh(np.asarray(devices), ("fleet",))


def shard_mapped_body(cfg: JaxSimConfig, masked: bool, mesh: Mesh):
    """`shard_map(fleet_body)` over the fleet axis — the exact (un-jitted)
    sharded program, shared by :func:`_sharded_runner` and by
    `repro.analysis` (the SA502 lint traces this body and proves it free of
    collectives over the ``"fleet"`` mesh axis). Volumes are fully
    independent, so every input/output leaf shards its leading axis and the
    body runs collective-free on each device's slice of the fleet."""
    body = functools.partial(fleet_body, cfg, masked)
    return jax.shard_map(body, mesh=mesh,
                         in_specs=(P("fleet"), P("fleet"), P("fleet")),
                         out_specs=P("fleet"), check_vma=False)


@functools.lru_cache(maxsize=None)
def _sharded_runner(cfg: JaxSimConfig, masked: bool, mesh: Mesh):
    """jit-compiled :func:`shard_mapped_body`."""
    return jax.jit(shard_mapped_body(cfg, masked, mesh))


def scheme_groups(policy: FleetPolicy) -> list[tuple[str, np.ndarray]]:
    """Distinct schemes present in a fleet and their volume indices, in
    dense-id order. The grouped runner replays each group under a config
    whose dispatch branch stack is pruned to that one scheme
    (``JaxSimConfig.scheme_group``), instead of paying every registered
    scheme's `lax.switch` branch per step per volume."""
    return [(SCHEME_NAMES[int(sid)],
             np.nonzero(policy.scheme_id == sid)[0])
            for sid in np.unique(policy.scheme_id)]


def _replay_fleet(padded: np.ndarray, cfg_h: JaxSimConfig,
                  policy: FleetPolicy, mesh: Mesh | None) -> dict:
    """One fleet replay (no grouping): shard_map over the mesh when more
    than one device is visible, plain jit otherwise. Returns the final
    batched state (device), blocked until ready. Sharded replays place
    every input straight onto the mesh (fleet axis split across devices)
    and may carry trailing copies of the last volume, which pad the fleet
    to a multiple of the device count; callers keep the first
    ``padded.shape[0]`` rows."""
    masked = bool((padded < 0).any())
    if mesh is None or mesh.size == 1:
        nxts = fleet_annotations(padded, policy.scheme_id)
        return jax.block_until_ready(
            _run_fleet(cfg_h, jnp.asarray(padded),
                       coerce_fleet_annotations(nxts, padded.shape), masked,
                       policy.as_state_arrays()))
    V = padded.shape[0]
    rows = np.minimum(np.arange(-(-V // mesh.size) * mesh.size), V - 1)
    padded, policy = padded[rows], _policy_rows(policy, rows)
    nxts = fleet_annotations(padded, policy.scheme_id)
    fleet = NamedSharding(mesh, P("fleet"))
    return jax.block_until_ready(_sharded_runner(cfg_h, masked, mesh)(
        jax.device_put(padded, fleet),
        coerce_fleet_annotations(nxts, padded.shape, device=fleet),
        policy.as_state_arrays(device=fleet)))


def _policy_rows(policy: FleetPolicy, idx: np.ndarray) -> FleetPolicy:
    return FleetPolicy(scheme_id=policy.scheme_id[idx],
                       selector_id=policy.selector_id[idx],
                       gp_threshold=policy.gp_threshold[idx],
                       nc_window=policy.nc_window[idx],
                       gcsched_id=policy.gcsched_id[idx])


def simulate_fleet_hetero(traces, cfg: JaxSimConfig, policy: FleetPolicy, *,
                          mesh: Mesh | None = None, shard: bool = True,
                          group: bool = True, return_state: bool = False):
    """Replay a heterogeneous-config fleet, sharded across devices when more
    than one is visible and (by default) grouped by placement scheme.

    ``traces``: list of 1-D LBA traces or padded (V, T) matrix; ``policy``:
    per-volume knobs (see :func:`encode_policies` / :func:`policy_grid`).
    ``cfg`` supplies the static shape knobs (n_lbas, segment size, kernels);
    its scheme/selector/gp are ignored in favor of ``policy``.

    ``group=True`` sorts volumes into per-scheme groups and replays each
    group as its own program with the dispatch branch stack pruned to that
    scheme (under vmap, `lax.switch` evaluates *every* branch per step —
    grouping makes each volume pay only its own scheme's work). Every group
    shares the full fleet's static shapes (`hetero_config` over the whole
    policy), so per-volume results are bit-identical to the ungrouped
    replay (and to single-volume runs) — `tests/test_differential.py` pins
    all three. Returns the same result dict as `simulate_fleet` (plus the
    final batched state, volumes in input order, when ``return_state``)."""
    padded = coerce_fleet(traces)
    V = padded.shape[0]
    if policy.n_volumes != V:
        raise ValueError(f"policy covers {policy.n_volumes} volumes, "
                         f"traces cover {V}")
    if cfg.gc_engine == "legacy" and np.any(policy.gcsched_id != 0):
        raise ValueError("GC scheduling policies require the tick engine; "
                         "the legacy engine is the greedy parity oracle")
    cfg_h = hetero_config(cfg, policy)
    if mesh is None and shard:
        mesh = fleet_mesh()

    groups = scheme_groups(policy) if group else [(None, np.arange(V))]
    states = []
    for name, idx in groups:
        cfg_g = cfg_h if name is None else dataclasses.replace(
            cfg_h, scheme_group=(name,))
        states.append(_replay_fleet(padded[idx], cfg_g,
                                    _policy_rows(policy, idx), mesh))
    # reassemble volumes in input order on the host, where the summary reads
    # them anyway (groups share one pytree structure: init_state carries
    # every scheme's slice regardless); sharded pad rows are dropped here
    order = np.argsort(np.concatenate([idx for _, idx in groups]))
    sizes = [len(idx) for _, idx in groups]
    st = jax.tree_util.tree_map(
        lambda *xs: np.concatenate(
            [x[:n] for x, n in zip(xs, sizes)], axis=0)[order],
        *jax.device_get(states))

    res = summarize_fleet(cfg_h, st, V)
    res["fleet"]["n_devices"] = 1 if mesh is None else mesh.size
    res["fleet"]["n_scheme_groups"] = len(groups)
    if return_state:
        return res, st
    return res


# -- sweep aggregation ---------------------------------------------------------

# two-sided 95% Student-t critical values by degrees of freedom (df = n - 1);
# the default sweep runs only a handful of volumes per cell, where the
# normal 1.96 would understate the interval ~6.5x at n = 2
_T95 = {1: 12.706, 2: 4.303, 3: 3.182, 4: 2.776, 5: 2.571, 6: 2.447,
        7: 2.365, 8: 2.306, 9: 2.262, 10: 2.228, 12: 2.179, 15: 2.131,
        20: 2.086, 30: 2.042}


def _t95(df: int) -> float:
    """Nearest tabulated value at or below ``df`` — uniformly conservative
    (wider CI) between table entries and past the df = 30 edge."""
    if df <= 0:
        return float("inf")
    return _T95[max(k for k in _T95 if k <= df)]

def sweep_summary(res: dict, policy: FleetPolicy,
                  cells: list[tuple] | None = None) -> list[dict]:
    """Aggregate a heterogeneous fleet result per policy cell.

    Returns one row per (scheme, selector, gp) with user/GC write totals and
    the cell's overall WA, in grid order when ``cells`` is given (else in
    order of first appearance). Timing-model runs additionally get per-cell
    latency columns (p50/p99 from the cell's merged histogram)."""
    groups: dict[tuple, dict] = {}
    order = []
    for i, vol in enumerate(res["volumes"]):
        key = policy.describe(i)
        if key not in groups:
            groups[key] = {"scheme": key[0], "selector": key[1],
                           "gp_threshold": key[2], "n_volumes": 0,
                           "user_writes": 0, "gc_writes": 0,
                           "overflow": 0, "free_exhausted": 0,
                           "per_volume_wa": []}
            order.append(key)
        g = groups[key]
        g["n_volumes"] += 1
        g["user_writes"] += vol["user_writes"]
        g["gc_writes"] += vol["gc_writes"]
        g["overflow"] += vol["overflow"]
        g["free_exhausted"] += vol["overflow"]
        g["per_volume_wa"].append(vol["wa"])
        if "latency" in vol:
            lat = vol["latency"]
            acc = g.setdefault("_lat", {
                "hist": np.zeros(len(lat["hist"]), np.int64),
                "max": 0.0, "total": 0.0, "gc_debt": 0.0,
                "write_cost": lat["write_cost"]})
            acc["hist"] += np.asarray(lat["hist"])
            acc["max"] = max(acc["max"], lat["max"])
            acc["total"] += lat["total"]
            acc["gc_debt"] += lat["gc_debt"]
    if cells is not None:
        # group keys carry float32 thresholds (they round-trip the device);
        # normalize the grid's python floats the same way before matching
        norm = [(s, sel, float(np.float32(gp))) for s, sel, gp in cells]
        order = [key for key in norm if key in groups]
    rows = []
    for key in order:
        g = groups[key]
        g["wa"] = (g["user_writes"] + g["gc_writes"]) / max(g["user_writes"], 1)
        wa = np.asarray(g["per_volume_wa"], dtype=np.float64)
        g["median_wa"] = float(np.median(wa))
        g["wa_mean"] = float(wa.mean())
        # Student-t 95% CI over the cell's volumes (identical workloads per
        # cell, so this is pure policy-response spread); 0 for n = 1
        g["wa_ci95"] = (float(_t95(len(wa) - 1) * wa.std(ddof=1)
                              / np.sqrt(len(wa)))
                        if len(wa) > 1 else 0.0)
        g["degraded"] = g["overflow"] > 0
        acc = g.pop("_lat", None)
        if acc is not None:
            g["lat_p50"] = hist_quantile(acc["hist"], 0.50, acc["write_cost"])
            g["lat_p99"] = hist_quantile(acc["hist"], 0.99, acc["write_cost"])
            g["lat_max"] = acc["max"]
            g["lat_mean"] = acc["total"] / max(g["user_writes"], 1)
            g["gc_debt"] = acc["gc_debt"]
        rows.append(g)
    return rows


def simulate_fleet_sweep(traces, cfg: JaxSimConfig, *, schemes, selectors,
                         gp_thresholds, nc_window: int = 16,
                         gcsched: str = "greedy",
                         mesh: Mesh | None = None, shard: bool = True,
                         group: bool = True) -> dict:
    """One-call sweep: ``traces`` must hold ``cells × per_cell`` volumes laid
    out cell-major (see `tracegen.tiled_fleet`). Returns the fleet result
    with a ``"sweep"`` list of per-cell aggregates attached."""
    padded = coerce_fleet(traces)
    cells = list(itertools.product(schemes, selectors, gp_thresholds))
    if padded.shape[0] % len(cells):
        raise ValueError(f"{padded.shape[0]} volumes do not tile a "
                         f"{len(cells)}-cell grid")
    per_cell = padded.shape[0] // len(cells)
    policy, cells = policy_grid(schemes, selectors, gp_thresholds,
                                volumes_per_cell=per_cell, nc_window=nc_window,
                                gcsched=gcsched)
    res = simulate_fleet_hetero(padded, cfg, policy, mesh=mesh, shard=shard,
                                group=group)
    res["sweep"] = sweep_summary(res, policy, cells)
    res["policy"] = policy
    return res
