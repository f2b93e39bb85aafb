"""TPU-resident log-structured placement simulator (`jax.lax.scan`).

The numpy simulator (`simulator.py`) is the reference event loop; this module
re-expresses the same volume state machine as dense arrays + `lax.scan` so an
entire trace replay — placement decisions, GP-triggered GC, Greedy or
Cost-Benefit victim selection, SepBIT's on-line ℓ estimation — compiles to a
single XLA program. This is the paper's control plane made TPU-native: all
per-write state transitions are static-shape scatters; GC's variable-length
rewrite work is bounded by the segment size and expressed with masked
scatters (`mode="drop"`).

Schemes come from the placement registry (`core/placement/registry.py`):
every registered scheme carries a JAX triple and runs on this engine —
nosep / sepgc / sepbit, the ported baselines fk / dac / ml / sfs, the Exp#4
ablations uw / gw, and the shared-classifier temperature schemes eti / mq /
sfr / fadac / warcip (whose float decay math lives in
`placement/temperature_shared.py`, executed verbatim by both backends for
bit parity). Per-write dispatch is `jax.lax.switch` on the traced
per-volume scheme id over the registered branch stack; each scheme's
mutable tables (DAC's region ladder, MultiLog's counters, FK's pending-BIT
table, WARCIP's rewrite-interval centroids, ...) live in a per-scheme slice
of the state pytree (keys ``sch_<name>_*``), initialized by the registry
triple's `init_state`.
Future-knowledge schemes additionally consume a per-request BIT annotation
(`fk_annotations`, threaded through the scan alongside the LBA stream).
Selectors: greedy / cost_benefit. Validated against the numpy simulator in
tests/test_jaxsim.py and tests/test_differential.py.

Fleet mode (`simulate_fleet`): the per-volume state dict is a pytree that
`jax.vmap` maps over a leading fleet axis, so one compiled program replays N
independent volumes (heterogeneous traces, same config) in lockstep — the
paper's deployment context, a cloud block store running thousands of volumes.
Traces of unequal length are padded with -1; padded steps are masked no-ops,
so each volume's replay is bit-identical to a single-volume `simulate_jax`.

With ``cfg.use_kernels`` the GC victim argmax routes through the Pallas
``kernels/segsel`` kernel and SepBIT class assignment through
``kernels/classify``; the pure-jnp expressions remain the fallback/oracle.

Heterogeneous-config fleets: the per-volume policy knobs (scheme, selector,
GP threshold, nc window) are *traced* scalars carried inside the state pytree
("p_scheme", "p_selector", "p_gp", "p_ncw", "p_classes"), not Python-static
config, so one compiled program can replay a fleet where every volume runs a
different placement policy. Scheme dispatch is `jax.lax.switch` over the
registry's branch stack and selector dispatch `jnp.where` over the two
selector ids; the class axis is padded to ``cfg.n_class_slots`` (the widest
scheme present) with inactive classes masked to exact no-ops, so a volume's
replay stays bit-identical to a single-volume run of its own scheme-derived
config. `core/fleetshard.py` builds the per-volume policy arrays and shards
the fleet axis across devices.

GC engine (``cfg.gc_engine``): the default **tick** engine runs GC as
synchronized fleet-level ticks — after each vmapped user write, a single
``lax.while_loop`` ticks until no volume's garbage proportion exceeds its
``p_gp`` threshold; triggering volumes run the fused `_gc_once` (one
segmented scatter over (class, rank) keys) while the rest take masked exact
no-ops, and the cheap GP guard runs *before* any victim-selection argmax.
``cfg.scheme_group`` additionally prunes the dispatch branch stack to a
static scheme subset (fleetshard groups volumes by scheme so each group
compiles only its own branches). The **legacy** engine keeps the pre-tick
formulation (entry-point victim selection, per-class unrolled rewrite) as
the benchmark baseline and a bitwise parity oracle; docs/architecture.md
maps the whole stack.

Timing/SLO model (``cfg.timing``): per-volume ``lat_*`` state slices carry a
foreground clock, a device-busy horizon, and a fixed-bucket latency
histogram; each user write charges ``write_cost`` plus any queueing behind
charged GC work, and each victim rewrite books ``nvalid * gc_block_cost``
of GC debt. *When* that debt lands on the foreground is the traced
per-volume scheduling policy ``p_gcsched`` (greedy / rate_limited /
idle_window — see GCSCHED_IDS and docs/gc_scheduling.md). With timing off
the ``lat_*`` keys still exist (one pytree structure) but are carried
through untouched, and all non-``lat_*`` state is bit-identical to a
timing-on greedy run.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from .placement import registry as scheme_registry
from .placement.jax_schemes import NOBIT

BIG = jnp.int32(2 ** 30)

# Policy-id encodings for the traced per-volume knobs. The scheme tables are
# views of the placement registry (`placement/registry.py`) — dense ids in
# JAX-registration order; registering a new scheme extends them automatically.
_JAX_SCHEMES = scheme_registry.jax_schemes()
SCHEME_IDS = {sd.name: i for i, (sd, _) in enumerate(_JAX_SCHEMES)}
SCHEME_NAMES = tuple(sd.name for sd, _ in _JAX_SCHEMES)
SCHEME_CLASSES = tuple(sd.n_classes for sd, _ in _JAX_SCHEMES)
SCHEME_REQUIRES_FUTURE = tuple(sd.requires_future for sd, _ in _JAX_SCHEMES)
SELECTOR_IDS = {"greedy": 0, "cost_benefit": 1}
SELECTOR_NAMES = tuple(SELECTOR_IDS)
MAX_CLASSES = max(SCHEME_CLASSES)

# GC scheduling policies (traced per-volume, like the selector ids). All
# three run the same tick engine; they differ in *when* GC work runs and
# when its cost lands on the foreground timeline (docs/gc_scheduling.md):
#   greedy       — GC whenever GP exceeds p_gp; full rewrite cost charged
#                  the same tick (today's behavior, the bit-parity baseline)
#   rate_limited — identical GC decisions, but at most cfg.gc_rate rewritten
#                  blocks are *charged* against the foreground per tick; the
#                  rest accrues as lat_debt and drains in later ticks
#   idle_window  — defer GC while recent-write density is high, with a hard
#                  free-pool watermark override so the pool can't exhaust
GCSCHED_IDS = {"greedy": 0, "rate_limited": 1, "idle_window": 2}
GCSCHED_NAMES = tuple(GCSCHED_IDS)

# Latency histogram: quarter-octave log2 buckets of latency/write_cost.
# Bucket b covers [2^(b/4), 2^((b+1)/4)); quantiles report the lower edge,
# so an uncontended trace (every latency == write_cost, bucket 0) yields
# p50 == p99 == write_cost exactly.
LAT_BUCKETS_PER_OCTAVE = 4


@dataclasses.dataclass(frozen=True)
class JaxSimConfig:
    n_lbas: int
    segment_size: int = 128
    gp_threshold: float = 0.15
    selector: str = "cost_benefit"          # or "greedy"
    scheme: str = "sepbit"                  # sepbit | sepgc | nosep
    nc_window: int = 16
    max_gc_per_step: int = 64
    n_segments: int | None = None           # S_max; default sized from capacity
    use_kernels: bool = False               # route hot paths via Pallas kernels
    #                                         (interpreted on CPU, compiled on
    #                                         TPU: repro.kernels.interpret_mode)
    class_slots: int | None = None          # pad the class axis (hetero fleets)
    sfs_resample: int = 4096                # SFS quantile refresh period
    #                                         (= numpy SFS resample_every)
    gc_engine: str = "tick"                 # "tick" (synchronized GC ticks,
    #                                         fused _gc_once) or "legacy" (the
    #                                         pre-tick per-volume loop, kept as
    #                                         the gcbench baseline + a bitwise
    #                                         parity oracle for the rewrite)
    scheme_group: tuple[str, ...] | None = None
    #                                       # prune the lax.switch branch stack
    #                                         to these schemes only (grouped
    #                                         dispatch; None = full registry)
    timing: bool = False                    # latency/SLO model: charge service
    #                                         times and report p50/p99/max
    #                                         foreground latency alongside WA
    write_cost: float = 1.0                 # service time per user write
    gc_block_cost: float = 1.0              # device time per GC-rewritten block
    gc_sched: str = "greedy"                # greedy | rate_limited | idle_window
    gc_rate: int = 4                        # rate_limited: blocks charged/tick
    gc_watermark: int | None = None         # idle_window: free rows below which
    #                                         deferral is overridden (default
    #                                         2 * n_class_slots + 2 — one GC
    #                                         iteration can consume up to C
    #                                         fresh rows while releasing one)
    idle_density: float = 0.5               # idle_window: defer while the
    #                                         write-density EWMA exceeds this
    density_window: int = 16                # EWMA window (writes) for density
    lat_buckets: int = 64                   # latency histogram width

    @property
    def n_classes(self) -> int:
        return scheme_registry.get(self.scheme).n_classes

    @property
    def n_class_slots(self) -> int:
        """Static width of the class axis. Heterogeneous fleets pad every
        volume to the widest scheme present; classes >= the volume's own
        count are masked to no-ops."""
        return self.class_slots if self.class_slots is not None else self.n_classes

    @property
    def s_max(self) -> int:
        if self.n_segments is not None:
            return self.n_segments
        cap_segments = int(np.ceil(self.n_lbas / (1.0 - self.gp_threshold)
                                   / self.segment_size))
        return 2 * cap_segments + 4 * self.n_class_slots + 8

    @property
    def watermark_rows(self) -> int:
        """Free-row floor for idle_window's hard override."""
        if self.gc_watermark is not None:
            return self.gc_watermark
        return 2 * self.n_class_slots + 2

    @property
    def pad_row(self) -> int:
        """Index of the sacrificial overflow segment row (see init_state)."""
        return self.s_max

    @property
    def n_rows(self) -> int:
        return self.s_max + 1


def _scheme_id_or_raise(scheme: str) -> int:
    if scheme not in SCHEME_IDS:
        raise ValueError(
            f"scheme {scheme!r} has no JAX implementation (numpy-only); "
            f"JAX schemes: {SCHEME_NAMES}")
    return SCHEME_IDS[scheme]


def _dispatch_table(cfg: JaxSimConfig):
    """The (SchemeDef, JaxPlacement) branch stack this config dispatches
    over, plus each branch's *global* dense scheme id.

    ``cfg.scheme_group`` prunes the stack: a fleet whose volumes all run
    schemes from the group compiles only those branches instead of paying
    every registered scheme's branch per step (under vmap, ``lax.switch``
    lowers to a select over *all* branch results). `core/fleetshard.py`
    groups volumes by scheme id and runs each group under a pruned config;
    the traced ``p_scheme`` values keep their global ids and are remapped to
    branch positions at dispatch time."""
    if cfg.scheme_group is None:
        return _JAX_SCHEMES, tuple(range(len(_JAX_SCHEMES)))
    gids = tuple(_scheme_id_or_raise(n) for n in cfg.scheme_group)
    return tuple(_JAX_SCHEMES[g] for g in gids), gids


def _local_scheme_index(gids, scheme_id):
    """Branch position of the traced global ``scheme_id`` in a (possibly
    pruned) stack. Ids outside the stack map to branch 0 — group membership
    is validated host-side (`default_policy` / the fleetshard grouper)."""
    if gids == tuple(range(len(_JAX_SCHEMES))):
        return scheme_id
    local = jnp.int32(0)
    for k, g in enumerate(gids):
        local = jnp.where(scheme_id == g, jnp.int32(k), local)
    return local


def default_policy(cfg: JaxSimConfig) -> dict:
    """Traced-policy scalars equivalent to the static knobs in ``cfg``."""
    if cfg.scheme_group is not None and cfg.scheme not in cfg.scheme_group:
        raise ValueError(f"scheme {cfg.scheme!r} is outside this config's "
                         f"dispatch group {cfg.scheme_group}")
    if cfg.gc_sched not in GCSCHED_IDS:
        raise ValueError(f"unknown gc_sched {cfg.gc_sched!r}; "
                         f"choices: {GCSCHED_NAMES}")
    if cfg.gc_engine == "legacy" and cfg.gc_sched != "greedy":
        raise ValueError("GC scheduling policies require the tick engine; "
                         "the legacy engine is the greedy parity oracle")
    return {
        "p_scheme": jnp.int32(_scheme_id_or_raise(cfg.scheme)),
        "p_selector": jnp.int32(SELECTOR_IDS[cfg.selector]),
        "p_gp": jnp.float32(cfg.gp_threshold),
        "p_ncw": jnp.int32(cfg.nc_window),
        "p_classes": jnp.int32(cfg.n_classes),
        "p_gcsched": jnp.int32(GCSCHED_IDS[cfg.gc_sched]),
    }


def init_state(cfg: JaxSimConfig, policy: dict | None = None) -> dict:
    # Segment arrays carry one extra *sacrificial* row (index cfg.pad_row,
    # state 3 = reserved): when the free pool is exhausted, allocations land
    # there instead of wrapping around to row S-1 via negative indexing and
    # silently corrupting a live segment. Under sustained exhaustion the pad
    # row acts as one emergency segment (filled past capacity its writes are
    # dropped, so occupancy/GP stats degrade to logical rather than physical
    # accounting) — live rows are never corrupted, and every pad allocation
    # is counted in ``overflow`` so callers can detect an undersized config.
    #
    # ``policy`` (traced per-volume knobs, see default_policy) controls how
    # many of the C class slots are live: slots >= p_classes stay free and are
    # masked to no-ops everywhere downstream, so a padded-class volume is
    # bit-identical to one built with its own scheme-derived class count.
    if policy is None:
        policy = default_policy(cfg)
    active = jnp.asarray(policy["p_classes"], jnp.int32)
    R, s, C, n = cfg.n_rows, cfg.segment_size, cfg.n_class_slots, cfg.n_lbas
    slot = jnp.arange(C, dtype=jnp.int32)
    state = {
        "seg_lba": jnp.zeros((R, s), jnp.int32),
        "seg_utime": jnp.zeros((R, s), jnp.int32),
        "seg_valid": jnp.zeros((R, s), jnp.bool_),
        "seg_n": jnp.zeros(R, jnp.int32),
        "seg_nvalid": jnp.zeros(R, jnp.int32),
        "seg_cls": jnp.zeros(R, jnp.int32),
        "seg_state": jnp.zeros(R, jnp.int32),   # 0 free, 1 open, 2 sealed, 3 reserved
        "seg_ctime": jnp.zeros(R, jnp.int32),
        "seg_stime": jnp.zeros(R, jnp.int32),
        "open_sid": jnp.arange(C, dtype=jnp.int32),
        "loc_seg": jnp.full(n, -1, jnp.int32),
        "loc_off": jnp.zeros(n, jnp.int32),
        "last_uw": jnp.full(n, -BIG, jnp.int32),
        "t": jnp.int32(0),
        "total_occ": jnp.int32(0),
        "total_valid": jnp.int32(0),
        "user_writes": jnp.int32(0),
        "gc_writes": jnp.int32(0),
        "reclaimed": jnp.int32(0),
        "overflow": jnp.int32(0),
        "ell": jnp.float32(jnp.inf),
        "ell_tot": jnp.float32(0),
        "nc": jnp.int32(0),
        "class_user": jnp.zeros(C, jnp.int32),
        "class_gc": jnp.zeros(C, jnp.int32),
        # latency/SLO model (docs/gc_scheduling.md). Always present so the
        # pytree structure (and state_spec, hence the SA202 drift gate) is
        # independent of cfg.timing; with timing off every key below except
        # lat_dens (the idle_window density EWMA, tracked unconditionally)
        # is carried through bit-unchanged.
        "lat_now": jnp.float32(0),      # foreground clock (completion time
        #                                 of the volume's latest user write)
        "lat_busy": jnp.float32(0),     # device-busy horizon: foreground
        #                                 writes queue behind charged GC work
        "lat_debt": jnp.float32(0),     # GC work done but not yet charged
        "lat_charged": jnp.float32(0),  # cumulative charged GC time
        "lat_dens": jnp.float32(0),     # recent-write density EWMA
        "lat_sum": jnp.float32(0),      # sum of per-write latencies
        "lat_max": jnp.float32(0),      # max per-write latency
        "lat_hist": jnp.zeros(cfg.lat_buckets, jnp.int32),
    }
    # every registered JAX scheme contributes its state slice (sch_<name>_*)
    # to every volume — heterogeneous fleets need one pytree structure, and
    # inactive schemes' slices are never touched (their branch never runs)
    for sd, jp in _JAX_SCHEMES:
        extra = jp.init_state(cfg)
        clash = set(extra) & set(state)
        if clash:
            raise ValueError(f"scheme {sd.name!r} state keys collide: {clash}")
        state.update(extra)
    state.update({k: jnp.asarray(v) for k, v in policy.items()})
    # the first p_classes segments start open, one per live class; padded
    # class slots leave their row in the free pool (as it would be for a
    # config without the padding)
    state["seg_state"] = state["seg_state"].at[:C].set(
        jnp.where(slot < active, 1, 0))
    state["seg_cls"] = state["seg_cls"].at[:C].set(jnp.where(slot < active, slot, 0))
    state["seg_state"] = state["seg_state"].at[cfg.pad_row].set(3)
    return state


def state_spec(cfg: JaxSimConfig, policy: dict | None = None) -> dict:
    """Canonical shape/dtype spec of the carried state pytree, as a dict of
    ``jax.ShapeDtypeStruct`` — computed abstractly (no device allocation).

    This is the single source of truth for what the tick engine carries:
    the static analyzer (`repro.analysis`) seeds scheme traces from it, and
    its dtype-drift lint (SA202) checks that one user step maps this spec
    exactly onto itself — a leaf whose dtype, shape, or weak-type flag
    changes across a tick boundary would silently re-trace/recompile (or
    truncate) inside ``lax.scan``."""
    return jax.eval_shape(lambda: init_state(cfg, policy))


# -- placement rules (lax.switch over the registry's branch stack) ------------

def _user_class_dispatch(cfg: JaxSimConfig, st, lba, v, nxt):
    """Class for one user write under the volume's traced scheme id.

    Each scheme in the config's dispatch table (the full registry, or the
    pruned ``cfg.scheme_group``) is one switch branch `(st, lba, v, nxt) ->
    (cls, st)`; branches update only their own ``sch_<name>_*`` state slice,
    so every branch returns an identically-structured state dict and the
    switch output is well-formed. A single-scheme group skips the switch
    entirely. ``nxt`` is the request's BIT annotation (consumed by
    future-knowledge schemes, ignored elsewhere)."""
    table, gids = _dispatch_table(cfg)
    branches = tuple(functools.partial(jp.user_class, cfg)
                     for _, jp in table)
    if len(branches) == 1:
        return branches[0](st, lba, v, nxt)
    return jax.lax.switch(_local_scheme_index(gids, st["p_scheme"]),
                          branches, st, lba, v, nxt)


def _gc_class_dispatch(cfg: JaxSimConfig, st, victim_cls, lba_v, utime_v,
                       valid_v):
    """Classes for every slot of a GC victim (Algorithm 1 GCWrite and its
    baseline counterparts), vectorized over the victim's slots.

    With ``cfg.use_kernels`` the stateless (elementwise) schemes are batched
    through the Pallas classify kernel — evaluated once, selected by the
    traced scheme id inside the kernel — and their switch branches just
    return that result; stateful schemes always classify via their jnp
    branch (they need their per-LBA tables, and must update them). Pruned
    dispatch groups skip the kernel call when no scheme in the group is
    elementwise, and the kernel's select chain is pruned to the group."""
    table, gids = _dispatch_table(cfg)
    g = st["t"] - utime_v
    ew = None
    if cfg.use_kernels and any(jp.elementwise is not None for _, jp in table):
        from_c1 = jnp.full(g.shape, 0, jnp.int32) + (victim_cls == 0)
        ew = _classify_kernel_call(cfg, st, jnp.zeros_like(g), g, from_c1,
                                   jnp.ones_like(g))
    branches = []
    for _, jp in table:
        if ew is not None and jp.elementwise is not None:
            branches.append(lambda st_, *a, _ew=ew: (_ew, st_))
        else:
            branches.append(functools.partial(jp.gc_classes, cfg))
    if len(branches) == 1:
        return branches[0](st, victim_cls, lba_v, utime_v, valid_v, g)
    return jax.lax.switch(_local_scheme_index(gids, st["p_scheme"]),
                          tuple(branches), st, victim_cls,
                          lba_v, utime_v, valid_v, g)


def _scores(st):
    """Victim scores over all segments; -inf for non-sealed / zero-garbage.
    Both selectors are evaluated and the volume's traced id picks one — the
    per-branch values are unchanged from the static-config formulation."""
    n = st["seg_n"].astype(jnp.float32)
    nv = st["seg_nvalid"].astype(jnp.float32)
    garbage = n - nv
    greedy = garbage / jnp.maximum(n, 1.0)
    u = nv / jnp.maximum(n, 1.0)
    age = jnp.maximum(st["t"] - st["seg_stime"], 0).astype(jnp.float32)
    cost_benefit = (1.0 - u) * age / (1.0 + u)
    score = jnp.where(st["p_selector"] == SELECTOR_IDS["greedy"],
                      greedy, cost_benefit)
    eligible = (st["seg_state"] == 2) & (garbage > 0)
    return jnp.where(eligible, score, -jnp.inf)


# -- kernel-backed hot paths --------------------------------------------------

def _select_victim(cfg: JaxSimConfig, st):
    """GC victim argmax, or -1 when no segment is eligible — Pallas segsel
    kernel or the jnp oracle above. Runs once per GC iteration: the result
    both gates the trigger loop and names the victim. The selector is the
    volume's traced policy id (a per-volume scalar input to the kernel)."""
    if cfg.use_kernels:
        from repro.kernels.segsel import segment_select
        idx, _ = segment_select(
            st["seg_n"], st["seg_nvalid"], st["seg_stime"], st["seg_state"],
            st["t"], selector_id=st["p_selector"])
        return idx.astype(jnp.int32)
    scores = _scores(st)
    idx = jnp.argmax(scores).astype(jnp.int32)
    return jnp.where(jnp.isfinite(scores[idx]), idx, -1)


def _classify_kernel_call(cfg: JaxSimConfig, st, v, g, from_c1, is_gc):
    from repro.kernels.classify import classify
    _, gids = _dispatch_table(cfg)
    sids = None if cfg.scheme_group is None else gids
    return classify(v, g, from_c1, is_gc, st["ell"],
                    scheme_id=st["p_scheme"], scheme_ids=sids)


def _select_victims_fleet(cfg: JaxSimConfig, st):
    """Per-volume GC victims for a batched (V-leading) fleet state — one
    batched Pallas segsel call (grid over volumes × tiles) under
    ``cfg.use_kernels``, else the vmapped jnp argmax."""
    if cfg.use_kernels:
        from repro.kernels.segsel import segment_select_batch
        idx, _ = segment_select_batch(
            st["seg_n"], st["seg_nvalid"], st["seg_stime"], st["seg_state"],
            st["t"], selector_ids=st["p_selector"])
        return idx.astype(jnp.int32)
    return jax.vmap(functools.partial(_select_victim, cfg))(st)


# -- GC: rewrite one victim segment ------------------------------------------

def _alloc_free_ids(cfg: JaxSimConfig, st, count):
    """Indices of ``count`` free segments (static shape). When the free pool
    is exhausted the fill is the sacrificial ``cfg.pad_row`` (never free:
    state 3), not -1 — a -1 scatter index would wrap to the last real row."""
    free = st["seg_state"] == 0
    if count == 1:
        # every user write asks for one row: the first free one is an
        # argmax, one reduction, where nonzero's prefix sum costs several
        # device ops per scan step (the same row either way)
        first = jnp.argmax(free).astype(jnp.int32)
        return jnp.where(free[first], first, cfg.pad_row)[None]
    ids, = jnp.nonzero(free, size=count, fill_value=cfg.pad_row)
    return ids.astype(jnp.int32)


def _gc_bookkeeping(cfg: JaxSimConfig, st, victim):
    """Shared head of both GC engines: ℓ estimation (Algorithm 1 lines 4-9),
    class dispatch (letting stateful schemes update their tables under the
    refreshed ℓ), and free-segment allocation. Returns the updated state,
    the victim's columns, per-slot classes (-1 for dead slots), and the C
    candidate fresh segment ids."""
    C = cfg.n_class_slots
    lba_v = st["seg_lba"][victim]
    utime_v = st["seg_utime"][victim]
    valid_v = st["seg_valid"][victim]
    victim_cls = st["seg_cls"][victim]

    is_c1 = victim_cls == 0          # only Class-1 victims feed ℓ
    nc = st["nc"] + jnp.where(is_c1, 1, 0)
    ell_tot = st["ell_tot"] + jnp.where(
        is_c1, (st["t"] - st["seg_ctime"][victim]).astype(jnp.float32), 0.0)
    refresh = nc >= st["p_ncw"]
    ell = jnp.where(refresh, ell_tot / jnp.maximum(nc, 1), st["ell"])
    nc = jnp.where(refresh, 0, nc)
    ell_tot = jnp.where(refresh, 0.0, ell_tot)

    st = dict(st, ell=ell, ell_tot=ell_tot, nc=nc)
    gc_cls, st = _gc_class_dispatch(cfg, st, victim_cls, lba_v, utime_v,
                                    valid_v)
    classes = jnp.where(valid_v, gc_cls, -1)
    free_ids = _alloc_free_ids(cfg, st, C)
    return st, lba_v, utime_v, classes, free_ids


def _gc_once(cfg: JaxSimConfig, st, victim):
    """Rewrite one victim segment: one fused segmented scatter over
    ``(class, rank)`` keys.

    The historical formulation (`_gc_once_legacy`) unrolled a Python loop
    over the C class slots, re-running the gather/scatter cascade C times
    per GC; here every victim slot computes its destination ``(segment,
    offset)`` from its class's open segment and rank-within-class, and one
    scatter per array moves all slots at once. Bit-identical to the legacy
    unroll whenever the free pool is not exhausted (the parity gate in
    tests/test_differential.py pins this); under exhaustion several classes
    can alias the shared sacrificial pad row, where the fused form reads all
    open-segment fills upfront instead of sequentially — the pad row's
    degraded (logical-not-physical) accounting differs in that corner, but
    every engine runs the same program, live rows are never corrupted, and
    ``overflow`` still counts every pad allocation."""
    s, C, n = cfg.segment_size, cfg.n_class_slots, cfg.n_lbas
    victim = jnp.maximum(victim, 0)  # caller guards eligibility (victim >= 0)
    k_total = st["seg_nvalid"][victim]
    victim_n = st["seg_n"][victim]
    st, lba_v, utime_v, classes, free_ids = _gc_bookkeeping(cfg, st, victim)
    drop = jnp.int32(cfg.n_rows)     # out-of-range row => scatter dropped

    # per-slot (class, rank) keys: rank = position among same-class live slots
    slot_cls = jnp.clip(classes, 0, C - 1)
    onehot = (classes[:, None]
              == jnp.arange(C, dtype=jnp.int32)[None, :]).astype(jnp.int32)
    cum = jnp.cumsum(onehot, axis=0)                      # (s, C)
    rank = jnp.take_along_axis(cum, slot_cls[:, None], 1)[:, 0] - 1
    k = cum[-1]                                           # (C,) per-class count

    # per-class destinations: the class's current open segment, spilling into
    # a fresh free segment once full. Open sids, fresh ids, and the victim
    # are pairwise distinct (state 1 / 0 / 2) until exhaustion aliases fresh
    # slots onto the pad row. Padded class slots (>= p_classes) never match
    # any slot (k = 0) and their stale open_sid is masked out of every
    # metadata write below.
    cls_active = jnp.arange(C, dtype=jnp.int32) < st["p_classes"]
    sids = st["open_sid"]
    n0 = st["seg_n"][sids]
    room = jnp.maximum(s - n0, 0)    # clamp: a pad-row open segment can sit
    #                                  past capacity; negative room would
    #                                  credit phantom blocks to the fresh row
    took1 = jnp.minimum(k, room)
    took2 = k - took1

    live = classes >= 0
    in_first = live & (rank < room[slot_cls])
    dst_sid = jnp.where(live, jnp.where(in_first, sids[slot_cls],
                                        free_ids[slot_cls]), drop)
    dst_off = jnp.where(in_first, n0[slot_cls] + rank, rank - room[slot_cls])
    seg_lba = st["seg_lba"].at[dst_sid, dst_off].set(lba_v, mode="drop")
    seg_utime = st["seg_utime"].at[dst_sid, dst_off].set(utime_v, mode="drop")
    seg_valid = st["seg_valid"].at[dst_sid, dst_off].set(True, mode="drop")
    dst_lba = jnp.where(live, lba_v, n)                  # n => dropped
    loc_seg = st["loc_seg"].at[dst_lba].set(dst_sid, mode="drop")
    loc_off = st["loc_off"].at[dst_lba].set(dst_off, mode="drop")

    # per-class metadata, as masked C-vector scatters (drop = no-op): fill
    # counters, first-block creation time, seal-if-full + promote-fresh
    seg_n = st["seg_n"].at[sids].add(took1).at[free_ids].add(took2)
    seg_nvalid = st["seg_nvalid"].at[sids].add(took1).at[free_ids].add(took2)
    seg_ctime = st["seg_ctime"].at[
        jnp.where((n0 == 0) & (k > 0), sids, drop)].set(st["t"], mode="drop")
    sealed = cls_active & (n0 + took1 >= s)
    ssid = jnp.where(sealed, sids, drop)
    seg_state = st["seg_state"].at[ssid].set(2, mode="drop")
    seg_stime = st["seg_stime"].at[ssid].set(st["t"], mode="drop")
    pfresh = jnp.where(sealed, free_ids, drop)           # promote to open
    seg_state = seg_state.at[pfresh].set(1, mode="drop")
    seg_cls = st["seg_cls"].at[pfresh].set(
        jnp.arange(C, dtype=jnp.int32), mode="drop")
    seg_ctime = seg_ctime.at[pfresh].set(st["t"], mode="drop")
    open_sid = jnp.where(sealed, free_ids, sids)
    used_pad = (free_ids == cfg.pad_row) & ((took2 > 0) | sealed)
    overflow = st["overflow"] + jnp.sum(used_pad.astype(jnp.int32))

    # over-capacity appends to the pad row are dropped; cap its fill count
    seg_n = seg_n.at[cfg.pad_row].min(s)

    # release the victim; the sacrificial pad row (reachable as a victim only
    # after free-pool exhaustion promoted it) returns to reserved state 3,
    # never to the free pool — _alloc_free_ids' fill must stay "never free"
    seg_state = seg_state.at[victim].set(
        jnp.where(victim == cfg.pad_row, 3, 0))
    seg_valid = seg_valid.at[victim].set(False)
    seg_n = seg_n.at[victim].set(0)
    seg_nvalid = seg_nvalid.at[victim].set(0)

    # total_valid is untouched: GC moves valid blocks, it never creates or
    # destroys them (the conservation property in tests/test_property.py)
    return dict(
        st,
        seg_lba=seg_lba, seg_utime=seg_utime, seg_valid=seg_valid,
        seg_n=seg_n, seg_nvalid=seg_nvalid, seg_cls=seg_cls,
        seg_state=seg_state, seg_ctime=seg_ctime, seg_stime=seg_stime,
        open_sid=open_sid, loc_seg=loc_seg, loc_off=loc_off,
        total_occ=st["total_occ"] - victim_n + k_total,
        gc_writes=st["gc_writes"] + k_total,
        reclaimed=st["reclaimed"] + 1,
        overflow=overflow,
        class_gc=st["class_gc"] + k,
        **_gc_time_debt(cfg, st, k_total),
    )


def _gp(st):
    occ = jnp.maximum(st["total_occ"], 1).astype(jnp.float32)
    return 1.0 - st["total_valid"].astype(jnp.float32) / occ


# -- GC scheduling + the timing/SLO model -------------------------------------

def _gc_time_debt(cfg: JaxSimConfig, st, k_total) -> dict:
    """State delta booking one victim rewrite's device time as lat_debt.
    Empty (an exact no-op on the jaxpr) with the timing model off."""
    if not cfg.timing:
        return {}
    return {"lat_debt": st["lat_debt"]
            + k_total.astype(jnp.float32) * jnp.float32(cfg.gc_block_cost)}


def _gc_deferred(cfg: JaxSimConfig, st):
    """idle_window's defer predicate, evaluated per GC iteration: skip GC
    while the recent-write density EWMA says the foreground is busy, unless
    the free pool has drained to the hard watermark (then GC runs regardless
    — the override that keeps the pool from exhausting). False for greedy
    and rate_limited volumes, so their GC decisions are untouched."""
    idle = st["p_gcsched"] == GCSCHED_IDS["idle_window"]
    hot = st["lat_dens"] > jnp.float32(cfg.idle_density)
    free_rows = jnp.sum((st["seg_state"] == 0).astype(jnp.int32))
    return idle & hot & (free_rows >= cfg.watermark_rows)


def _charge_gc(cfg: JaxSimConfig, st):
    """Move accrued GC debt onto the foreground busy horizon (end of tick).

    greedy and idle_window charge the whole debt the tick it accrues;
    rate_limited caps the charge at ``gc_rate * gc_block_cost`` per tick and
    carries the rest — identical GC *decisions* (non-lat state bit-equal to
    greedy), different *timing*. Conservation invariant (pinned in
    tests/test_timing.py): lat_charged + lat_debt == gc_writes * gc_block_cost.
    """
    if not cfg.timing:
        return st
    cap = jnp.float32(cfg.gc_rate * cfg.gc_block_cost)
    limited = st["p_gcsched"] == GCSCHED_IDS["rate_limited"]
    charge = jnp.where(limited, jnp.minimum(st["lat_debt"], cap),
                       st["lat_debt"])
    return dict(
        st,
        lat_busy=jnp.maximum(st["lat_busy"], st["lat_now"]) + charge,
        lat_debt=st["lat_debt"] - charge,
        lat_charged=st["lat_charged"] + charge,
    )


def _maybe_gc(cfg: JaxSimConfig, st):
    """GC trigger loop, tick formulation: the cheap GP guard alone gates the
    loop, and victim selection (a full masked argmax over the segment pool)
    moved *inside* the body — the legacy formulation paid that argmax at loop
    entry on every user write, GC or not. A triggering state with no
    eligible victim sets ``stalled`` after one selection and exits (the
    legacy loop's ``victim >= 0`` entry guard, one iteration later).

    ``_gc_deferred`` joins the guard: an idle_window volume skips GC while
    the foreground is busy (unless the free-pool watermark overrides), and
    the predicate re-evaluates each iteration so a watermark-forced burst
    stops as soon as the pool recovers. Greedy volumes see a constant-False
    term — their iteration sequence is unchanged."""
    def cond(carry):
        st, i, stalled = carry
        return (_gp(st) > st["p_gp"]) & ~_gc_deferred(cfg, st) & ~stalled \
            & (i < cfg.max_gc_per_step)

    def body(carry):
        st, i, stalled = carry
        victim = _select_victim(cfg, st)
        st = jax.lax.cond(victim >= 0,
                          lambda s: _gc_once(cfg, s, victim),
                          lambda s: s, st)
        return st, i + 1, victim < 0

    st, _, _ = jax.lax.while_loop(
        cond, body, (st, jnp.int32(0), jnp.asarray(False)))
    return st


def fleet_gc_tick(cfg: JaxSimConfig, st, step_active=None):
    """Synchronized fleet-level GC tick over a batched (V-leading) state.

    One ``lax.while_loop`` serves the whole fleet: each tick selects a
    victim and runs the fused `_gc_once` for every volume whose garbage
    proportion exceeds its traced ``p_gp`` threshold; volumes below
    threshold (or stalled, or on a padded no-op step — ``step_active``) take
    a masked exact no-op, their state passed through bit-unchanged. The GP
    guard is evaluated *before* any victim selection, so a step where no
    volume triggers costs one reduction, not a fleet of segment argmaxes —
    and the loop itself runs zero iterations.

    Per volume this replays exactly the `_maybe_gc` iteration sequence (a
    volume's triggering ticks are a prefix of the tick loop, so the shared
    tick counter enforces the same ``max_gc_per_step`` budget), which is
    what keeps fleet replays bit-identical to single-volume runs."""
    def need(st, stalled):
        over = jax.vmap(_gp)(st) > st["p_gp"]
        over = over & ~jax.vmap(functools.partial(_gc_deferred, cfg))(st)
        over = over & ~stalled
        if step_active is not None:
            over = over & step_active
        return over

    def cond(carry):
        st, i, stalled = carry
        return jnp.any(need(st, stalled)) & (i < cfg.max_gc_per_step)

    def body(carry):
        st, i, stalled = carry
        active = need(st, stalled)
        victims = _select_victims_fleet(cfg, st)
        do = active & (victims >= 0)
        new = jax.vmap(functools.partial(_gc_once, cfg))(st, victims)
        st = jax.tree_util.tree_map(
            lambda a, b: jnp.where(do.reshape(do.shape + (1,) * (a.ndim - 1)),
                                   a, b), new, st)
        return st, i + 1, stalled | (active & (victims < 0))

    V = st["t"].shape[0]
    st, _, _ = jax.lax.while_loop(
        cond, body, (st, jnp.int32(0), jnp.zeros(V, bool)))
    return st


# -- legacy GC engine ----------------------------------------------------------
# The pre-tick formulation (victim selection at loop entry on every user
# write; per-class unrolled rewrite), retained verbatim as (a) the baseline
# that `benchmarks/run.py --mode gcbench` measures the tick engine against
# and (b) a bitwise parity oracle for the fused `_gc_once` rewrite
# (tests/test_differential.py). Select with ``JaxSimConfig(gc_engine="legacy")``.

def _gc_once_legacy(cfg: JaxSimConfig, st, victim):
    s, C, n = cfg.segment_size, cfg.n_class_slots, cfg.n_lbas
    victim = jnp.maximum(victim, 0)
    k_total = st["seg_nvalid"][victim]
    victim_n = st["seg_n"][victim]
    st, lba_v, utime_v, classes, free_ids = _gc_bookkeeping(cfg, st, victim)

    seg_lba, seg_utime, seg_valid = st["seg_lba"], st["seg_utime"], st["seg_valid"]
    seg_n, seg_nvalid = st["seg_n"], st["seg_nvalid"]
    seg_cls, seg_state = st["seg_cls"], st["seg_state"]
    seg_ctime, seg_stime = st["seg_ctime"], st["seg_stime"]
    open_sid, loc_seg, loc_off = st["open_sid"], st["loc_seg"], st["loc_off"]
    class_gc = st["class_gc"]
    overflow = st["overflow"]

    for cls in range(C):  # static unroll; each class's blocks batch-appended
        # padded class slots must be exact no-ops: their k is always 0, but
        # the seal/promote logic reads seg_n through a stale open_sid that
        # may now belong to another class's recycled row — gate it.
        cls_active = jnp.int32(cls) < st["p_classes"]
        mask = classes == cls
        ranks = jnp.cumsum(mask) - 1
        k = jnp.where(mask.any(), jnp.max(jnp.where(mask, ranks, -1)) + 1, 0)
        sid = open_sid[cls]
        n0 = seg_n[sid]
        room = jnp.maximum(s - n0, 0)
        seg_ctime = seg_ctime.at[sid].set(
            jnp.where((n0 == 0) & (k > 0), st["t"], seg_ctime[sid]))
        in_first = mask & (ranks < room)
        in_second = mask & ~in_first
        fresh = free_ids[cls]

        p1 = jnp.where(in_first, n0 + ranks, s)        # s => dropped
        seg_lba = seg_lba.at[sid, p1].set(lba_v, mode="drop")
        seg_utime = seg_utime.at[sid, p1].set(utime_v, mode="drop")
        seg_valid = seg_valid.at[sid, p1].set(True, mode="drop")
        dst1 = jnp.where(in_first, lba_v, n)           # n => dropped
        loc_seg = loc_seg.at[dst1].set(sid, mode="drop")
        loc_off = loc_off.at[dst1].set(n0 + ranks, mode="drop")

        p2 = jnp.where(in_second, ranks - room, s)
        seg_lba = seg_lba.at[fresh, p2].set(lba_v, mode="drop")
        seg_utime = seg_utime.at[fresh, p2].set(utime_v, mode="drop")
        seg_valid = seg_valid.at[fresh, p2].set(True, mode="drop")
        dst2 = jnp.where(in_second, lba_v, n)
        loc_seg = loc_seg.at[dst2].set(fresh, mode="drop")
        loc_off = loc_off.at[dst2].set(ranks - room, mode="drop")

        took1 = jnp.minimum(k, room)
        took2 = k - took1
        seg_n = seg_n.at[sid].add(took1)
        seg_nvalid = seg_nvalid.at[sid].add(took1)
        seg_n = seg_n.at[fresh].add(took2)
        seg_nvalid = seg_nvalid.at[fresh].add(took2)
        class_gc = class_gc.at[cls].add(k)

        sealed_now = cls_active & (seg_n[sid] >= s)
        seg_state = seg_state.at[sid].set(jnp.where(sealed_now, 2, seg_state[sid]))
        seg_stime = seg_stime.at[sid].set(jnp.where(sealed_now, st["t"], seg_stime[sid]))
        promote = sealed_now
        seg_state = seg_state.at[fresh].set(jnp.where(promote, 1, seg_state[fresh]))
        seg_cls = seg_cls.at[fresh].set(jnp.where(promote, cls, seg_cls[fresh]))
        seg_ctime = seg_ctime.at[fresh].set(jnp.where(promote, st["t"], seg_ctime[fresh]))
        open_sid = open_sid.at[cls].set(jnp.where(promote, fresh, sid))
        used_pad = (fresh == cfg.pad_row) & ((took2 > 0) | promote)
        overflow = overflow + used_pad.astype(jnp.int32)

    seg_n = seg_n.at[cfg.pad_row].min(s)
    seg_state = seg_state.at[victim].set(
        jnp.where(victim == cfg.pad_row, 3, 0))
    seg_valid = seg_valid.at[victim].set(False)
    seg_n = seg_n.at[victim].set(0)
    seg_nvalid = seg_nvalid.at[victim].set(0)

    return dict(
        st,
        seg_lba=seg_lba, seg_utime=seg_utime, seg_valid=seg_valid,
        seg_n=seg_n, seg_nvalid=seg_nvalid, seg_cls=seg_cls,
        seg_state=seg_state, seg_ctime=seg_ctime, seg_stime=seg_stime,
        open_sid=open_sid, loc_seg=loc_seg, loc_off=loc_off,
        total_occ=st["total_occ"] - victim_n + k_total,
        gc_writes=st["gc_writes"] + k_total,
        reclaimed=st["reclaimed"] + 1,
        overflow=overflow,
        class_gc=class_gc,
        **_gc_time_debt(cfg, st, k_total),
    )


def _maybe_gc_legacy(cfg: JaxSimConfig, st):
    # victim selection runs once per iteration and is carried into the body:
    # its -1 sentinel gates the loop and names the victim — which also means
    # the argmax is paid at loop entry on every user write, GC or not.
    def cond(carry):
        st, i, victim = carry
        return (_gp(st) > st["p_gp"]) & (victim >= 0) \
            & (i < cfg.max_gc_per_step)

    def body(carry):
        st, i, victim = carry
        st = _gc_once_legacy(cfg, st, victim)
        return st, i + 1, _select_victim(cfg, st)

    st, _, _ = jax.lax.while_loop(
        cond, body, (st, jnp.int32(0), _select_victim(cfg, st)))
    return st


# -- per-user-write step -------------------------------------------------------

def _user_write(cfg: JaxSimConfig, st, lba, nxt):
    s, C, n = cfg.segment_size, cfg.n_class_slots, cfg.n_lbas
    t = st["t"]

    # invalidate predecessor (no-op for a fresh LBA: loc_seg = -1 drops;
    # the drop sentinel is n_rows, past even the sacrificial pad row)
    old_sid = st["loc_seg"][lba]
    old_off = st["loc_off"][lba]
    had_old = old_sid >= 0
    drop_sid = jnp.where(had_old, old_sid, cfg.n_rows)
    seg_valid = st["seg_valid"].at[drop_sid, old_off].set(False, mode="drop")
    seg_nvalid = st["seg_nvalid"].at[drop_sid].add(-1, mode="drop")
    v = t - st["last_uw"][lba]  # huge for fresh LBAs => "infinite lifespan"

    # user writes classify one block at a time — a Pallas call would pad the
    # single element to a full (8, 128) tile every scan step, so the scalar
    # jnp dispatch serves both modes (bit-identical to the kernel; the
    # segment-wide GC batch in _gc_once is where the kernel earns its tile)
    cls, st = _user_class_dispatch(cfg, st, lba, v, nxt)
    sid = st["open_sid"][cls]
    off = st["seg_n"][sid]
    # mode="drop": off can reach s only on the over-capacity pad row
    seg_lba = st["seg_lba"].at[sid, off].set(lba, mode="drop")
    seg_utime = st["seg_utime"].at[sid, off].set(t, mode="drop")
    seg_valid = seg_valid.at[sid, off].set(True, mode="drop")
    seg_n = st["seg_n"].at[sid].add(1)
    seg_n = seg_n.at[cfg.pad_row].min(s)
    seg_nvalid = seg_nvalid.at[sid].add(1)
    loc_seg = st["loc_seg"].at[lba].set(sid)
    loc_off = st["loc_off"].at[lba].set(off)
    last_uw = st["last_uw"].at[lba].set(t)

    # seal-if-full, promote a free segment to open
    fresh = _alloc_free_ids(cfg, st, 1)[0]
    sealed_now = seg_n[sid] >= s
    seg_state = st["seg_state"].at[sid].set(jnp.where(sealed_now, 2, st["seg_state"][sid]))
    seg_stime = st["seg_stime"].at[sid].set(jnp.where(sealed_now, t, st["seg_stime"][sid]))
    seg_state = seg_state.at[fresh].set(jnp.where(sealed_now, 1, seg_state[fresh]))
    seg_cls_arr = st["seg_cls"].at[fresh].set(jnp.where(sealed_now, cls, st["seg_cls"][fresh]))
    seg_ctime = st["seg_ctime"].at[fresh].set(jnp.where(sealed_now, t, st["seg_ctime"][fresh]))
    open_sid = st["open_sid"].at[cls].set(jnp.where(sealed_now, fresh, sid))

    # recent-write density EWMA (idle_window's defer signal): updated on
    # every real user write regardless of cfg.timing — pad steps are masked
    # no-ops, so fleet replays stay bit-identical to single-volume runs
    a = jnp.float32(1.0 / cfg.density_window)
    lat = {"lat_dens": st["lat_dens"] * (1.0 - a) + a}
    if cfg.timing:
        # closed-loop service model: this write arrives when the previous
        # one completed (lat_now), waits for any charged GC work still
        # occupying the device (lat_busy), then takes write_cost to serve
        wc = jnp.float32(cfg.write_cost)
        arrive = st["lat_now"]
        latency = jnp.maximum(st["lat_busy"] - arrive, 0.0) + wc
        bucket = jnp.clip(
            jnp.floor(LAT_BUCKETS_PER_OCTAVE * jnp.log2(latency / wc)),
            0, cfg.lat_buckets - 1).astype(jnp.int32)
        lat.update(
            lat_now=arrive + latency,
            lat_sum=st["lat_sum"] + latency,
            lat_max=jnp.maximum(st["lat_max"], latency),
            lat_hist=st["lat_hist"].at[bucket].add(1),
        )

    st = dict(
        st,
        seg_lba=seg_lba, seg_utime=seg_utime, seg_valid=seg_valid,
        seg_n=seg_n, seg_nvalid=seg_nvalid, seg_cls=seg_cls_arr,
        seg_state=seg_state, seg_ctime=seg_ctime, seg_stime=seg_stime,
        open_sid=open_sid, loc_seg=loc_seg, loc_off=loc_off, last_uw=last_uw,
        t=t + 1,
        total_occ=st["total_occ"] + 1,
        total_valid=st["total_valid"] - had_old.astype(jnp.int32) + 1,
        user_writes=st["user_writes"] + 1,
        overflow=st["overflow"]
        + (sealed_now & (fresh == cfg.pad_row)).astype(jnp.int32),
        class_user=st["class_user"].at[cls].add(1),
        **lat,
    )
    return st


def _user_step(cfg: JaxSimConfig, st, lba, nxt):
    """One user write followed by the GC trigger loop and (with the timing
    model on) the end-of-tick GC time charge — the single-volume scan step;
    fleet mode runs the write vmapped, GC as a fleet tick, and the same
    charge vmapped after it, so the per-volume op sequence is identical."""
    st = _user_write(cfg, st, lba, nxt)
    st = _maybe_gc_legacy(cfg, st) if cfg.gc_engine == "legacy" \
        else _maybe_gc(cfg, st)
    return _charge_gc(cfg, st)


# -- BIT annotations (future-knowledge schemes) -------------------------------

def fk_annotations(trace) -> np.ndarray:
    """Per-request BIT annotation for future-knowledge schemes: the index of
    the next write to the same LBA, clipped to the int32 ``NOBIT`` sentinel
    when there is none. Threaded through the scan alongside the LBA stream
    (`simulator.annotate_next_write` is the host-side producer)."""
    from .simulator import annotate_next_write
    trace = np.asarray(trace, dtype=np.int64)
    nxt = annotate_next_write(trace, 0)
    return np.minimum(nxt, NOBIT).astype(np.int32)


def _policy_scheme_id(cfg: JaxSimConfig, policy: dict | None) -> int:
    if policy is None:
        return _scheme_id_or_raise(cfg.scheme)
    sid = int(np.asarray(policy["p_scheme"]))
    if cfg.scheme_group is not None \
            and SCHEME_NAMES[sid] not in cfg.scheme_group:
        raise ValueError(f"policy scheme {SCHEME_NAMES[sid]!r} is outside "
                         f"this config's dispatch group {cfg.scheme_group}")
    return sid


def _single_annotations(trace: np.ndarray, cfg: JaxSimConfig,
                        policy: dict | None) -> np.ndarray | None:
    if SCHEME_REQUIRES_FUTURE[_policy_scheme_id(cfg, policy)]:
        return fk_annotations(trace)
    return None


def fleet_annotations(padded: np.ndarray, scheme_ids) -> np.ndarray | None:
    """(V, T) BIT annotations for a (possibly padded) fleet: rows whose
    scheme needs future knowledge are annotated per volume (pad entries are
    -1, never a real LBA, so real requests' links are unaffected and pad
    steps' values are discarded by the mask); all other rows are ``NOBIT``.
    Returns None when *no* volume needs future knowledge — callers then
    substitute a device-side fill (:func:`coerce_fleet_annotations`) and
    skip materializing/transferring a trace-sized host matrix."""
    need = [bool(SCHEME_REQUIRES_FUTURE[int(sid)])
            for sid in np.asarray(scheme_ids)]
    if not any(need):
        return None
    out = np.full(padded.shape, NOBIT, dtype=np.int32)
    for i, row_needs in enumerate(need):
        if row_needs:
            out[i] = fk_annotations(padded[i])
    return out


def coerce_fleet_annotations(nxts, shape, device=None) -> jnp.ndarray:
    """Device array for the scan's annotation stream, placed on ``device``
    (a device or sharding; None = default); NOBIT fill for None."""
    if nxts is None:
        return jnp.full(shape, NOBIT, jnp.int32, device=device)
    return jnp.asarray(nxts, jnp.int32, device=device)


@functools.partial(jax.jit, static_argnums=0)
def _run(cfg: JaxSimConfig, trace: jnp.ndarray, policy: dict | None = None,
         nxt: jnp.ndarray | None = None) -> dict:
    st = init_state(cfg, policy)
    if nxt is None:
        nxt = jnp.full(trace.shape, NOBIT, jnp.int32)

    def step(st, x):
        lba, nx = x
        return _user_step(cfg, st, lba, nx), None

    st, _ = jax.lax.scan(step, st, (trace, jnp.asarray(nxt, jnp.int32)))
    return st


def hist_quantile(hist, q: float, write_cost: float = 1.0) -> float:
    """q-quantile latency from a quarter-octave histogram (lower bucket
    edge, so an all-bucket-0 histogram reports exactly ``write_cost``)."""
    hist = np.asarray(hist)
    total = int(hist.sum())
    if total == 0:
        return 0.0
    target = int(np.ceil(q * total))
    idx = int(np.searchsorted(np.cumsum(hist), target))
    return float(write_cost * 2.0 ** (idx / LAT_BUCKETS_PER_OCTAVE))


def latency_summary(cfg: JaxSimConfig, st: dict) -> dict:
    """Foreground-latency stats from a (host-side) final volume state."""
    user = int(st["user_writes"])
    hist = np.asarray(st["lat_hist"])
    return {
        "p50": hist_quantile(hist, 0.50, cfg.write_cost),
        "p99": hist_quantile(hist, 0.99, cfg.write_cost),
        "max": float(st["lat_max"]),
        "mean": float(st["lat_sum"]) / max(user, 1),
        "total": float(st["lat_sum"]),
        "gc_time_charged": float(st["lat_charged"]),
        "gc_debt": float(st["lat_debt"]),
        "write_cost": cfg.write_cost,
        "hist": hist.tolist(),
    }


def _summary(cfg: JaxSimConfig, st: dict) -> dict:
    """Summary-stats dict from a (host-side) final state of one volume."""
    user = int(st["user_writes"])
    gc_writes = int(st["gc_writes"])
    overflow = int(st["overflow"])
    out = {
        "scheme": SCHEME_NAMES[int(st["p_scheme"])],
        "selector": SELECTOR_NAMES[int(st["p_selector"])],
        "gp_threshold": float(st["p_gp"]),
        "gcsched": GCSCHED_NAMES[int(st["p_gcsched"])],
        "user_writes": user,
        "gc_writes": gc_writes,
        "wa": (user + gc_writes) / user if user else 1.0,
        "reclaimed": int(st["reclaimed"]),
        "overflow": overflow,
        "free_exhausted": overflow,
        "degraded": overflow > 0,   # pad-row-aliased accounting: WA et al.
        #                             are logical, not physical, past here
        "ell": float(st["ell"]),
        "class_user_writes": np.asarray(st["class_user"]).tolist(),
        "class_gc_writes": np.asarray(st["class_gc"]).tolist(),
    }
    if cfg.timing:
        out["latency"] = latency_summary(cfg, st)
    return out


def simulate_jax(trace: np.ndarray, cfg: JaxSimConfig,
                 policy: dict | None = None) -> dict:
    """Replay ``trace`` on the XLA state machine; returns summary stats.

    ``policy`` optionally overrides the config's placement knobs with traced
    scalars (see :func:`default_policy`) — same compiled program for every
    policy, used by the differential harness to pit one static config shape
    against many policies without recompiling. Future-knowledge schemes get
    their BIT annotations computed here (host-side) and threaded in."""
    trace_np = np.asarray(trace, dtype=np.int32)
    nxt = _single_annotations(trace_np, cfg, policy)
    st = jax.block_until_ready(
        _run(cfg, jnp.asarray(trace_np), policy,
             None if nxt is None else jnp.asarray(nxt)))
    return _summary(cfg, jax.device_get(st))


# -- fleet mode: vmap over a leading volume axis ------------------------------

def pad_fleet(traces) -> np.ndarray:
    """Stack heterogeneous-length 1-D traces into a (V, T_max) int32 matrix
    padded with -1 (replayed as masked no-op steps)."""
    traces = [np.asarray(t, dtype=np.int32) for t in traces]
    T = max((len(t) for t in traces), default=0)
    out = np.full((len(traces), T), -1, dtype=np.int32)
    for i, t in enumerate(traces):
        out[i, : len(t)] = t
    return out


def _masked_step(cfg: JaxSimConfig, st, lba, nxt):
    """One full user step (write + GC), or a state-preserving no-op for pad
    entries (-1) — the legacy fleet engine's per-volume step."""
    active = lba >= 0
    new = _user_step(cfg, st, jnp.maximum(lba, 0), nxt)
    return jax.tree_util.tree_map(lambda a, b: jnp.where(active, a, b), new, st)


def _masked_write(cfg: JaxSimConfig, st, lba, nxt):
    """One user write (GC deferred to the fleet tick), or a no-op for pads."""
    active = lba >= 0
    new = _user_write(cfg, st, jnp.maximum(lba, 0), nxt)
    return jax.tree_util.tree_map(lambda a, b: jnp.where(active, a, b), new, st)


def broadcast_policies(cfg: JaxSimConfig, n_volumes: int) -> dict:
    """Uniform (V,)-shaped policy arrays replicating ``cfg``'s knobs."""
    pol = default_policy(cfg)
    return {k: jnp.broadcast_to(v, (n_volumes,)) for k, v in pol.items()}


def fleet_step(cfg: JaxSimConfig, masked: bool, st: dict, lbas: jnp.ndarray,
               nxs: jnp.ndarray) -> dict:
    """One synchronized fleet tick over a batched (V-leading) state: the
    scan body of :func:`fleet_body`, factored out so `repro.analysis` can
    trace the tick boundary in isolation (the SA5xx volume-isolation lints
    compare this function's in/out state specs and provenance).

    Tick engine (default): vmap the GC-free user write, then one fleet-level
    :func:`fleet_gc_tick`. Legacy engine: vmap the full per-volume step
    (write + `_maybe_gc_legacy`). ``masked`` is static: uniform-length
    fleets (no -1 padding anywhere) skip the per-step state select."""
    if cfg.gc_engine == "legacy":
        inner = _masked_step if masked else _user_step
        return jax.vmap(functools.partial(inner, cfg))(st, lbas, nxs)

    write = _masked_write if masked else _user_write
    st = jax.vmap(functools.partial(write, cfg))(st, lbas, nxs)
    st = fleet_gc_tick(cfg, st, (lbas >= 0) if masked else None)
    if cfg.timing:
        new = jax.vmap(functools.partial(_charge_gc, cfg))(st)
        if masked:
            # pad steps stay exact no-ops: a finished volume must not
            # keep draining rate_limited debt the single run wouldn't
            active = lbas >= 0
            new = jax.tree_util.tree_map(
                lambda a, b: jnp.where(
                    active.reshape(active.shape
                                   + (1,) * (a.ndim - 1)), a, b),
                new, st)
        st = new
    return st


def fleet_body(cfg: JaxSimConfig, masked: bool, traces: jnp.ndarray,
               nxts: jnp.ndarray, policies: dict) -> dict:
    """The (un-jitted) fleet replay: vmapped scan over a leading volume axis.

    ``policies`` is a dict of (V,)-shaped traced policy arrays (see
    :func:`default_policy` for the keys) — each volume runs its own scheme /
    selector / GP threshold / nc window. ``nxts`` is the (V, T) BIT
    annotation matrix (see :func:`fleet_annotations`). Exposed un-jitted so
    `core/fleetshard.py` can wrap it in `shard_map` over the fleet axis.
    Each scan step is one :func:`fleet_step`."""
    st = jax.vmap(lambda pol: init_state(cfg, pol))(policies)

    def step(st, x):
        lbas, nxs = x
        return fleet_step(cfg, masked, st, lbas, nxs), None

    st, _ = jax.lax.scan(step, st, (traces.T, nxts.T))
    return st


@functools.partial(jax.jit, static_argnums=(0, 3))
def _run_fleet(cfg: JaxSimConfig, traces: jnp.ndarray, nxts: jnp.ndarray,
               masked: bool, policies: dict) -> dict:
    return fleet_body(cfg, masked, traces, nxts, policies)


def summarize_fleet(cfg: JaxSimConfig, st: dict, n_volumes: int) -> dict:
    """Host-side per-volume summaries + fleet aggregate from a batched state."""
    st = jax.device_get(st)
    vols = [_summary(cfg, jax.tree_util.tree_map(lambda x: x[i], st))
            for i in range(n_volumes)]
    user = sum(r["user_writes"] for r in vols)
    gc = sum(r["gc_writes"] for r in vols)
    overflow = sum(r["overflow"] for r in vols)
    fleet = {
        "n_volumes": n_volumes,
        "user_writes": user,
        "gc_writes": gc,
        "wa": (user + gc) / max(user, 1),
        "overflow": overflow,
        "free_exhausted": overflow,
        "degraded": overflow > 0,
        "per_volume_wa": [r["wa"] for r in vols],
    }
    if cfg.timing:
        # fleet-level quantiles come from the merged histogram, not from
        # averaging per-volume quantiles (which has no meaning for p99)
        hist = np.asarray(st["lat_hist"])[:n_volumes].sum(axis=0)
        fleet["latency"] = {
            "p50": hist_quantile(hist, 0.50, cfg.write_cost),
            "p99": hist_quantile(hist, 0.99, cfg.write_cost),
            "max": max((r["latency"]["max"] for r in vols), default=0.0),
            "mean": sum(r["latency"]["total"] for r in vols) / max(user, 1),
            "gc_debt": sum(r["latency"]["gc_debt"] for r in vols),
        }
    return {"volumes": vols, "fleet": fleet}


def coerce_fleet(traces) -> np.ndarray:
    """Normalize a list of 1-D traces / (V, T) matrix to padded int32."""
    padded = np.asarray(traces, dtype=np.int32) if isinstance(traces, np.ndarray) \
        else pad_fleet(traces)
    if padded.ndim != 2:
        raise ValueError("traces must be a list of 1-D traces or a (V, T) matrix")
    return padded


def simulate_fleet(traces, cfg: JaxSimConfig, policies: dict | None = None) -> dict:
    """Replay N independent volumes in one compiled program.

    ``traces``: a list of 1-D LBA arrays (heterogeneous lengths allowed) or a
    pre-padded (V, T) int32 matrix with -1 padding. ``policies`` optionally
    supplies (V,)-shaped per-volume policy arrays (heterogeneous configs; see
    `core/fleetshard.py` for the encoder and the device-sharded runner) —
    when omitted every volume runs ``cfg``'s knobs. Either way per-volume
    results are bit-identical to running each trace through
    :func:`simulate_jax` alone with the matching policy.

    Returns ``{"volumes": [per-volume summary, ...], "fleet": aggregate}``.
    """
    padded = coerce_fleet(traces)
    V = padded.shape[0]
    masked = bool((padded < 0).any())
    if policies is None:
        policies = broadcast_policies(cfg, V)
    policies = {k: jnp.asarray(v) for k, v in policies.items()}
    nxts = fleet_annotations(padded, policies["p_scheme"])
    st = jax.block_until_ready(
        _run_fleet(cfg, jnp.asarray(padded),
                   coerce_fleet_annotations(nxts, padded.shape), masked,
                   policies))
    return summarize_fleet(cfg, st, V)
