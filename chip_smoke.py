"""Chip smoke test: the fleet replay on a TPU at deployment volume size.

Run from the repository root on a machine with a TPU:

    python chip_smoke.py [--seed 0]     # phases (a)-(e), one chip
    python chip_smoke.py --chips 4      # the sharded 64-volume fleet only

Everything runs in one process, and the first failed check exits nonzero.
Phases of the one-chip run:

(a) device: the default device must be a TPU (no CPU fallback);
(b) a 16-volume fleet of 1 GiB volumes (2^18 4 KiB blocks, 128-block
    segments, GP threshold 0.15) under zipf_mixture traffic, 2^16 updates per
    volume after the sequential fill, replayed by
    ``fleetshard.simulate_fleet_hetero`` (scheme-grouped, jnp engine) under
    four schemes × two selectors, two volumes each; every volume is checked
    against the numpy reference simulator (``user_writes`` exact, WA within
    the differential suite's bands), and the fleet must run GC without
    exhausting its free pool;
(c) the same fleet on the Pallas kernel path, bitwise equal to (b) leaf by
    leaf in the final state;
(d) one 10 GiB volume (``PAPER_N`` blocks, SepBIT, 2^19 updates) through the
    same entry point as a fleet of one, checked against numpy as in (b), and
    required to run GC;
(e) volumes 0-3 of (b) replayed by the same engine on the host CPU backend;
    the count of counters that differ bitwise from the TPU is printed, not
    gated (the numpy check in (b) decides). It runs on a background thread
    while (b)-(d) run, so their printed seconds are taken with the host
    busy.

``--chips 4`` runs only the sharded path: (b)'s fleet tiled to 64 volumes,
sharded over ``fleet_mesh()`` (16 volumes per chip), compared bitwise per
volume with the same fleet replayed unsharded (each scheme group on one
chip, the groups at once on separate chips), with each chip's peak memory
printed.

The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import inspect
import json
import sys
import threading
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core.analysis import BLOCKS_PER_GIB, PAPER_N  # noqa: E402
from repro.core.fleetshard import (  # noqa: E402
    encode_policies,
    fleet_mesh,
    hetero_config,
    policy_grid,
    scheme_groups,
    simulate_fleet_hetero,
)
from repro.core.jaxsim import JaxSimConfig  # noqa: E402
from repro.core.placement import registry  # noqa: E402
from repro.core.simulator import simulate  # noqa: E402
from repro.core.tracegen import make_fleet  # noqa: E402

SCHEMES = ("sepbit", "nosep", "fk", "warcip")
SELECTORS = ("greedy", "cost_benefit")
VOLUMES_PER_CELL = 2
SEGMENT = 128
GP = 0.15
FLEET_UPDATES = 2 ** 16
# without GC, GP after the fill is updates / (n_lbas + updates): 2^17 updates
# on PAPER_N blocks stop at GP 0.048, under the 0.15 trigger; 2^19 reach
# 0.167, so GC runs over the volume's 48,222 segments for the last ~60k
LARGE_UPDATES = 2 ** 19
CROSS_BACKEND_VOLUMES = 4
# numpy-vs-JAX WA bands of tests/test_differential.py: argmax ties break
# differently between the backends, and the stateful temperature ladders
# compound that through their per-LBA tables
WA_BAND = {"greedy": 0.08, "cost_benefit": 0.03}
LADDER_BAND = 0.10
LADDERS = ("dac", "ml", "sfs", "eti", "mq", "sfr", "fadac", "warcip")
COUNTERS = ("t", "user_writes", "gc_writes", "reclaimed", "overflow",
            "total_occ", "total_valid", "ell", "class_user", "class_gc")
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class SmokeFailure(Exception):
    """A failed check; the message names the phase."""


def check(ok: bool, phase: str, what: str) -> None:
    if not ok:
        raise SmokeFailure(f"({phase}) {what}")


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, per thread, from
    its own monitoring events (zero for programs already compiled). Nested
    jits emit nested events, so the clock measures the union of the
    events' time spans, not the sum of their durations."""

    def __init__(self):
        self._spans: dict[int, list[tuple[float, float]]] = {}
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event in COMPILE_EVENTS:
            end = time.perf_counter()
            self._spans.setdefault(threading.get_ident(), []).append(
                (end - duration, end))

    def seconds(self, t0: float, t1: float) -> float:
        """Compile seconds on the calling thread within [t0, t1]."""
        total, reach = 0.0, t0
        for a, b in sorted(self._spans.get(threading.get_ident(), [])):
            a, b = max(a, reach), min(b, t1)
            if b > a:
                total, reach = total + b - a, b
        return total


class Background:
    """Run ``fn`` on a daemon thread (a failed smoke test exits without
    waiting for it); :meth:`result` joins and re-raises its exception."""

    def __init__(self, fn):
        self._out = self._exc = None
        self._thread = threading.Thread(target=self._run, args=(fn,),
                                        daemon=True)
        self._thread.start()

    def _run(self, fn):
        try:
            self._out = fn()
        except BaseException as e:  # re-raised on the main thread
            self._exc = e

    def result(self):
        self._thread.join()
        if self._exc is not None:
            raise self._exc
        return self._out


def timed(clock: CompileClock, fn):
    """Run ``fn``; return (result, wall seconds, compile seconds within)."""
    t0 = time.perf_counter()
    out = fn()
    t1 = time.perf_counter()
    return out, t1 - t0, clock.seconds(t0, t1)


def peak_bytes(device) -> int:
    return int(device.memory_stats()["peak_bytes_in_use"])


def state_bytes(st: dict) -> int:
    return sum(np.asarray(x).nbytes for x in st.values())


def bitwise_equal(a, b) -> bool:
    a, b = np.atleast_1d(a), np.atleast_1d(b)
    return (a.dtype == b.dtype and a.shape == b.shape
            and np.array_equal(a.view(np.uint8), b.view(np.uint8)))


def differing_leaves(st_a: dict, st_b: dict, rows=slice(None)) -> list[str]:
    return [k for k in st_a if not bitwise_equal(st_a[k][rows], st_b[k][rows])]


def numpy_reference(trace, scheme: str, selector: str, n_lbas: int,
                    nc_window: int):
    """The numpy event loop on the same trace, with the fleet volume's
    nc_window for schemes that take one."""
    params = inspect.signature(registry.get(scheme).numpy_cls).parameters
    takes_ncw = "nc_window" in params or any(
        p.kind is p.VAR_KEYWORD for p in params.values())
    kwargs = {"placement_kwargs": {"nc_window": nc_window}} if takes_ncw else {}
    return simulate(trace, scheme, segment_size=SEGMENT, n_lbas=n_lbas,
                    selector=selector, gp_threshold=GP, **kwargs)


def check_against_numpy(phase: str, traces, policy, res, n_lbas: int) -> dict:
    """Per-volume numpy gate; returns {(scheme, selector): [(jax, numpy)]}."""
    by_cell: dict = {}
    for i, vol in enumerate(res["volumes"]):
        scheme, selector, _ = policy.describe(i)
        ref = numpy_reference(traces[i], scheme, selector, n_lbas,
                              int(policy.nc_window[i]))
        check(vol["user_writes"] == ref.user_writes, phase,
              f"volume {i} ({scheme}/{selector}): user_writes "
              f"{vol['user_writes']} != numpy {ref.user_writes}")
        band = WA_BAND[selector]
        if scheme in LADDERS:
            band = max(band, LADDER_BAND)
        check(abs(vol["wa"] - ref.wa) <= band * ref.wa, phase,
              f"volume {i} ({scheme}/{selector}): WA {vol['wa']!r} vs numpy "
              f"{ref.wa!r} outside the {band} band")
        by_cell.setdefault((scheme, selector), []).append((vol["wa"], ref.wa))
    return by_cell


def sub_policy(policy, rows):
    """The policy of the volumes ``rows``, as a fleet of their own."""
    return encode_policies(
        len(rows), schemes=policy.scheme_id[rows],
        selectors=policy.selector_id[rows],
        gp_thresholds=policy.gp_threshold[rows],
        nc_windows=policy.nc_window[rows])


def smoke_policy(copies: int = 1):
    """(b)'s policy: scheme × selector cells, two volumes each, cell-major,
    tiled ``copies`` times."""
    policy, _ = policy_grid(SCHEMES, SELECTORS, [GP],
                            volumes_per_cell=VOLUMES_PER_CELL)
    if copies == 1:
        return policy
    return encode_policies(
        policy.n_volumes * copies,
        schemes=np.tile(policy.scheme_id, copies),
        selectors=np.tile(policy.selector_id, copies),
        gp_thresholds=np.tile(policy.gp_threshold, copies),
        nc_windows=np.tile(policy.nc_window, copies))


def fleet_traces(seed: int) -> np.ndarray:
    n_volumes = len(SCHEMES) * len(SELECTORS) * VOLUMES_PER_CELL
    return np.asarray(make_fleet("zipf_mixture", n_volumes, BLOCKS_PER_GIB,
                                 FLEET_UPDATES, seed=seed), np.int32)


def phase_device(chips: int):
    devices = jax.devices()
    dev = devices[0]
    check(dev.platform == "tpu", "a",
          f"default device platform is {dev.platform!r}; this smoke test "
          f"needs a TPU and has no CPU fallback")
    check(len(devices) >= chips, "a",
          f"--chips {chips} needs {chips} devices, found {len(devices)}")
    cache = enable_compile_cache()
    print(f"(a) device_kind={dev.device_kind} devices={len(devices)} "
          f"jax={jax.__version__} compile_cache={cache}", flush=True)
    return devices


def phase_fleet(clock, dev, traces, policy, cfg):
    (res, st), wall, comp = timed(clock, lambda: simulate_fleet_hetero(
        traces, cfg, policy, shard=False, return_state=True))
    print(f"(b) fleet {policy.n_volumes} volumes x {cfg.n_lbas} LBAs, "
          f"{traces.shape[1]} writes each, {res['fleet']['n_scheme_groups']} "
          f"scheme groups: {wall:.3f} s wall, of which compile {comp:.3f} s, "
          f"replay and host summary {wall - comp:.3f} s (results blocked "
          f"until ready; (e) busy on the host meanwhile); final state "
          f"{state_bytes(st)} bytes; "
          f"peak_bytes_in_use {peak_bytes(dev)}", flush=True)
    f = res["fleet"]
    check(f["gc_writes"] > 0, "b", "the fleet ran no GC")
    check(f["overflow"] == 0, "b", f"free pool exhausted ({f['overflow']})")
    t0 = time.perf_counter()
    cells = check_against_numpy("b", traces, policy, res, cfg.n_lbas)
    print(f"(b) numpy reference: {policy.n_volumes} volumes in "
          f"{time.perf_counter() - t0:.1f} s; fleet user_writes "
          f"{f['user_writes']} gc_writes {f['gc_writes']} WA {f['wa']!r}",
          flush=True)
    for (scheme, selector), pairs in cells.items():
        print(f"(b) {scheme:>7s}/{selector:<12s} WA jax "
              f"{[w for w, _ in pairs]} numpy {[w for _, w in pairs]}",
              flush=True)
    return st


def phase_kernels(clock, traces, policy, cfg, st_jnp):
    kcfg = dataclasses.replace(cfg, use_kernels=True)
    (_, st), wall, comp = timed(clock, lambda: simulate_fleet_hetero(
        traces, kcfg, policy, shard=False, return_state=True))
    diff = differing_leaves(st, st_jnp)
    print(f"(c) kernel path: {wall:.3f} s (compile {comp:.3f} s); "
          f"{len(diff)} of {len(st)} state leaves differ from the jnp path "
          f"{diff}", flush=True)
    check(not diff, "c", f"kernel path differs from jnp in {diff}")


def phase_large_volume(clock, dev, seed):
    cfg = JaxSimConfig(n_lbas=PAPER_N, segment_size=SEGMENT, gp_threshold=GP)
    traces = np.asarray(make_fleet("zipf_mixture", 1, PAPER_N, LARGE_UPDATES,
                                   seed=seed), np.int32)
    policy = encode_policies(1, schemes="sepbit", selectors="cost_benefit",
                             gp_thresholds=GP)
    res, wall, comp = timed(clock, lambda: simulate_fleet_hetero(
        traces, cfg, policy, shard=False))
    vol = res["volumes"][0]
    print(f"(d) one volume x {PAPER_N} LBAs, {traces.shape[1]} writes: "
          f"{wall:.3f} s (compile {comp:.3f} s); peak_bytes_in_use "
          f"{peak_bytes(dev)}; user_writes {vol['user_writes']} gc_writes "
          f"{vol['gc_writes']} WA {vol['wa']!r} overflow {vol['overflow']}",
          flush=True)
    check(vol["gc_writes"] > 0, "d", "the volume ran no GC")
    check(vol["overflow"] == 0, "d", f"free pool exhausted ({vol['overflow']})")
    check_against_numpy("d", traces, policy, res, PAPER_N)


def cpu_backend_replay(traces, policy, cfg):
    """Volumes 0-3 of (b) replayed by the same engine on the host CPU
    backend, under the full fleet's static shapes. Returns (final state,
    wall seconds)."""
    rows = np.arange(CROSS_BACKEND_VOLUMES)
    pinned = hetero_config(cfg, policy)
    t0 = time.perf_counter()
    with jax.default_device(jax.devices("cpu")[0]):
        _, st = simulate_fleet_hetero(traces[rows], pinned,
                                      sub_policy(policy, rows), shard=False,
                                      return_state=True)
    return st, time.perf_counter() - t0


def phase_cross_backend(cpu_run: Background, st_tpu):
    """(e): information only — TPU and CPU float division may break argmax
    ties differently; the numpy gate in (b) decides."""
    st, wall = cpu_run.result()
    n = CROSS_BACKEND_VOLUMES
    differ = sum(not bitwise_equal(st[k][i], st_tpu[k][i])
                 for i in range(n) for k in COUNTERS)
    leaves = differing_leaves(st, st_tpu, slice(0, n))
    print(f"(e) cpu backend, volumes 0-{n - 1}: {wall:.3f} s on a background "
          f"thread (compile included); {differ} of {n * len(COUNTERS)} "
          f"counters differ bitwise from the TPU; state leaves differing: "
          f"{leaves}", flush=True)


def phase_sharded(clock, devices, traces16, cfg, chips):
    mesh = fleet_mesh()
    check(mesh is not None and mesh.size == chips, "4",
          f"fleet_mesh() spans {None if mesh is None else mesh.size} devices")
    copies = 4
    traces = np.tile(traces16, (copies, 1))
    policy = smoke_policy(copies)
    (res_sh, st_sh), wall, comp = timed(clock, lambda: simulate_fleet_hetero(
        traces, cfg, policy, mesh=mesh, return_state=True))
    check(res_sh["fleet"]["n_devices"] == chips, "4", "fleet was not sharded")
    peaks = [peak_bytes(d) for d in devices[:chips]]
    print(f"(4) sharded: {policy.n_volumes} volumes over {chips} chips, "
          f"{wall:.3f} s (compile {comp:.3f} s); final state "
          f"{state_bytes(st_sh)} bytes; peak_bytes_in_use per device {peaks}",
          flush=True)
    check(res_sh["fleet"]["gc_writes"] > 0, "4", "the fleet ran no GC")
    check(res_sh["fleet"]["overflow"] == 0, "4", "free pool exhausted")
    # the unsharded replay runs each scheme group (the groups the fleet call
    # runs anyway) as a one-chip program under the whole fleet's static
    # shapes; the groups share nothing, so each gets its own chip and they
    # run at once
    pinned = hetero_config(cfg, policy)

    def one_chip(rows, device):
        def run():
            with jax.default_device(device):
                return timed(clock, lambda: simulate_fleet_hetero(
                    traces[rows], pinned, sub_policy(policy, rows),
                    shard=False, return_state=True)[1])
        return Background(run)

    groups = scheme_groups(policy)
    runs = [one_chip(rows, devices[k % chips])
            for k, (_, rows) in enumerate(groups)]
    for (name, rows), run in zip(groups, runs):
        st_1, wall1, comp1 = run.result()
        bad = [int(v) for j, v in enumerate(rows)
               if any(not bitwise_equal(st_1[k][j], st_sh[k][v])
                      for k in st_1)]
        print(f"(4) one chip, {name} ({len(rows)} volumes, concurrent with "
              f"the other groups): {wall1:.3f} s "
              f"(compile {comp1:.3f} s); volumes differing bitwise from the "
              f"sharded replay: {bad}", flush=True)
        check(not bad, "4", f"sharded replay differs from one chip on {bad}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="trace generator seed (default 0)")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 = run only the sharded 64-volume fleet and its "
                         "one-chip comparison")
    args = ap.parse_args()
    try:
        devices = phase_device(args.chips)
        clock = CompileClock()
        traces = fleet_traces(args.seed)
        cfg = JaxSimConfig(n_lbas=BLOCKS_PER_GIB, segment_size=SEGMENT,
                           gp_threshold=GP)
        if args.chips == 4:
            phase_sharded(clock, devices, traces, cfg, args.chips)
        else:
            policy = smoke_policy()
            # (e)'s host-CPU replay overlaps the TPU phases; it is read last
            cpu_run = Background(lambda: cpu_backend_replay(traces, policy,
                                                            cfg))
            st = phase_fleet(clock, devices[0], traces, policy, cfg)
            phase_kernels(clock, traces, policy, cfg, st)
            phase_large_volume(clock, devices[0], args.seed)
            phase_cross_backend(cpu_run, st)
    except SmokeFailure as e:
        print(f"FAIL {e}", file=sys.stderr)
        return 1
    dev = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
