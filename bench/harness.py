"""Drives one benchmark cell: set-up, the measured window, the check.

The system under test is the fleet replay engine (``repro.core.jaxsim``).
A replay of a whole trace from an empty volume does not fit a window at
deployment size, so the harness drives the engine's own pieces the way
``jaxsim.fleet_body`` does, from a carried state: ``init_state`` vmapped
over the fleet's policies, then chunks of ``chunk_steps`` scan steps of
``jaxsim.fleet_step``, each chunk one compiled program with the state
donated. Set-up replays the mix's set-up writes through that same program;
the window dispatches further chunks, keeping about ``QUEUE_S`` of device
work queued, until its time is up, and ends when the device has replayed
the last of them. ``summarize_fleet`` is left out of the window: it runs
once per replay whatever its length, and pulls the whole fleet state to
the host (9.5 GB at 47 x 10 GiB), so in a window of seconds it would stand
for far more than its share of a replay of minutes.

Everything that belongs to one configuration, traffic mix or per-layer
metric is read from its own file, found by the name in ``BENCHMARK.json``:
``configs/<name>.json`` (sizes, per-volume policies, and the names of its
annotation stream ``annotations/<name>.py`` and its plain reference
``references/<name>.py``), ``traffic/<name>.json`` (phases, each made by
``phases/<kind>.py``) and ``metrics/<name>.py``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import gzip
import json
import math
import tempfile
import time
from collections import deque
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from repro.core import jaxsim
from repro.core.fleetshard import encode_policies, hetero_config

import plugins
from check import compare, load_limits, verdict
from trace_reduce import breakdown, load_events, reduce_events
from trafficgen import Traffic

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
QUEUE_S = 4.0               # device work kept in flight: host stalls up to
#                             this long leave the device busy (stalls of
#                             1.5 s and 2.8 s were seen on a v5e host)
TRACE_WINDOW_S = 1.0        # a traced run traces this much, plus its drain
REFERENCE_VOLUMES = 2       # volumes the reference replays, drawn per seed
STATE_READ = ("loc_seg", "loc_off", "seg_lba", "seg_utime", "seg_valid",
              "user_writes", "gc_writes", "reclaimed", "overflow",
              "class_user", "class_gc")


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    per_layer: list[str]


def load_cell(workload: str, bench_file: Path = ROOT / "BENCHMARK.json"
              ) -> Cell:
    bench = json.loads(bench_file.read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    w = cells[workload]
    conf = next(c for c in bench["configs"] if c["name"] == w["config"])
    per_layer = [m["name"] for m in bench["per_layer"]
                 if workload in m.get("workloads", [workload])]
    return Cell(
        name=workload, chips=int(w["chips"]),
        config=json.loads((ROOT / conf["file"]).read_text()),
        traffic=json.loads((BENCH / "traffic" / f"{w['traffic']}.json")
                           .read_text()),
        per_layer=per_layer)


def check_device(chips: int):
    """The devices to run on; raises :class:`NoChip` without a TPU."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (default platform "
                     f"{devices[0].platform!r}); this benchmark has no "
                     f"fallback")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found "
                     f"{len(devices)}")
    return devices


def load_reader(name: str):
    return plugins.load("metrics", name).read


POLICY_KEYS = ("scheme", "selector", "gp_threshold", "nc_window")


def volume_config(config: dict, v: int) -> dict:
    """Volume ``v``'s configuration: a policy key given as a list (one
    value per volume) resolved to the volume's own value."""
    return {k: (val[v] if k in POLICY_KEYS and isinstance(val, list)
                else val) for k, val in config.items()}


# -- the system under test ----------------------------------------------------

@dataclasses.dataclass
class Program:
    cfg: object
    policies: dict
    n_volumes: int


def build_program(config: dict) -> Program:
    """The engine's static config and per-volume policies. ``scheme``,
    ``selector``, ``gp_threshold`` and ``nc_window`` are each one value for
    the fleet or a list of one per volume."""
    V = int(config["volumes"])
    vols = [volume_config(config, v) for v in range(V)]
    base = jaxsim.JaxSimConfig(
        n_lbas=config["n_lbas"], segment_size=config["segment_size"],
        gp_threshold=max(c["gp_threshold"] for c in vols),
        selector=vols[0]["selector"], scheme=vols[0]["scheme"],
        nc_window=vols[0]["nc_window"],
        max_gc_per_step=config["max_gc_per_write"],
        n_segments=config["n_segments"])
    policy = encode_policies(V, **{
        arg: [c[key] for c in vols] for arg, key in
        (("schemes", "scheme"), ("selectors", "selector"),
         ("gp_thresholds", "gp_threshold"), ("nc_windows", "nc_window"))})
    # the scheme-grouped program simulate_fleet_hetero runs for this fleet
    group = tuple(dict.fromkeys(c["scheme"] for c in vols))
    cfg = dataclasses.replace(hetero_config(base, policy), scheme_group=group)
    return Program(cfg, policy.as_state_arrays(), V)


def chunk_body(cfg, st, lbas, nxs):
    """``lbas.shape[0]`` fleet steps, each volume's write annotated by
    ``nxs``; returns the state and a token."""
    def step(st, x):
        return jaxsim.fleet_step(cfg, False, st, *x), None

    st, _ = jax.lax.scan(step, st, (lbas, nxs))
    return st, jnp.sum(st["user_writes"])


def init_fleet(cfg, policies):
    return jax.vmap(lambda p: jaxsim.init_state(cfg, p))(policies)


def compile_programs(prog: Program, k: int, device=None):
    """(init, chunk) compiled for the cell's shapes. ``device`` may be a
    sharding of a described chip, for a compile without one."""
    put = {} if device is None else {"sharding": device}
    pols = {key: jax.ShapeDtypeStruct(v.shape, v.dtype, **put)
            for key, v in prog.policies.items()}
    init = jax.jit(functools.partial(init_fleet, prog.cfg)).lower(
        pols).compile()
    spec = jax.eval_shape(functools.partial(init_fleet, prog.cfg), pols)
    spec = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, **put), spec)
    lbas = jax.ShapeDtypeStruct((k, prog.n_volumes), jnp.int32, **put)
    chunk = jax.jit(functools.partial(chunk_body, prog.cfg),
                    donate_argnums=0).lower(spec, lbas, lbas).compile()
    return init, chunk


# -- one run --------------------------------------------------------------------

class Feed:
    """Dispatches chunks with at most ``depth`` in flight; keeps every chunk
    fed (the reference replays them) and how late the host ran."""

    def __init__(self, chunk_fn, traffic: Traffic, notes, depth: int):
        self.chunk_fn, self.traffic, self.depth = chunk_fn, traffic, depth
        self.notes = notes
        self.annotate = None
        self.fed: list[np.ndarray] = []
        self.inflight: deque = deque()
        self.gen_s = 0.0
        self.starved = 0
        self.done_at: list[float] = []   # when each waited-for chunk ended

    def _span(self, name):
        return self.annotate(name) if self.annotate else \
            contextlib.nullcontext()

    def step(self, st):
        with self._span("generator"):
            t0 = time.perf_counter()
            lbas = self.traffic.chunk(len(self.fed))
            x = jax.device_put((lbas, self.notes(len(self.fed), lbas)))
            self.gen_s += time.perf_counter() - t0
        self.fed.append(lbas)
        if self.inflight and self.inflight[-1].is_ready():
            self.starved += 1
        with self._span("dispatch"):
            st, token = self.chunk_fn(st, *x)
        self.inflight.append(token)
        if len(self.inflight) > self.depth:
            with self._span("wait"):
                self.inflight.popleft().block_until_ready()
            self.done_at.append(time.perf_counter())
        return st

    def drain(self):
        while self.inflight:
            self.inflight.popleft().block_until_ready()


class CompileCount:
    """Backend compiles JAX reports, counted while ``on``."""

    def __init__(self):
        self.on, self.n = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event: str, duration: float, **_) -> None:
        if self.on and event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def chunk_intervals(done_at: list[float]) -> str:
    if len(done_at) < 2:
        return "n/a"
    gaps = 1e3 * np.diff(done_at)
    return "/".join(f"{q:.2f}" for q in
                    np.percentile(gaps, [10, 50, 90, 100]))


def counters(st) -> dict:
    got = jax.device_get({k: st[k] for k in
                          ("user_writes", "gc_writes", "reclaimed")})
    return {k: int(np.sum(v)) for k, v in got.items()}


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             started: float, chunk_fn=None,
             keep_trace: str | None = None) -> dict:
    """One run of ``cell``; returns the result line's fields. ``chunk_fn``
    replaces the compiled chunk (the fault tests break it on purpose);
    ``keep_trace`` names a file for the traced window's events."""

    t_start = time.perf_counter()
    prog = build_program(cell.config)
    traffic = Traffic(cell.traffic, cell.config["n_lbas"], prog.n_volumes,
                      seed)
    notes = plugins.load("annotations", cell.config["annotation"]).make(
        cell.config, traffic)
    init, chunk = compile_programs(prog, traffic.k)
    if chunk_fn is not None:
        chunk = functools.partial(chunk_fn, chunk)
    # warm the compiled chunk once on a throwaway fleet, and size the queue
    # from how long it took
    warm = jax.block_until_ready(init(prog.policies))
    t_programs = time.perf_counter()
    lbas = traffic.chunk(0)
    warm, _ = chunk(warm, *jax.device_put((lbas, notes(0, lbas))))
    jax.block_until_ready(warm)
    del warm
    t_warm = time.perf_counter()
    depth = min(256, max(2, math.ceil(QUEUE_S / (t_warm - t_programs))))

    st = init(prog.policies)
    feed = Feed(chunk, traffic, notes, depth)
    for _ in range(traffic.setup_chunks):
        st = feed.step(st)
    feed.drain()
    jax.block_until_ready(st)
    before = counters(st)
    setup_s = time.perf_counter() - started
    print(f"set-up: {setup_s:.3f} s: start {t_start - started:.3f} s, "
          f"programs {t_programs - t_start:.3f} s, warm-up "
          f"{t_warm - t_programs:.3f} s, {traffic.setup_chunks} chunks of "
          f"{traffic.k} steps x {prog.n_volumes} volumes "
          f"{time.perf_counter() - t_warm:.3f} s ({feed.gen_s:.3f} s "
          f"generating); queue {depth} chunks", flush=True)

    compiles = CompileCount()
    tmp = tempfile.TemporaryDirectory() if trace else None
    window = min(seconds, TRACE_WINDOW_S) if trace else seconds
    feed.annotate = jax.profiler.TraceAnnotation if trace else None
    feed.gen_s, feed.starved, feed.done_at = 0.0, 0, []
    if trace:
        jax.profiler.start_trace(tmp.name)
    compiles.on = True
    with feed._span("window"):
        t0 = time.perf_counter()
        while True:
            st = feed.step(st)
            if time.perf_counter() - t0 >= window:
                break
        feed.drain()
        jax.block_until_ready(st)
        t1 = time.perf_counter()
    compiles.on = False
    if trace:
        jax.profiler.stop_trace()
    chunks = len(feed.fed) - traffic.setup_chunks
    steps = chunks * traffic.k
    writes = steps * prog.n_volumes
    window_s = t1 - t0
    print(f"window: {window_s:.3f} s, {chunks} chunks, {writes} writes; "
          f"generator {feed.gen_s:.3f} s, device idle waiting for it at "
          f"{feed.starved} of {chunks} dispatches; {compiles.n} "
          f"compiles; chunk "
          f"intervals ms p10/p50/p90/max {chunk_intervals(feed.done_at)}",
          flush=True)

    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    host = jax.device_get({k: st[k] for k in STATE_READ})
    del st

    after = {k: int(np.sum(host[k])) for k in before}
    fed = np.concatenate(feed.fed)
    correct, checks, ref_s = check_run(cell, prog, host, fed, seed)

    out = {"correct": correct, "attempted": writes,
           "failed": abs(writes - (after["user_writes"]
                                   - before["user_writes"])),
           "device": {"platform": dev.platform, "kind": dev.device_kind,
                      "count": len(jax.devices()),
                      "memory_peak_bytes": peak}}
    if not trace:
        out["metrics"] = {
            "writes_per_s": {"value": writes / window_s, "unit": "writes/s"},
            "peak_hbm_mb": {"value": peak / 1e6, "unit": "MB"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
    else:
        events = load_events(tmp.name)
        tmp.cleanup()
        reduced = reduce_events(events)
        if keep_trace:
            save_events(keep_trace, events, reduced)
        ctx = {"trace": reduced, "steps": steps,
               "before": before, "after": after}
        metrics = {}
        for name in cell.per_layer:
            got = load_reader(name)(ctx)
            if got is not None:
                metrics[name] = {"value": got[0], "unit": got[1]}
        out["metrics"] = metrics
        out["device"]["busy_s"] = reduced["busy_s"]
        out["device"]["window_s"] = reduced["window_s"]
        out["breakdown"] = breakdown(reduced)
    print(f"reference: {ref_s:.3f} s", flush=True)
    out["checks"] = checks
    return out


def save_events(path: str, events: dict, reduced: dict) -> None:
    """The reduction, and the raw events of the window's first 50 ms and
    of its last 20 ms, gzipped JSON."""
    _, lo, dur = max((e for e in events["host"] if e[0] == "window"),
                     key=lambda e: e[2])
    hi = lo + dur

    def keep(e):
        return lo <= e[1] <= lo + 50e6 or hi - 20e6 <= e[1] <= hi

    out = {"reduced": reduced,
           "device": {k: [e for e in v if keep(e)]
                      for k, v in events["device"].items()},
           "host": [e for e in events["host"]
                    if e[0] == "window" or keep(e)]}
    with gzip.open(path, "wt") as f:
        json.dump(out, f)


def fleet_states(host: dict, n_volumes: int) -> list[dict]:
    return [{k: v[i] for k, v in host.items()} for i in range(n_volumes)]


def sample_volumes(seed: int, n_volumes: int) -> list[int]:
    rng = np.random.default_rng([seed % (1 << 64), 3])
    k = min(REFERENCE_VOLUMES, n_volumes)
    return sorted(int(v) for v in rng.choice(n_volumes, k, replace=False))


def check_run(cell: Cell, prog: Program, host: dict, fed: np.ndarray,
              seed: int):
    """(correct, checks, reference seconds) for the run's final state."""
    t0 = time.perf_counter()
    states = fleet_states(host, prog.n_volumes)
    ref = plugins.load("references", cell.config["reference"])
    refs = {}
    try:
        for v in sample_volumes(seed, prog.n_volumes):
            refs[v] = ref.replay_volume(volume_config(cell.config, v),
                                        fed[:, v])
    except ref.PoolExhausted:
        checks = {"reference_pool_exhausted": {"value": 1, "limit": 0}}
        return False, checks, time.perf_counter() - t0
    numbers = compare(states, fed, cell.config["n_lbas"], refs)
    correct, checks = verdict(numbers, load_limits())
    return correct, checks, time.perf_counter() - t0
