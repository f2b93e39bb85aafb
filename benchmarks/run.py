"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. ``derived`` is the quantity the
paper's table/figure reports (WA, conditional probability, reduction %);
``us_per_call`` is the wall time of the producing computation.

Run:  PYTHONPATH=src python -m benchmarks.run [--full] [--only exp1,...]
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import time

import numpy as np


def _row(name, us, derived):
    print(f"{name},{us:.1f},{derived}", flush=True)


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return (time.perf_counter() - t0) * 1e6, out


def _pool(full):
    from repro.core.volumes import default_pool
    pool = default_pool(scale=2 if full else 1)
    return pool if full else pool[:6]


def _all_schemes():
    """Every registered placement scheme (numpy backend), registry order."""
    from repro.core.placement import registry
    return [sd.name for sd in registry.all_schemes()]


def _jax_schemes():
    """Every scheme with a JAX triple — the fleet/sweep scheme axis."""
    from repro.core.jaxsim import SCHEME_NAMES
    return list(SCHEME_NAMES)


def exp1_selection(full=False):
    """Exp#1 (Fig 12): overall WA per scheme under Greedy & Cost-Benefit."""
    from repro.core.simulator import simulate
    from repro.core.volumes import overall_wa
    pool = _pool(full)
    for sel in ("greedy", "cost_benefit"):
        for scheme in _all_schemes():
            us, rs = _timed(lambda: [simulate(tr, scheme, segment_size=128,
                                              selector=sel) for _, tr in pool])
            _row(f"exp1/{sel}/{scheme}", us, f"WA={overall_wa(rs):.4f}")


def exp2_segsize(full=False):
    """Exp#2 (Fig 13): WA vs segment size at fixed 512MiB-equivalent GC IO."""
    from repro.core.simulator import simulate
    from repro.core.volumes import overall_wa
    pool = _pool(full)
    for seg, batch in ((32, 4), (64, 2), (128, 1)):
        for scheme in ("nosep", "sepgc", "warcip", "sepbit", "fk"):
            us, rs = _timed(lambda: [simulate(tr, scheme, segment_size=seg,
                                              gc_batch_segments=batch,
                                              selector="cost_benefit")
                                     for _, tr in pool])
            _row(f"exp2/seg{seg}/{scheme}", us, f"WA={overall_wa(rs):.4f}")


def exp3_gp(full=False):
    """Exp#3 (Fig 14): WA vs GP trigger threshold."""
    from repro.core.simulator import simulate
    from repro.core.volumes import overall_wa
    pool = _pool(full)
    for gp in (0.10, 0.15, 0.20, 0.25):
        for scheme in ("nosep", "sepgc", "warcip", "sepbit", "fk"):
            us, rs = _timed(lambda: [simulate(tr, scheme, segment_size=128,
                                              gp_threshold=gp,
                                              selector="cost_benefit")
                                     for _, tr in pool])
            _row(f"exp3/gp{int(gp*100)}/{scheme}", us, f"WA={overall_wa(rs):.4f}")


def exp4_breakdown(full=False):
    """Exp#4 (Fig 15): NoSep / SepGC / UW / GW / SepBIT breakdown + the
    per-volume WA-reduction distribution vs SepGC."""
    from repro.core.simulator import simulate
    from repro.core.volumes import overall_wa
    pool = _pool(full)
    results = {}
    for scheme in ("nosep", "sepgc", "uw", "gw", "sepbit"):
        us, rs = _timed(lambda: [simulate(tr, scheme, segment_size=128,
                                          selector="cost_benefit")
                                 for _, tr in pool])
        results[scheme] = rs
        _row(f"exp4/{scheme}", us, f"WA={overall_wa(rs):.4f}")
    red = [100 * (1 - a.wa / b.wa) for a, b in zip(results["sepbit"],
                                                   results["sepgc"])]
    _row("exp4/sepbit_vs_sepgc_reduction", 0,
         f"median={np.median(red):.1f}%;max={max(red):.1f}%")


def exp5_memory(full=False):
    """Exp#5 (Fig 16): FIFO-queue memory vs a full LBA map."""
    from repro.core.simulator import simulate
    pool = _pool(full)
    worst, snap = [], []
    t0 = time.perf_counter()
    for name, tr in pool:
        r = simulate(tr, "sepbit", segment_size=128, selector="cost_benefit")
        wss = r.wss_unique_lbas
        if r.fifo_occupancy_peak:
            worst.append(100 * (1 - r.fifo_occupancy_peak / wss))
            snap.append(100 * (1 - r.fifo_occupancy_last / wss))
    us = (time.perf_counter() - t0) * 1e6
    _row("exp5/memory_reduction_worst", us,
         f"median={np.median(worst):.1f}%;min={min(worst):.1f}%")
    _row("exp5/memory_reduction_snapshot", 0,
         f"median={np.median(snap):.1f}%;max={max(snap):.1f}%")


def fig8_user_bit(full=False):
    """Fig 8: closed-form Pr(u<=u0 | v<=v0) — checked against paper values."""
    from repro.core.analysis import BLOCKS_PER_GIB as G, pr_user_bit
    for (u0, v0, alpha, paper) in ((0.25, 4, 1.0, 77.1), (1, 0.25, 1.0, None),
                                   (1, 4, 1.0, 87.1), (1, 1, 0.0, 9.5)):
        us, p = _timed(lambda: pr_user_bit(u0 * G, v0 * G, alpha=alpha))
        tag = f"paper={paper}" if paper else "n/a"
        _row(f"fig8/u{u0}v{v0}a{alpha}", us, f"P={100*p:.1f}%;{tag}")


def fig10_gc_bit(full=False):
    """Fig 10: closed-form Pr(u<=g0+r0 | u>=g0)."""
    from repro.core.analysis import BLOCKS_PER_GIB as G, pr_gc_bit
    for (g0, r0, alpha, paper) in ((2, 8, 1.0, 41.2), (32, 8, 1.0, 14.9),
                                   (2, 8, 0.2, None), (32, 8, 0.2, None)):
        us, p = _timed(lambda: pr_gc_bit(g0 * G, r0 * G, alpha=alpha))
        tag = f"paper={paper}" if paper else "n/a"
        _row(f"fig10/g{g0}r{r0}a{alpha}", us, f"P={100*p:.1f}%;{tag}")


def fig9_11_trace(full=False):
    """Fig 9/11: empirical conditional probabilities on the volume pool."""
    from repro.core.analysis import trace_conditional_gc, trace_conditional_user
    pool = _pool(full)
    n = int(max(tr.max() for _, tr in pool)) + 1
    for v0f in (0.1, 0.4):
        us, ps = _timed(lambda: [trace_conditional_user(tr, int(0.1 * n), int(v0f * n))
                                 for _, tr in pool])
        ps = [p for p in ps if np.isfinite(p)]
        _row(f"fig9/v0={v0f}wss", us, f"median={100*np.median(ps):.1f}%")
    for g0f in (0.1, 1.0):
        us, ps = _timed(lambda: [trace_conditional_gc(tr, int(g0f * n), int(0.5 * n))
                                 for _, tr in pool])
        _row(f"fig11/g0={g0f}wss", us, f"median={100*np.median(ps):.1f}%")


def obs_trace_analysis(full=False):
    """§2.3 Observations 1-3 on the synthetic pool."""
    pool = _pool(full)
    t0 = time.perf_counter()
    short_fracs, rare_fracs, cvs = [], [], []
    for name, tr in pool:
        n = int(tr.max()) + 1
        last = np.full(n, -1, dtype=np.int64)
        lifespans = []
        count = np.zeros(n, dtype=np.int64)
        per_lba_spans: dict = {}
        for i, lba in enumerate(tr):
            if last[lba] >= 0:
                d = i - last[lba]
                lifespans.append(d)
                per_lba_spans.setdefault(lba, []).append(d)
            last[lba] = i
            count[lba] += 1
        spans = np.asarray(lifespans)
        short_fracs.append(100 * np.mean(spans < 0.5 * n) if len(spans) else 0)
        rare_fracs.append(100 * np.mean(count[count > 0] <= 4))
        hot = np.argsort(-count)[: max(n // 100, 1)]
        cv = [np.std(per_lba_spans[i]) / np.mean(per_lba_spans[i])
              for i in hot if i in per_lba_spans and len(per_lba_spans[i]) > 3]
        if cv:
            cvs.append(np.median(cv))
    us = (time.perf_counter() - t0) * 1e6
    _row("obs1/short_lifespan_frac", us, f"median={np.median(short_fracs):.1f}%")
    _row("obs2/top1pct_lifespan_cv", 0, f"median={np.median(cvs):.2f}")
    _row("obs3/rarely_updated_frac", 0, f"median={np.median(rare_fracs):.1f}%")


def kv_wa(full=False):
    """Beyond-paper: serving KV-compaction WA, SepBIT vs baselines."""
    from repro.serving.scheduler import WorkloadConfig, compare_policies
    w = WorkloadConfig(n_requests=2500 if full else 1200, max_batch=24, seed=5)
    us, res = _timed(lambda: compare_policies(w, n_frames=64, pages_per_frame=32))
    for policy, r in res.items():
        _row(f"kv_wa/{policy}", us / 3, f"WA={r['wa']:.4f}")


def ckpt_wa(full=False):
    """Beyond-paper: checkpoint-store compaction WA, SepBIT vs NoSep."""
    import shutil
    import tempfile
    from repro.checkpoint import LogBlobStore, LogStoreConfig
    rng = np.random.default_rng(0)
    for policy in ("nosep", "sepbit"):
        root = tempfile.mkdtemp()
        t0 = time.perf_counter()
        store = LogBlobStore(root, LogStoreConfig(segment_bytes=1 << 15,
                                                  gp_threshold=0.12,
                                                  policy=policy))
        steps = 120 if full else 60
        for i in range(steps):
            for k in range(6):
                store.put(f"opt/{k}", rng.bytes(2048))     # churns every step
            if i % 5 == 0:
                store.put(f"ema/{i}", rng.bytes(4096))     # long-lived
        us = (time.perf_counter() - t0) * 1e6
        _row(f"ckpt_wa/{policy}", us, f"WA={store.write_amplification:.4f}")
        shutil.rmtree(root)


def jaxsim_throughput(full=False):
    """TPU-resident simulator throughput (writes/s on this CPU host)."""
    from repro.core.jaxsim import JaxSimConfig, simulate_jax
    from repro.core.traces import zipf_trace
    n = 1 << 10
    tr = zipf_trace(n, 2 * n, alpha=1.0, seed=1)
    cfg = JaxSimConfig(n_lbas=n, segment_size=32, scheme="sepbit")
    simulate_jax(tr, cfg)  # compile
    us, r = _timed(lambda: simulate_jax(tr, cfg))
    _row("jaxsim/sepbit_cb", us, f"writes_per_s={1e6*len(tr)/us:.0f};WA={r['wa']:.3f}")


def fleet(full=False, n_volumes=None, kind="mixed"):
    """Fleet-scale batched replay: one vmapped XLA program over V volumes vs
    a Python loop of single-volume jaxsim runs.

    The fleet is heterogeneous (per-volume trace lengths differ, as in the
    paper's 186-volume corpus), which is exactly where batching wins: the
    padded fleet program compiles *once*, while the naive loop re-traces and
    re-compiles the scan for every distinct trace length. The headline
    ``cold`` rows therefore time the end-to-end evaluation including
    compilation for both sides (caches cleared first); ``steady`` rows show
    the recompile-free repeat throughput for transparency.
    """
    import jax
    import numpy as np
    from repro.core.jaxsim import JaxSimConfig, pad_fleet, simulate_fleet, simulate_jax
    from repro.core.tracegen import make_fleet
    V = n_volumes or (32 if full else 16)
    n = 512 if full else 256
    traces = make_fleet(kind, V, n, 3 * n, jitter=0.25, seed=9)
    cfg = JaxSimConfig(n_lbas=n, segment_size=32, scheme="sepbit")
    padded = pad_fleet(traces)
    n_lens = len({len(t) for t in traces})

    jax.clear_caches()
    us_f, rf = _timed(lambda: simulate_fleet(padded, cfg))   # 1 compile, V replays
    us_f2, _ = _timed(lambda: simulate_fleet(padded, cfg))
    jax.clear_caches()
    us_l, rl = _timed(lambda: [simulate_jax(t, cfg) for t in traces])
    us_l2, _ = _timed(lambda: [simulate_jax(t, cfg) for t in traces])

    wa = np.asarray(rf["fleet"]["per_volume_wa"])
    _row(f"fleet/{kind}/cold_vmap_v{V}", us_f,
         f"volumes_per_s={1e6 * V / us_f:.2f};WA={rf['fleet']['wa']:.4f}")
    _row(f"fleet/{kind}/cold_loop_v{V}", us_l,
         f"volumes_per_s={1e6 * V / us_l:.2f};distinct_lengths={n_lens}")
    _row(f"fleet/{kind}/cold_speedup", 0, f"x={us_l / us_f:.2f}")
    _row(f"fleet/{kind}/steady_vmap_v{V}", us_f2,
         f"volumes_per_s={1e6 * V / us_f2:.2f}")
    _row(f"fleet/{kind}/steady_loop_v{V}", us_l2,
         f"volumes_per_s={1e6 * V / us_l2:.2f}")
    _row(f"fleet/{kind}/per_volume_wa", 0,
         f"median={np.median(wa):.4f};min={wa.min():.4f};max={wa.max():.4f}")
    mism = sum(rf["volumes"][i]["gc_writes"] != rl[i]["gc_writes"] for i in range(V))
    _row(f"fleet/{kind}/parity_mismatches", 0, str(mism))


def sweep(full=False, n_volumes=None, kind="mixed", schemes=None,
          selectors=None, gp_grid=None, use_kernels=False, json_path=None,
          timing=False):
    """Heterogeneous-config fleet sweep: one compiled program replays a
    (scheme × selector × gp_threshold) policy grid, every volume running its
    own placement policy via traced per-volume knobs, sharded over devices
    when more than one is visible. Each grid cell replays the same tiled
    workloads, so per-cell WA rows compare policies on equal traffic.

    The default scheme axis is *every* scheme with a registered JAX triple
    (the paper's Exp#1/#3 zoo on the fleet path); ``--schemes`` filters it.
    ``--json OUT.json`` writes a per-cell artifact (scheme, selector, gp,
    WA mean ± 95% CI across the cell's volumes) for plotting WA-vs-gp
    curves per scheme."""
    from repro.core.fleetshard import simulate_fleet_sweep
    from repro.core.jaxsim import JaxSimConfig
    from repro.core.tracegen import tiled_fleet
    schemes = schemes or _jax_schemes()
    selectors = selectors or ["greedy", "cost_benefit"]
    gp_grid = gp_grid or [0.10, 0.15, 0.20]
    n_cells = len(schemes) * len(selectors) * len(gp_grid)
    V = n_volumes or (n_cells * (4 if full else 2))
    per_cell = max(V // n_cells, 1)
    V = per_cell * n_cells
    # n_lbas = 512 is the smallest scale where the paper's Exp#1/#3 WA
    # ordering (FK <= SepBIT <= temperature ladders <= NoSep at the default
    # gp = 0.15) is reproduced — below it the ladder schemes' six open
    # segments are too large a fraction of the working set
    n = 512
    traces = tiled_fleet(kind, n_cells, per_cell, n, 4 * n, jitter=0.25, seed=17)
    cfg = JaxSimConfig(n_lbas=n, segment_size=32, use_kernels=use_kernels,
                       timing=timing)
    us, res = _timed(lambda: simulate_fleet_sweep(
        traces, cfg, schemes=schemes, selectors=selectors, gp_thresholds=gp_grid))
    f = res["fleet"]
    _row(f"sweep/{kind}/fleet_v{V}", us,
         f"volumes_per_s={1e6 * V / us:.2f};cells={n_cells};"
         f"devices={f['n_devices']};WA={f['wa']:.4f};"
         f"overflow={f['overflow']};degraded={f['degraded']}")
    for row in res["sweep"]:
        lat = (f";p50={row['lat_p50']:.2f};p99={row['lat_p99']:.2f}"
               if timing else "")
        _row(f"sweep/{row['scheme']}/{row['selector']}/"
             f"gp{int(round(100 * row['gp_threshold']))}", 0,
             f"WA={row['wa']:.4f};mean={row['wa_mean']:.4f}"
             f"±{row['wa_ci95']:.4f};median={row['median_wa']:.4f};"
             f"n={row['n_volumes']}" + lat)
    best = min(res["sweep"], key=lambda r: r["wa"])
    worst = max(res["sweep"], key=lambda r: r["wa"])
    _row(f"sweep/{kind}/best_cell", 0,
         f"{best['scheme']}/{best['selector']}/gp{best['gp_threshold']:.2f};"
         f"WA={best['wa']:.4f};reduction_vs_worst="
         f"{100 * (1 - best['wa'] / worst['wa']):.1f}%")
    if json_path:
        keys = ["scheme", "selector", "gp_threshold", "n_volumes",
                "user_writes", "gc_writes", "wa", "wa_mean", "wa_ci95",
                "median_wa", "per_volume_wa", "overflow", "free_exhausted",
                "degraded"]
        if timing:
            keys += ["lat_p50", "lat_p99", "lat_max", "lat_mean", "gc_debt"]
        cells = [{k: row[k] for k in keys} for row in res["sweep"]]
        artifact = {
            "workload": kind, "n_lbas": n, "segment_size": 32,
            "n_updates": 4 * n, "volumes_per_cell": per_cell,
            "n_volumes": V, "schemes": schemes, "selectors": selectors,
            "gp_thresholds": gp_grid, "n_devices": f["n_devices"],
            "timing": timing, "fleet_wa": f["wa"], "wall_us": us,
            "cells": cells,
        }
        with open(json_path, "w") as fp:
            json.dump(artifact, fp, indent=1)
        _row(f"sweep/{kind}/json", 0, json_path)


def gcbench(full=False, n_volumes=None, kind="mixed", gp_grid=None,
            json_path=None):
    """Steady-state fleet GC throughput: the synchronized-tick engine
    (fleet-level GC ticks, fused ``_gc_once``, scheme-grouped dispatch)
    against the pre-tick ``legacy`` engine on a heterogeneous-GP fleet.

    Heterogeneous GP thresholds de-synchronize GC triggers across volumes —
    the worst case for the legacy vmapped ``while_loop``, which paid a
    per-volume victim argmax on *every* user write and ran the full rewrite
    cascade for every volume whenever any one triggered. Reports cold
    (compile-inclusive) and steady (recompile-free repeat) timings for both
    engines, asserts bitwise result parity between them, and writes the
    ``BENCH_fleet_gc.json`` artifact (schema-checked + uploaded in CI)."""
    import dataclasses

    import jax

    from repro.core.fleetshard import encode_policies, simulate_fleet_hetero
    from repro.core.jaxsim import JaxSimConfig
    from repro.core.tracegen import make_fleet

    V = n_volumes or 16
    n = 512 if full else 256
    gps = gp_grid or [0.08, 0.12, 0.16, 0.22]
    gp_per_vol = [gps[i % len(gps)] for i in range(V)]
    traces = make_fleet(kind, V, n, 4 * n, jitter=0.25, seed=23)
    policy = encode_policies(V, schemes="sepbit", selectors="cost_benefit",
                             gp_thresholds=gp_per_vol)
    base = JaxSimConfig(n_lbas=n, segment_size=32)

    engines, results = {}, {}
    for name, cfg, group in (
            ("legacy", dataclasses.replace(base, gc_engine="legacy"), False),
            ("tick", base, True)):
        jax.clear_caches()
        us_cold, res = _timed(lambda: simulate_fleet_hetero(
            traces, cfg, policy, group=group))
        us_steady, res = _timed(lambda: simulate_fleet_hetero(
            traces, cfg, policy, group=group))
        results[name] = res
        engines[name] = {
            "cold_us": us_cold, "steady_us": us_steady,
            "steady_volumes_per_s": 1e6 * V / us_steady, "grouped": group,
        }
        _row(f"gcbench/{kind}/{name}_steady_v{V}", us_steady,
             f"volumes_per_s={1e6 * V / us_steady:.2f};"
             f"WA={res['fleet']['wa']:.4f}")
    speedup = (engines["tick"]["steady_volumes_per_s"]
               / engines["legacy"]["steady_volumes_per_s"])
    parity = all(
        a["wa"] == b["wa"] and a["gc_writes"] == b["gc_writes"]
        and a["reclaimed"] == b["reclaimed"] and a["ell"] == b["ell"]
        for a, b in zip(results["tick"]["volumes"],
                        results["legacy"]["volumes"]))
    _row(f"gcbench/{kind}/steady_speedup", 0, f"x={speedup:.2f}")
    _row(f"gcbench/{kind}/parity", 0, "ok" if parity else "MISMATCH")

    vols = results["tick"]["volumes"]
    reclaimed = [v["reclaimed"] for v in vols]
    total_user = sum(v["user_writes"] for v in vols)
    artifact = {
        "bench": "fleet_gc",
        "n_volumes": V, "n_lbas": n, "segment_size": 32, "workload": kind,
        "scheme": "sepbit", "selector": "cost_benefit",
        "gp_thresholds": gp_per_vol,
        "n_devices": results["tick"]["fleet"]["n_devices"],
        "engines": engines,
        "speedup_steady": speedup,
        "parity_ok": parity,
        "gc": {
            "total_reclaimed": sum(reclaimed),
            "per_volume_reclaimed": reclaimed,
            "gc_per_1k_user_writes": 1000.0 * sum(reclaimed)
            / max(total_user, 1),
        },
        "per_volume": [
            {"gp": gp_per_vol[i], "wa": v["wa"], "gc_writes": v["gc_writes"],
             "reclaimed": v["reclaimed"]} for i, v in enumerate(vols)],
    }
    out = json_path or "BENCH_fleet_gc.json"
    with open(out, "w") as fp:
        json.dump(artifact, fp, indent=1)
    _row(f"gcbench/{kind}/json", 0, out)


def latbench(full=False, n_volumes=None, kind="mixed", schemes=None,
             gcscheds=None, json_path=None):
    """GC latency/SLO benchmark: scheduling policy × placement scheme on a
    heterogeneous fleet with the timing model on.

    Every (gcsched, scheme) cell replays the same tiled workloads, so the
    per-cell p50/p99 foreground latencies and WA compare scheduling policies
    on equal traffic. The headline ``slo`` row picks the non-greedy policy
    with the largest p99 reduction vs greedy among cells holding WA within
    +5% — rate_limited makes identical GC *decisions* to greedy (WA ratio
    exactly 1) and only spreads when their cost is charged, so the bound is
    structural, not tuned. Writes ``BENCH_gc_latency.json`` (schema-checked
    + uploaded in CI)."""
    import numpy as np

    from repro.core.fleetshard import encode_policies, simulate_fleet_hetero
    from repro.core.jaxsim import GCSCHED_NAMES, JaxSimConfig, hist_quantile
    from repro.core.tracegen import tiled_fleet

    schemes = schemes or ["nosep", "sepgc", "sepbit", "fk"]
    gcscheds = gcscheds or list(GCSCHED_NAMES)
    cells = [(g, s) for g in gcscheds for s in schemes]
    per_cell = n_volumes // len(cells) if n_volumes else (4 if full else 2)
    per_cell = max(per_cell, 1)
    V = len(cells) * per_cell
    n = 512 if full else 256
    traces = tiled_fleet(kind, len(cells), per_cell, n, 4 * n,
                         jitter=0.25, seed=47)
    cfg = JaxSimConfig(n_lbas=n, segment_size=32, timing=True)
    policy = encode_policies(
        V,
        schemes=[s for _, s in cells for _ in range(per_cell)],
        selectors="cost_benefit", gp_thresholds=0.15,
        gcscheds=[g for g, _ in cells for _ in range(per_cell)])
    us, res = _timed(lambda: simulate_fleet_hetero(traces, cfg, policy))
    _row(f"latbench/{kind}/fleet_v{V}", us,
         f"volumes_per_s={1e6 * V / us:.2f};cells={len(cells)};"
         f"devices={res['fleet']['n_devices']}")

    rows = []
    for ci, (g, s) in enumerate(cells):
        vols = res["volumes"][ci * per_cell:(ci + 1) * per_cell]
        hist = np.sum([v["latency"]["hist"] for v in vols], axis=0)
        user = sum(v["user_writes"] for v in vols)
        gc = sum(v["gc_writes"] for v in vols)
        overflow = sum(v["overflow"] for v in vols)
        row = {
            "gcsched": g, "scheme": s, "n_volumes": per_cell,
            "user_writes": user, "gc_writes": gc,
            "wa": (user + gc) / max(user, 1),
            "overflow": overflow, "degraded": overflow > 0,
            "write_cost": cfg.write_cost,
            "p50": hist_quantile(hist, 0.50, cfg.write_cost),
            "p99": hist_quantile(hist, 0.99, cfg.write_cost),
            "max": max(v["latency"]["max"] for v in vols),
            "mean": sum(v["latency"]["total"] for v in vols) / max(user, 1),
            "gc_debt": sum(v["latency"]["gc_debt"] for v in vols),
        }
        rows.append(row)
        _row(f"latbench/{g}/{s}", 0,
             f"p50={row['p50']:.2f};p99={row['p99']:.2f};"
             f"max={row['max']:.2f};WA={row['wa']:.4f};"
             f"debt={row['gc_debt']:.0f}")

    # headline: best p99 reduction vs greedy at <= +5% WA, per the
    # acceptance bar; compared within each scheme on identical traffic
    by_cell = {(r["gcsched"], r["scheme"]): r for r in rows}
    slo = None
    for r in rows:
        if r["gcsched"] == "greedy":
            continue
        base = by_cell.get(("greedy", r["scheme"]))
        if base is None or base["p99"] <= 0:
            continue
        wa_ratio = r["wa"] / max(base["wa"], 1e-9)
        if wa_ratio > 1.05:
            continue
        cand = {"gcsched": r["gcsched"], "scheme": r["scheme"],
                "p99": r["p99"], "p99_greedy": base["p99"],
                "p99_reduction": 1.0 - r["p99"] / base["p99"],
                "wa": r["wa"], "wa_greedy": base["wa"],
                "wa_ratio": wa_ratio}
        if slo is None or cand["p99_reduction"] > slo["p99_reduction"]:
            slo = cand
    if slo:
        _row(f"latbench/{kind}/slo_win", 0,
             f"{slo['gcsched']}/{slo['scheme']};p99={slo['p99']:.2f}"
             f"vs{slo['p99_greedy']:.2f}"
             f"(-{100 * slo['p99_reduction']:.0f}%);"
             f"wa_ratio={slo['wa_ratio']:.3f}")

    artifact = {
        "bench": "gc_latency",
        "workload": kind, "n_lbas": n, "segment_size": 32,
        "n_updates": 4 * n, "volumes_per_cell": per_cell, "n_volumes": V,
        "schemes": schemes, "gcscheds": gcscheds,
        "selector": "cost_benefit", "gp_threshold": 0.15,
        "write_cost": cfg.write_cost, "gc_block_cost": cfg.gc_block_cost,
        "gc_rate": cfg.gc_rate, "idle_density": cfg.idle_density,
        "n_devices": res["fleet"]["n_devices"], "wall_us": us,
        "cells": rows, "slo": slo,
    }
    out = json_path or "BENCH_gc_latency.json"
    with open(out, "w") as fp:
        json.dump(artifact, fp, indent=1)
    _row(f"latbench/{kind}/json", 0, out)


def kernels(full=False):
    """Pallas kernel timings: interpreted on a CPU backend, compiled on a
    TPU (the row's derived column says which)."""
    import jax.numpy as jnp
    from repro.kernels import interpret_mode, ops
    mode = "interpret-mode" if interpret_mode() else "compiled"
    rng = np.random.default_rng(0)
    S = 1 << 14
    n = jnp.asarray(rng.integers(1, 129, S), jnp.int32)
    nv = jnp.minimum(jnp.asarray(rng.integers(0, 129, S), jnp.int32), n)
    st = jnp.asarray(rng.integers(0, 10000, S), jnp.int32)
    state = jnp.asarray(rng.integers(0, 3, S), jnp.int32)
    t = jnp.int32(20000)
    ops.segment_select(n, nv, st, state, t)  # compile
    us, _ = _timed(lambda: ops.segment_select(n, nv, st, state, t)[0].block_until_ready())
    _row("kernels/segsel_16k", us, mode)
    B, Hq, Hkv, D, S2 = 2, 8, 2, 128, 1024
    q = jnp.asarray(rng.standard_normal((B, Hq, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S2, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S2, Hkv, D)), jnp.float32)
    kl = jnp.full((B,), S2, jnp.int32)
    ops.flash_decode(q, k, v, kl)
    us, _ = _timed(lambda: ops.flash_decode(q, k, v, kl).block_until_ready())
    _row("kernels/flash_decode_1k", us, mode)


def roofline(full=False):
    """§Roofline summary from the dry-run artifact (if present)."""
    path = os.environ.get("DRYRUN_JSON", ".cache/dryrun_all.json")
    if not os.path.exists(path):
        _row("roofline/skipped", 0, f"no {path}; run repro.launch.dryrun first")
        return
    from repro.roofline import build_table
    for r in build_table(path):
        _row(f"roofline/{r.arch}/{r.shape}", 0,
             f"dom={r.dominant};useful={r.useful_ratio:.2f};"
             f"roofline={100*r.roofline_fraction():.1f}%")


def analysis_bench(full=False):
    """Wall time to trace+lint the full registry with the static contract
    verifier (`repro.analysis`) — analyzer cost must stay visible as the
    scheme zoo grows."""
    from repro import analysis as ra
    from repro.core.placement import registry
    cfg = ra.probe_config(n_lbas=4096 if full else 256,
                          segment_size=32 if full else 16)
    total = 0.0
    for sd, impl in registry.jax_schemes():
        us, (findings, _) = _timed(
            lambda: ra.analyze_scheme(cfg, sd.name, sd.n_classes, impl))
        total += us
        _row(f"analysis/scheme/{sd.name}", us, f"findings={len(findings)}")
    us, per_kernel = _timed(ra.analyze_kernels)
    total += us
    n_kernel = sum(len(v) for v in per_kernel.values())
    _row("analysis/kernels", us, f"findings={n_kernel}")
    us, engine_findings = _timed(lambda: ra.analyze_engine(cfg))
    total += us
    _row("analysis/engine", us, f"findings={len(engine_findings)}")
    us, fleet_findings = _timed(lambda: ra.analyze_fleet(cfg))
    total += us
    _row("analysis/fleet", us, f"findings={len(fleet_findings)}")
    _row("analysis/total", total, f"n_lbas={cfg.n_lbas}")
    us, report = _timed(lambda: ra.analyze_registry(cfg))
    _row("analysis/full_report", us, f"findings={report['n_findings']}")


BENCHES = {
    "analysis": analysis_bench,
    "exp1": exp1_selection, "exp2": exp2_segsize, "exp3": exp3_gp,
    "exp4": exp4_breakdown, "exp5": exp5_memory,
    "fig8": fig8_user_bit, "fig10": fig10_gc_bit, "fig9_11": fig9_11_trace,
    "obs": obs_trace_analysis, "kv_wa": kv_wa, "ckpt_wa": ckpt_wa,
    "jaxsim": jaxsim_throughput, "fleet": fleet, "sweep": sweep,
    "gcbench": gcbench, "latbench": latbench, "kernels": kernels,
    "roofline": roofline,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="benchmark-grade sizes")
    ap.add_argument("--only", default=None, help="comma-separated bench names")
    ap.add_argument("--mode", default=None,
                    choices=[None, "paper", "fleet", "sweep", "gcbench",
                             "latbench", "analysis-bench"],
                    help="fleet = batched multi-volume replay benchmark only; "
                         "sweep = heterogeneous policy-grid sweep only; "
                         "gcbench = steady-state GC-tick engine vs the legacy "
                         "fleet path (writes BENCH_fleet_gc.json); "
                         "latbench = GC scheduling policy × placement scheme "
                         "latency/SLO sweep (writes BENCH_gc_latency.json); "
                         "analysis-bench = trace+lint wall time of the "
                         "static contract verifier over the registry; "
                         "paper = every bench except fleet/sweep/gcbench/"
                         "latbench")
    ap.add_argument("--volumes", type=int, default=None,
                    help="fleet/sweep mode: number of volumes")
    ap.add_argument("--workload", default="mixed",
                    help="fleet/sweep mode: mixed|zipf_mixture|shifting_hotspot|msr_burst")
    ap.add_argument("--schemes", default=None,
                    help="sweep mode: comma-separated scheme filter "
                         "(default: every JAX-registered scheme)")
    ap.add_argument("--selectors", default=None,
                    help="sweep mode: comma-separated selectors")
    ap.add_argument("--gp-grid", default=None,
                    help="sweep mode: comma-separated GP thresholds (default 0.10,0.15,0.20)")
    ap.add_argument("--use-kernels", action="store_true",
                    help="sweep mode: route hot paths through the Pallas kernels")
    ap.add_argument("--timing", action="store_true",
                    help="sweep mode: enable the latency/SLO timing model "
                         "(adds p50/p99 columns to rows and the JSON)")
    ap.add_argument("--gcscheds", default=None,
                    help="latbench mode: comma-separated GC scheduling "
                         "policies (default: greedy,rate_limited,idle_window)")
    ap.add_argument("--json", default=None, metavar="OUT.json",
                    help="sweep mode: write the per-cell artifact "
                         "(scheme/selector/gp, WA mean ± CI) to this path")
    args, _ = ap.parse_known_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    benches = dict(BENCHES)  # bind fleet flags once, wherever it's dispatched
    benches["fleet"] = functools.partial(fleet, n_volumes=args.volumes,
                                         kind=args.workload)
    gp_grid = [float(x) for x in args.gp_grid.split(",")] if args.gp_grid else None
    benches["sweep"] = functools.partial(
        sweep, n_volumes=args.volumes, kind=args.workload,
        schemes=args.schemes.split(",") if args.schemes else None,
        selectors=args.selectors.split(",") if args.selectors else None,
        gp_grid=gp_grid, use_kernels=args.use_kernels, json_path=args.json,
        timing=args.timing)
    benches["gcbench"] = functools.partial(
        gcbench, n_volumes=args.volumes, kind=args.workload,
        gp_grid=gp_grid, json_path=args.json)
    benches["latbench"] = functools.partial(
        latbench, n_volumes=args.volumes, kind=args.workload,
        schemes=args.schemes.split(",") if args.schemes else None,
        gcscheds=args.gcscheds.split(",") if args.gcscheds else None,
        json_path=args.json)
    if args.mode == "analysis-bench":
        analysis_bench(full=args.full)
        return
    if args.mode in ("fleet", "sweep", "gcbench", "latbench"):
        benches[args.mode](full=args.full)
        return
    names = args.only.split(",") if args.only else list(benches)
    if args.mode == "paper" and not args.only:
        names = [n for n in names if n not in ("fleet", "sweep", "gcbench",
                                               "latbench")]
    for name in names:
        benches[name](full=args.full)


if __name__ == "__main__":
    main()
