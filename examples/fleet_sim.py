"""Fleet-scale batched replay demo (paper §6 deployment context).

Replays a heterogeneous fleet of synthetic volumes through one vmapped XLA
program and prints per-volume + aggregate WA:

    PYTHONPATH=src python examples/fleet_sim.py --volumes 16 --workload mixed \
        [--scheme sepbit] [--selector cost_benefit] [--use-kernels]

``--sweep`` switches to a heterogeneous-config policy sweep: every volume
runs its own (scheme, selector, gp_threshold) cell of a policy grid — one
compiled program, sharded over devices when more than one is visible:

    PYTHONPATH=src python examples/fleet_sim.py --sweep --volumes 72 \
        [--schemes nosep,sepgc,sepbit] [--selectors greedy,cost_benefit] \
        [--gp-grid 0.10,0.15,0.20]

``--timing`` enables the latency/SLO model (write latency p50/p99/max per
volume and fleet-wide); ``--gcsched`` picks the GC scheduling policy
(greedy | rate_limited | idle_window) applied fleet-wide:

    PYTHONPATH=src python examples/fleet_sim.py --volumes 8 --timing \
        --gcsched rate_limited
"""

import argparse
import time

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core.fleetshard import simulate_fleet_sweep
from repro.core.jaxsim import (GCSCHED_NAMES, SCHEME_NAMES, JaxSimConfig,
                               pad_fleet, simulate_fleet)
from repro.core.tracegen import FLEET_GENERATORS, make_fleet, tiled_fleet


def run_sweep(args) -> None:
    schemes = args.schemes.split(",")
    selectors = args.selectors.split(",")
    gp_grid = [float(x) for x in args.gp_grid.split(",")]
    n_cells = len(schemes) * len(selectors) * len(gp_grid)
    per_cell = max(args.volumes // n_cells, 1)
    n_updates = int(args.traffic * args.n_lbas)
    traces = tiled_fleet(args.workload, n_cells, per_cell, args.n_lbas,
                         n_updates, jitter=args.jitter, seed=args.seed)
    cfg = JaxSimConfig(n_lbas=args.n_lbas, segment_size=args.segment,
                       use_kernels=args.use_kernels, timing=args.timing)
    print(f"sweep: {n_cells} policy cells × {per_cell} volumes "
          f"({len(traces)} total), workload={args.workload}, "
          f"gcsched={args.gcsched}")

    t0 = time.perf_counter()
    res = simulate_fleet_sweep(traces, cfg, schemes=schemes,
                               selectors=selectors, gp_thresholds=gp_grid,
                               gcsched=args.gcsched,
                               group=not args.ungrouped)
    dt = time.perf_counter() - t0

    lat_cols = " " + f"{'p50':>7s} {'p99':>7s}" if args.timing else ""
    print(f"\n{'scheme':>8s} {'selector':>14s} {'gp':>5s} {'vols':>5s} "
          f"{'WA':>8s} {'medianWA':>9s}{lat_cols}")
    for row in res["sweep"]:
        lat = (f" {row['lat_p50']:7.2f} {row['lat_p99']:7.2f}"
               if args.timing else "")
        print(f"{row['scheme']:>8s} {row['selector']:>14s} "
              f"{row['gp_threshold']:5.2f} {row['n_volumes']:5d} "
              f"{row['wa']:8.4f} {row['median_wa']:9.4f}{lat}")
    best = min(res["sweep"], key=lambda r: r["wa"])
    f = res["fleet"]
    print(f"\nbest cell: {best['scheme']}/{best['selector']}"
          f"/gp={best['gp_threshold']:.2f} (WA={best['wa']:.4f})")
    print(f"{f['n_volumes'] / dt:.2f} volumes/s (incl. compile) on "
          f"{f['n_devices']} device(s), {f['n_scheme_groups']} scheme "
          f"group(s), overflow={f['overflow']}, degraded={f['degraded']}")
    if args.timing:
        lat = f["latency"]
        print(f"fleet latency: p50={lat['p50']:.2f} p99={lat['p99']:.2f} "
              f"max={lat['max']:.2f} gc_debt={lat['gc_debt']:.1f}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--volumes", type=int, default=16)
    ap.add_argument("--workload", default="mixed",
                    choices=["mixed", *FLEET_GENERATORS])
    ap.add_argument("--n-lbas", type=int, default=512)
    ap.add_argument("--traffic", type=float, default=4.0, help="updates × WSS")
    ap.add_argument("--jitter", type=float, default=0.25,
                    help="per-volume trace-length spread (0 = uniform)")
    ap.add_argument("--segment", type=int, default=32)
    ap.add_argument("--scheme", default="sepbit", choices=list(SCHEME_NAMES))
    ap.add_argument("--selector", default="cost_benefit",
                    choices=["greedy", "cost_benefit"])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--timing", action="store_true",
                    help="enable the latency/SLO timing model and print "
                         "write-latency percentiles")
    ap.add_argument("--gcsched", default="greedy", choices=list(GCSCHED_NAMES),
                    help="GC scheduling policy (tick engine; fleet-wide)")
    ap.add_argument("--use-kernels", action="store_true",
                    help="route victim selection + classification through the "
                         "Pallas kernels")
    ap.add_argument("--sweep", action="store_true",
                    help="heterogeneous policy-grid sweep (one program, every "
                         "volume its own scheme/selector/gp)")
    ap.add_argument("--schemes", default=",".join(SCHEME_NAMES),
                    help="sweep: comma-separated schemes (default: every "
                         "JAX-registered scheme)")
    ap.add_argument("--selectors", default="greedy,cost_benefit",
                    help="sweep: comma-separated selectors")
    ap.add_argument("--gp-grid", default="0.10,0.15,0.20",
                    help="sweep: comma-separated GP thresholds")
    ap.add_argument("--ungrouped", action="store_true",
                    help="sweep: one program for the whole fleet instead of "
                         "per-scheme groups with pruned dispatch")
    args = ap.parse_args()
    enable_compile_cache()

    if args.sweep:
        run_sweep(args)
        return

    traces = make_fleet(args.workload, args.volumes, args.n_lbas,
                        int(args.traffic * args.n_lbas), jitter=args.jitter,
                        seed=args.seed)
    cfg = JaxSimConfig(n_lbas=args.n_lbas, segment_size=args.segment,
                       scheme=args.scheme, selector=args.selector,
                       use_kernels=args.use_kernels, timing=args.timing,
                       gc_sched=args.gcsched)
    padded = pad_fleet(traces)
    print(f"fleet: {args.volumes} volumes, {padded.shape[1]} padded steps, "
          f"{len({len(t) for t in traces})} distinct lengths, "
          f"scheme={args.scheme}/{args.selector}, gcsched={args.gcsched}")

    t0 = time.perf_counter()
    res = simulate_fleet(padded, cfg)
    dt = time.perf_counter() - t0

    lat_cols = f" {'p99':>7s} {'maxlat':>7s}" if args.timing else ""
    print(f"\n{'vol':>4s} {'writes':>8s} {'gc_writes':>10s} {'WA':>8s}{lat_cols}")
    for i, r in enumerate(res["volumes"]):
        lat = (f" {r['latency']['p99']:7.2f} {r['latency']['max']:7.2f}"
               if args.timing else "")
        print(f"{i:4d} {r['user_writes']:8d} {r['gc_writes']:10d} "
              f"{r['wa']:8.4f}{lat}")
    f = res["fleet"]
    wa = np.asarray(f["per_volume_wa"])
    print(f"\naggregate WA={f['wa']:.4f}  "
          f"per-volume median={np.median(wa):.4f} "
          f"[{wa.min():.4f}, {wa.max():.4f}]")
    print(f"{f['n_volumes'] / dt:.2f} volumes/s (incl. compile), "
          f"overflow={f['overflow']}, degraded={f['degraded']}")
    if args.timing:
        lat = f["latency"]
        print(f"fleet latency: p50={lat['p50']:.2f} p99={lat['p99']:.2f} "
              f"max={lat['max']:.2f} gc_debt={lat['gc_debt']:.1f}")


if __name__ == "__main__":
    main()
