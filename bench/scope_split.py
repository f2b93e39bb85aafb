"""Device time of one traced window by named scope, and the per-layer
metrics read from it.

    python3 bench/scope_split.py --workload <name> --seed <n> [--keep FILE]

from the root of a checkout on a machine with the chips the cell asks for.
The cell's set-up runs as in ``bench/run.py``; then ``CHUNKS`` chunks are
dispatched under the profiler and drained. The device ops of that window
are attributed to the program's named scopes (``scopes.py``, from the
compiled chunk's HLO). Prints a ``window:`` line, a ``scopes:`` line and
one JSON object: the split, the GC ticks, the trace's per-layer metrics
(``metrics/<name>.py``, those of ``BENCHMARK.json`` and the ones read from
scopes) and the largest device ops of each scope. ``--keep`` writes the
map and a slice of the events that holds the window's first GC tick loop
(gzipped JSON). Without a TPU it exits with 2.
"""

from __future__ import annotations

import argparse
import gzip
import json
import sys
import tempfile
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))

# read from the named scopes; each reader returns None where there is
# nothing to read
SCOPE_METRICS = ("user_write_us_per_step", "gc_tick_us_per_step",
                 "gc_select_us_per_tick", "gc_rewrite_us_per_tick",
                 "gc_merge_us_per_tick", "gc_tick_occupancy_pct")
CHUNKS = 8                      # 4,096 fleet steps in both cells, 44 GC
#                                 ticks in zipf_steady on a v5e
HEAD_NS = 20_000_000            # --keep: the window's first 20 ms


def traced_window(cell, seed: int, chunks: int) -> dict:
    """Set-up, then ``chunks`` chunks under the profiler; what the
    reduction and the readers need."""
    import jax
    import plugins
    from harness import Feed, build_program, compile_programs, counters
    from repro.core import jaxsim
    from scopes import scope_map
    from trace_reduce import load_events
    from trafficgen import Traffic

    prog = build_program(cell.config)
    traffic = Traffic(cell.traffic, cell.config["n_lbas"], prog.n_volumes,
                      seed)
    notes = plugins.load("annotations", cell.config["annotation"]).make(
        cell.config, traffic)
    init, chunk = compile_programs(prog, traffic.k)
    st = init(prog.policies)
    feed = Feed(chunk, traffic, notes, depth=max(2, chunks))
    for _ in range(traffic.setup_chunks):
        st = feed.step(st)
    feed.drain()
    before = counters(jax.block_until_ready(st))
    with tempfile.TemporaryDirectory() as tmp:
        feed.annotate = jax.profiler.TraceAnnotation
        jax.profiler.start_trace(tmp)
        with feed._span("window"):
            t0 = time.perf_counter()
            for _ in range(chunks):
                st = feed.step(st)
            feed.drain()
            jax.block_until_ready(st)
            window_s = time.perf_counter() - t0
        jax.profiler.stop_trace()
        events = load_events(tmp)
    steps = chunks * traffic.k
    print(f"window: {window_s:.3f} s, {chunks} chunks, "
          f"{steps * prog.n_volumes} writes", flush=True)
    return {"events": events, "steps": steps, "volumes": prog.n_volumes,
            "before": before, "after": counters(st),
            "scopes": scope_map(chunk.as_text(), jaxsim.SCOPES)}


def tick_slice(events: dict, scopes: dict) -> dict:
    """The window's first ``HEAD_NS``, and the whole of the first GC tick
    loop (the outermost ``gc_tick`` op around the first op of a tick's
    body) that ran in it."""
    from scopes import TICK_BODY, instruction
    (_, lo, dur), = [e for e in events["host"] if e[0] == "window"]
    ops, = events["device"].values()

    def scope(e):
        return scopes.get(instruction(e[0]))

    keep = [(lo, lo + HEAD_NS)]
    body = [e for e in ops if lo <= e[1] and scope(e) in TICK_BODY]
    if body:
        first = min(body, key=lambda e: e[1])
        loop = max((e for e in ops if scope(e) == "gc_tick"
                    and e[1] <= first[1]
                    and e[1] + e[2] >= first[1] + first[2]),
                   key=lambda e: e[2], default=first)
        keep.append((loop[1], loop[1] + loop[2]))

    def kept(e):
        return any(a <= e[1] and e[1] + e[2] <= b for a, b in keep)

    device = [e for e in ops if kept(e)]
    return {"device": {k: device for k in events["device"]},
            "host": [e for e in events["host"]
                     if e[0] == "window" or kept(e)],
            "scopes": {instruction(e[0]): scopes[instruction(e[0])]
                       for e in device if instruction(e[0]) in scopes}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--keep", metavar="FILE",
                    help="also write the scope map and a slice of the "
                         "events that holds a whole GC tick loop")
    args = ap.parse_args(argv)

    from harness import NoChip, check_device, load_cell, load_reader
    cell = load_cell(args.workload)
    try:
        check_device(cell.chips)
    except NoChip as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    import jax
    from repro.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    # JAX keys its cache on the program with the scopes stripped, so a
    # shared cache could hand back an executable compiled without them
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)

    from scopes import UNSCOPED, instruction, scope_line, scope_split
    from trace_reduce import reduce_events
    got = traced_window(cell, args.seed, CHUNKS)
    reduced = reduce_events(got["events"])
    split = scope_split(got["events"], got["scopes"])
    print(f"map: {len(got['scopes'])} instructions under a scope", flush=True)
    print(scope_line(split), flush=True)
    ctx = {"trace": reduced, "steps": got["steps"], "before": got["before"],
           "after": got["after"], "volumes": got["volumes"], **split}
    metrics = {}
    for name in (*cell.per_layer, *SCOPE_METRICS):
        value = load_reader(name)(ctx)
        if value is not None:
            metrics[name] = {"value": value[0], "unit": value[1]}
    top: dict[str, list] = {}           # the five largest ops of each scope
    for label, t in sorted(reduced["op_s"].items(), key=lambda kv: -kv[1]):
        ops = top.setdefault(
            got["scopes"].get(instruction(label.split()[0]), UNSCOPED), [])
        if len(ops) < 5:
            ops.append([label, t])
    out = {
        "workload": cell.name, "seed": args.seed, "steps": got["steps"],
        "reclaimed": got["after"]["reclaimed"] - got["before"]["reclaimed"],
        "busy_s": reduced["busy_s"], "window_s": reduced["window_s"],
        **split, "metrics": metrics,
        "top_ops": top,
        "device": jax.devices()[0].device_kind,
    }
    if args.keep:
        piece = tick_slice(got["events"], got["scopes"])
        piece["split"] = scope_split(piece, piece["scopes"])
        piece["window"] = out
        with gzip.open(args.keep, "wt") as f:
            json.dump(piece, f)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
