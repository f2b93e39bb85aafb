"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout on a machine with the chips the cell asks for.
The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``, each number compared beside its limit
(also the last lines on standard error). Without a TPU, or with fewer chips
than the cell asks for, it exits with 2 and prints no result.
"""

from __future__ import annotations

import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", metavar="FILE",
                    help="with --trace 1, also write the traced window's "
                         "reduction and a slice of its events (gzipped "
                         "JSON) to FILE")
    args = ap.parse_args(argv)

    from harness import NoChip, check_device, load_cell, run_cell
    cell = load_cell(args.workload)
    try:
        check_device(cell.chips)
    except NoChip as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    import jax
    from repro.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    # every program of the cell, however fast it compiles, is cached, so a
    # second run of the cell compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), STARTED,
                   keep_trace=args.keep_trace)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
