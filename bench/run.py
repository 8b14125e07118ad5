"""Run one cell of the benchmark once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is looked up in BENCHMARK.json at the root of the checkout.  The
run needs a TPU with at least as many chips as the cell asks for: without
one it prints no result and exits 2.  The last line of standard output is
the result object; the numbers that decide `correct` come last on
standard error, each beside its limit.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be non-negative")

    from bench import harness
    cell = harness.cell_spec(args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"bench: needs {cell['chips']} TPU chip(s); found "
              f"{len(devices)} {devices[0].platform} device(s); "
              "nothing ran", file=sys.stderr)
        return 2
    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           t_start=T_START)
    print(json.dumps(out), flush=True)
    print(f"bench: the reference check took {out['check_s']:.1f} s",
          file=sys.stderr)
    harness.print_checks(out["checks"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
