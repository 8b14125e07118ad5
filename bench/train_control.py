"""Readings that the limits of a training cell's `correct` are set from.

    python3 bench/train_control.py --workload <cell> --seeds 1 2 3 ... \
        [--program] [--control-seeds 3]

In one process, for each seed: the reference's first steps (float32 at
`highest`), and against them, by the numbers of `bench/train_check.py`:

  program     (with --program) the cell's own set-up from the seed: the
              step compiled once, its first steps through the entry's
              call, then freed; `program_worst` names the leaves that
              set its two per-leaf numbers;
  control     (first --control-seeds seeds) the reference with every
              matmul in float8 e4m3 (one scale per tensor, forward and
              backward), the nearest precision below bfloat16;
  half_batch, pod_local, altered, stale
              the faults a training cell can have (`bench/train_check.py`),
              planted in the reference put in the program's place (same
              seeds); the last two alter the reference's own answer;
  lr_low, no_decay
              two wrong optimizer updates, planted the same way.

One JSON line per seed.  The program needs the cell's chips; the
control and the faults run on one.  The benchmark's own runs never run
this.
"""
import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

def readings(cell: dict, seeds, program: bool, control_seeds: int,
             emit=print) -> list:
    from bench import harness
    from bench import train_check as tc
    from bench import train_reference as ref
    harness.use_compile_cache()
    entry = harness.load_module("entries", cell["traffic"]["entry"])
    built = entry.build(harness.Run(cell, 0, 0.0)) if program else None
    out = []
    for i, seed in enumerate(seeds):
        rec = {"seed": seed}
        if program:
            run = harness.Run(cell, seed, 0.0)
            prog = entry.free(entry.start(run, built))
        t0 = time.perf_counter()
        want = tc.reference_summary(cell, seed)
        rec["reference_s"] = time.perf_counter() - t0
        if program:
            rec["program"] = tc.numbers(prog, want)
            rec["program_worst"] = tc.worst_leaves(prog, want)
        if i < control_seeds:
            rec["control"] = tc.numbers(
                tc.reference_summary(cell, seed, ref.fp8_dot), want)
            for fault in ("half_batch", "pod_local", "lr_low", "no_decay"):
                rec[fault] = tc.numbers(
                    tc.reference_summary(cell, seed, plant=fault), want)
            rec["altered"] = tc.numbers(tc.altered(want), want)
            rec["stale"] = tc.numbers(tc.stale(want), want)
        out.append(rec)
        emit(json.dumps(rec))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--program", action="store_true")
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args(argv)
    from bench import harness
    cell = harness.cell_spec(args.workload)
    import jax
    devices = jax.devices()
    need = cell["chips"] if args.program else 1
    if devices[0].platform != "tpu" or len(devices) < need:
        print(f"train_control: needs {need} TPU chip(s); nothing ran",
              file=sys.stderr)
        return 2
    readings(cell, args.seeds, args.program, args.control_seeds,
             emit=lambda s: print(s, flush=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
