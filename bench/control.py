"""Readings that the limits of `correct` are set from, for one cell.

    python3 bench/control.py --workload <cell> --seeds 1 2 3 ... \
        [--control-seeds 3] [--seconds 2]

In one process (set-up once), for each seed: the cell's set-up draws, a
short window at the cell's own load, and the same sample of answers that
a run compares.  It prints, per seed, the program's numbers and, for the
first `--control-seeds` seeds, the control's: the reference computed in
bfloat16 put in the program's place, compared by the same rule.  The
benchmark's own runs never run the control.  Needs the chip, like run.py.
"""
import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def readings(cell: dict, seeds, control_seeds: int, seconds: float,
             emit=print) -> list:
    """Program (and control) numbers per seed, as dicts."""
    import jax

    from bench import check as ck
    from bench import harness
    harness.use_compile_cache()
    entry = harness.load_module("entries", cell["traffic"]["entry"])
    out = []
    for i, seed in enumerate(seeds):
        run = harness.Run(cell, seed, seconds)
        jax.monitoring.register_event_listener(run.on_event)
        st = entry.setup(run)
        entry.window(run, st, None)
        answers, init = entry.items(run, st)
        del st
        rec = {"seed": seed, "calls": len(run.calls),
               "program": {k: v["value"] for k, v in ck.run_check(
                   run, answers, init).items()}}
        if i < control_seeds:
            rec["control"] = {k: v["value"] for k, v in ck.run_check(
                run, answers, init, control=True).items()}
        out.append(rec)
        emit(json.dumps(rec))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    from bench import harness
    cell = harness.cell_spec(args.workload)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU; nothing ran", file=sys.stderr)
        return 2
    readings(cell, args.seeds, args.control_seeds, args.seconds,
             emit=lambda s: print(s, flush=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
