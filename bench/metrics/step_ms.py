"""Device milliseconds of the training step's executable (`jit_step`) per
step, averaged over the chips: `scan_ms_per_epoch`'s reduction (a step
is one call of one "epoch"), named apart because it moves
`tokens_per_s`."""
from bench.harness import load_module


def read(run):
    return load_module("metrics", "scan_ms_per_epoch").read(run)
