"""Share of the traced window in which no operation ran on a chip,
averaged over the chips, in percent: `device_idle_share`'s reduction,
named apart because in a training cell it moves `tokens_per_s`."""
from bench.harness import load_module


def read(run):
    return load_module("metrics", "device_idle_share").read(run)
