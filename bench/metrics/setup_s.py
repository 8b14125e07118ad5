"""Seconds from process start to the first timed call: imports, scenario
bundle load (or build), compile-cache load and the warm-up call at the
cell's own shapes (host clock)."""


def read(run):
    return run.setup_s
