"""Compilations inside the measured window: JAX's compile-cache events
(a hit or a miss each means a compile request) plus grid executables the
sweep service traced (`sweeps.grid_traces()` delta).  Should read 0."""


def read(run):
    if "window_compiles" not in run.counters:
        return None
    return run.counters["window_compiles"] + \
        run.counters.get("window_grid_traces", 0)
