"""Device milliseconds of the scan executable per epoch: the device time
of the cell's scan module (`_grid_core` or `_simulate`, by its jit name)
in the traced slice, over the epochs those calls ran."""


def read(run):
    s = run.trace_summary
    epochs = sum(c[3] for c in run.traced_calls)
    if not s or not s["scan_s"] or not epochs:
        return None
    return 1e3 * s["scan_s"] / epochs
