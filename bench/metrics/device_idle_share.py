"""Share of the traced window in which no operation ran on the device:
1 - (union of device op intervals) / (traced window), in percent."""


def read(run):
    s = run.trace_summary
    if not s or not s["window_s"]:
        return None
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"])
