"""The step executable's share of the chips' bf16 peak, in percent: the
model FLOPs of the traced steps (`bench/train_work.py`) over chips x
peak x the step's device time (averaged over the chips).  It bounds
every kernel's gain: a kernel taken off the step leaves its roofline
silent, and only this share shows what the step gained."""
from bench import peaks, train_work


def read(run):
    s = run.trace_summary
    steps = sum(c[3] for c in run.traced_calls)
    if not s or not s["scan_s"] or not steps:
        return None
    cell = run.cell
    return 100.0 * train_work.step_flops(cell) * steps / (
        train_work.chips(cell) * s["scan_s"] *
        peaks.peak(peaks.device_kind(), "bf16_flops"))
