"""Share of the scan module's device op time spent in gathers, scatters
and segment sums, in percent.

The rule, on the HLO text the trace names each op by: an unfused
`gather`/`scatter`, or a fusion that XLA's TPU backend emits as a custom
kernel (`kind=kCustom`) and that reads an integer index operand, which is
how the chip runs fused gathers, scatter-adds and the segment sums built
on them."""
import re

_PLAIN = re.compile(r"^%\S+ = .*? (gather|scatter)\(")
_FUSED = re.compile(r"^%\S+ = .*? fusion\((.*)\), kind=kCustom")
_INDEX = re.compile(r"\b[su](8|16|32|64)\[")


def is_gather_scatter(text: str) -> bool:
    if _PLAIN.match(text):
        return True
    m = _FUSED.match(text)
    return bool(m and _INDEX.search(m.group(1)))


def read(run):
    s = run.trace_summary
    if not s or not s["scan_op_s"]:
        return None
    gs = sum(v for k, v in s["scan_ops"].items() if is_gather_scatter(k))
    return 100.0 * gs / s["scan_op_s"]
