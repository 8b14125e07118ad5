"""Share of the step's op self time in the Uno cross-pod exchange, in
percent: ops whose `op_name` (read from the trace by `bench/stages.py`)
lies in the exchange's `shard_map` region (`make_uno_grad_sync`: flatten,
int8 quant, RS encode, the `ppermute`s, RS decode, dequant, unflatten)
over all the step's op self time, averaged over the chips.  XLA names a
fusion by its root, so a fusion that joins exchange and optimizer ops
counts where its root lies."""
REGION = "/shard_map/"


def read(run):
    s = run.trace_summary
    names = (s or {}).get("op_names")
    if not names or not s["scan_op_s"]:
        return None
    ex = sum(v for k, v in s["scan_ops"].items()
             if REGION in names.get(k, ""))
    return 100.0 * ex / s["scan_op_s"]
