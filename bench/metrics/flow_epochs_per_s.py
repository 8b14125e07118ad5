"""Flow-epochs per second of the window: every scan call issued, all the
flow-epochs they ran over the span from the first issue to the last
completion (host clock; each call ends in `block_until_ready`)."""


def read(run):
    if not run.calls:
        return None
    work = sum(c[2] for c in run.calls)
    span = run.calls[-1][1] - run.calls[0][0]
    return work / span
