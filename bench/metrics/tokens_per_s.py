"""Trained tokens per second of the window: every step issued, all their
tokens over the span from the first issue to the last completion (host
clock; each step ends in `block_until_ready` on its metrics)."""


def read(run):
    if not run.calls:
        return None
    return sum(c[2] for c in run.calls) / (run.calls[-1][1] -
                                           run.calls[0][0])
