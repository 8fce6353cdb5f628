"""90th percentile of time to first token over every request of the window:
from the loop iteration that queued the request (its step-clock arrival)
to the host time its first token was emitted."""

from chipbench.gaps import percentile


def read(run):
    return percentile([w[0] - run.due[r] for r, w in run.emits.items() if w],
                      90)
