"""95th percentile of the time between tokens over every gap between two
successive tokens of every request in the window (none left out)."""

from chipbench.gaps import percentile


def read(run):
    return percentile(run.gaps.values, 95) if len(run.gaps.values) else None
