"""Scheduler: gaps between tokens that hold an admission prefill (tail or
whole prompt) over all gaps, classified by the order of first tokens."""


def read(run):
    if not run.gaps.kinds:
        return None
    return 100.0 - run.gaps.share("plain")
