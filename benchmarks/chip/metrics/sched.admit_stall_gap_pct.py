"""Scheduler: gaps between tokens that hold an admission prefill (tail or
whole prompt) over all gaps, counted by the program where the work happens
(``ServeReport.gap_kinds``): the in-program twin of ``sched.stall_gap_pct``,
which orders first tokens on the harness's clock. A program without
``gap_kinds`` reads nothing."""


def read(run):
    kinds = getattr(run.report, "gap_kinds", None)
    n = sum(kinds.values()) if kinds else 0
    if not n:
        return None
    return 100.0 - 100.0 * kinds["plain"] / n
