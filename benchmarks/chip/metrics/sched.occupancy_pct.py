"""Scheduler: live slot-steps over all slot-steps of the window's decode
steps (ServeReport: served tokens per request, steps, slots)."""


def read(run):
    rep = run.report
    if not rep.steps:
        return None
    live = sum(len(r.tokens) - r.prompt_len - 1 for r in rep.results)
    return 100.0 * live / (rep.steps * rep.slots)
