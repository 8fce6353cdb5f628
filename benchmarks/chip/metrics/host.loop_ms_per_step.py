"""Serve loop (host): the host's serial work per decode step outside calls
that wait on the device, from the program's own counters
(``ServeReport.host_s``): the per-lane emission after each step (``emit``:
appending tokens, ``slot_done``, finishing requests) and the rest of the
loop outside any phase (``loop``: its own Python and its dispatches), over
the decode steps. A program without ``host_s`` reads nothing."""


def read(run):
    host = getattr(run.report, "host_s", None)
    if not host or not run.report.steps:
        return None
    return 1e3 * (host["emit"][0] + host["loop"][0]) / run.report.steps
