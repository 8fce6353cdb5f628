"""Serve loop (host): device idle time while the host pulls a decode step's
outputs (``host:step_sync``, the program's span around the pulls of
``serve_step``'s tokens, keys and done flags, and the
``host:device_to_host`` copies inside it), per decode step
(``jit_serve_step`` executions in the traced window).

The trace reduction books each idle gap to the innermost host span over its
middle. ``host:device_to_host`` is JAX's span for any copy of a device
array to the host, so it also holds the few first-token pulls of admissions
(``int()`` of the sampled token): one per request against one step pull per
decode step. A program without the ``host:step_sync`` span (no ``host_s``
in its report) reads nothing."""


def read(run):
    if run.trace is None or not getattr(run.report, "host_s", None):
        return None
    mods = run.trace["modules"]
    if "jit_serve_step" not in mods:
        return None
    idle = dict(run.trace["idle_gaps"])
    secs = idle.get("host:step_sync", 0.0) + idle.get("host:device_to_host",
                                                      0.0)
    return 1e3 * secs / mods["jit_serve_step"][1]
