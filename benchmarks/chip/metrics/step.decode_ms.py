"""Model step: device time of one decode step, the serve step program
(``jit_serve_step``) averaged over its executions in the traced window."""


def read(run):
    if run.trace is None or "jit_serve_step" not in run.trace["modules"]:
        return None
    secs, n = run.trace["modules"]["jit_serve_step"]
    return 1e3 * secs / n
