"""Model step: device time of whole and tail prefills (``jit_prefill``,
``jit_prefill_tail``) per 1,000 prompt tokens they computed."""


def read(run):
    if run.trace is None or not run.report.prefill_tokens:
        return None
    mods = run.trace["modules"]
    secs = sum(mods[m][0] for m in ("jit_prefill", "jit_prefill_tail")
               if m in mods)
    if not secs:
        return None
    return 1e3 * secs / (run.report.prefill_tokens / 1e3)
