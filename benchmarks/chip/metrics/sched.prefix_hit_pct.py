"""Scheduler / KV pool: prompt tokens served from shared prefix blocks over
all prompt tokens admitted (ServeReport counters)."""


def read(run):
    rep = run.report
    total = rep.shared_prefill_tokens + rep.prefill_tokens
    return 100.0 * rep.shared_prefill_tokens / total if total else None
