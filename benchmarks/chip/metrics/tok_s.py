"""Tokens completed per second: every token the window emitted, over the
window's wall time (host clock around the one serve call)."""


def read(run):
    return sum(len(w) for w in run.emits.values()) / run.window_s
