"""Process start to the first timed step: runtime start, weights, compile
or compile-cache load, and warm-up of every shape the window runs."""


def read(run):
    return run.setup_s
