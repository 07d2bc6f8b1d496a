"""Seconds from the start of the run to the first timed query: imports,
backend start, compile-cache loads, native builds and warm-up."""


def read(run):
    return run.setup_s
