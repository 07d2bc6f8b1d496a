"""Bisection probes per query, from the program's `search.probes`
counter over the window."""


def read(run):
    n = run.counters.get("search.probes", 0)
    return n / run.queries if n else None
