"""Mean ms per query in the capacity search: bisection, trace sampling
and the replay engine (`search` span)."""


def read(run):
    return run.span_mean_ms("search")
