"""Design points answered per second of query time: the (h, w) points
times the scenarios of every query, over the sum of query durations."""


def read(run):
    return run.points / sum(run.durations)
