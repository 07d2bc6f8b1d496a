"""Design points whose SLO capacity was answered, per second of query
time: the points of every query over the sum of query durations."""


def read(run):
    return run.points / sum(run.durations)
