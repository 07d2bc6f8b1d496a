"""Mean ms per query normalizing the networks' grids under the query's
weights and taking the Pareto set (the benchmark's `normalize` span
around `core.dse.robust_serving_config`)."""


def read(run):
    return run.span_mean_ms("normalize")
