"""Share of the layer rows the sweep kernels walked that were padding,
over the window: 1 - `sweep.layer_rows` / `sweep.layer_rows_padded`, the
program's counters of the real rows given to `kernels.ops.sweep` /
`sweep_batched` and of the rows the kernels walk once each table is
padded to its scenario batch and its 128-row chunks. A program without
these counters reads nothing."""


def read(run):
    real = run.counters.get("sweep.layer_rows", 0)
    walked = run.counters.get("sweep.layer_rows_padded", 0)
    return 1.0 - real / walked if walked else None
