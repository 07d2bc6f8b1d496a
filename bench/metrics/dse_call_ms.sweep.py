"""Mean ms per query in the DSE driver and its dispatch: `core.dse` into
`kernels.ops`, padding, the kernel, the sync and the copy to the host
(the benchmark's `dse_call` span)."""


def read(run):
    return run.span_mean_ms("dse_call")
