"""Mean ms per query lowering the network to GEMM rows (the benchmark's
`lower` span around `core.cnn_zoo` / `scenarios.matrix`)."""


def read(run):
    return run.span_mean_ms("lower")
