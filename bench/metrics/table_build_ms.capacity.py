"""Mean ms per query building the cost tables: lattice lowering, the one
fused kernel dispatch and table assembly (`table_build` span)."""


def read(run):
    return run.span_mean_ms("table_build")
