"""Device ns of the sweep kernel per real element: configs x scenarios x
unpadded layer rows of every query in the traced window, so it counts
the same work whatever implements it. The kernel's device operations
are the HLO instructions whose names start with one of KERNEL_NAMES:
on a TPU the Pallas calls `dse_eval` and `dse_eval_batched` appear as
the custom calls `%dse_eval.N` and `%dse_eval_batched.N`."""

KERNEL_NAMES = ("%dse_eval",)


def read(run):
    t = run.trace
    if not t:
        return None
    s = sum(v for n, v in t["op_s"].items()
            if n.startswith(KERNEL_NAMES))
    return 1e9 * s / run.elements if s else None
