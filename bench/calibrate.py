"""Readings that the correctness limits of a cell are set from.

    python3 bench/calibrate.py --workload <cell> --seeds <n> \
        --control-seeds <m> [--fault <name> --fault-seeds <k>] \
        --seconds <s> [--out <file>]

In one process, after one set-up: `n` short windows of the timed path at
the cell's own load, each on its own seed, `m` windows with the control
in the program's place (the plain reference computed in bfloat16, see
each query kind's `control`), and `k` windows with a fault of `FAULTS`
planted in the program. Each window keeps and compares as many answers
as a run does, or `--check-sample` of them.
A capacity mix draws its queries from a fixed pool, so a window that
keeps the whole pool reads the largest number any seed can give. Prints
one JSON line per window with every compared number, then the lower
reading (largest over the program's seeds) and the upper one (smallest
over the control's) of each. The benchmark's own runs never run this.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def _early_stop(setattr):
    """The capacity bisection halves its bracket once fewer than it
    should: each answer agrees with its summary but is coarser."""
    from repro.core import search
    real = search.batched_bisect

    def early(probe_batch, brackets, iters=9):
        return real(probe_batch, brackets, iters - 1)
    setattr(search, "batched_bisect", early)


def _answer_high(setattr):
    """The capacity bisection answers the top of its last bracket, a
    rate it saw miss the SLO, in place of the bottom."""
    from repro.core import search
    real = search._BisectLane._finish

    def finish(self, q, res):
        real(self, self.hi, res)
    setattr(search._BisectLane, "_finish", finish)


# faults planted in the program for a reading; each takes a
# `setattr(owner, name, value)` that it patches the program with
FAULTS = {"early_stop": _early_stop, "answer_high": _answer_high}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--fault", choices=sorted(FAULTS))
    ap.add_argument("--fault-seeds", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--first-seed", type=int, default=4_100_000_000)
    ap.add_argument("--check-sample", type=int,
                    help="answers kept per window; a capacity mix's pool "
                         "size makes one window check the whole pool")
    ap.add_argument("--out")
    a = ap.parse_args(argv)
    from bench import harness
    cell = harness.Cell(a.workload, mix_override=(
        {"check_sample": a.check_sample} if a.check_sample else None))
    rows = []
    runs = ([("program", None)] * a.seeds
            + [("control", cell.kind.control)] * a.control_seeds
            + [(a.fault, None)] * (a.fault_seeds if a.fault else 0))
    for i, (who, replace) in enumerate(runs):
        seed = a.first_seed + i
        undo = []
        if who in FAULTS:
            def patch(owner, name, value):
                undo.append((owner, name, getattr(owner, name)))
                setattr(owner, name, value)
            FAULTS[who](patch)
        w = cell.window(seed, a.seconds, replace_kept=replace)
        for owner, name, value in undo:
            setattr(owner, name, value)
        checks = cell.check(w["kept"])
        row = {"who": who, "seed": seed, "queries": len(w["durations"]),
               "kept": len(w["kept"]),
               **{k: v["value"] for k, v in checks.items()}}
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {}
    for k in cell.mix["limits"]:
        prog = [r[k] for r in rows if r["who"] == "program"]
        ctrl = [r[k] for r in rows if r["who"] == "control"]
        fault = [r[k] for r in rows if r["who"] == a.fault]
        summary[k] = {"lower": max(prog) if prog else None,
                      "upper": min(ctrl) if ctrl else None,
                      a.fault or "fault": min(fault) if fault else None,
                      "limit": cell.mix["limits"][k]}
    print(json.dumps({"workload": a.workload, "readings": summary}),
          flush=True)
    if a.out:
        with open(a.out, "w") as f:
            for r in rows:
                f.write(json.dumps(r) + "\n")
            f.write(json.dumps({"readings": summary}) + "\n")


if __name__ == "__main__":
    main()
