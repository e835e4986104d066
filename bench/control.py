"""Readings of the precision control: the reference computed in float32
put in the program's place, compared with the float64 reference by
`bench.check` on the units a run of each seed would draw.

    python3 bench/control.py --workload paper_edge.fig5 --seeds 1 2 3

The harness's own runs never run it; `PERF.md` gives its readings, the
upper ones the limits were set from.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import check  # noqa: E402
from bench.cell import Cell  # noqa: E402
from bench.reference import node  # noqa: E402


def readings(cell, seeds: list):
    """``(seed, checks)`` of each seed; each unit's lanes are computed
    once."""
    pool, rows = cell.pool(), {}
    for seed in seeds:
        units = check.draw(len(pool), cell.workload["check_units"], seed)
        for u in units:
            if u not in rows:
                rows[u] = [(lane,
                            check.reference(cell, pool[u], lane,
                                            r=node.float32),
                            check.reference(cell, pool[u], lane))
                           for lane in cell.lanes]
        yield seed, check.compare([r for u in units for r in rows[u]],
                                  cell.check_stats)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = Cell.load(args.workload)
    for seed, checks in readings(cell, args.seeds):
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "checks": checks}), flush=True)


if __name__ == "__main__":
    main()
