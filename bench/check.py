"""The comparison that decides a run's ``correct``.

After the window closes, units drawn from the seed among the pool units
the window ran are run again, every lane of them, through the plain
reference (the module of `bench.reference` the cell names). A lane is
one policy x capacity x stream of a unit. Two numbers, and a third
where it occurs, are compared with their limits:

* ``done_gap``: the largest difference, over the lanes, between the
  requests the program completed and those the reference completed.
  Exact: limit 0.
* ``group_gap``: a lane's gap is the largest relative difference
  between the program and the reference in mean response, mean
  slowdown, cold-start time, cold starts, evictions and deadline
  misses. A group is the lanes that share one policy, one capacity or
  one stream; the number is the largest, over the groups, of the
  group's smallest lane gap. A configuration's own ``check_stats`` join
  these statistics in a lane's gap.
* ``missing.<statistic>``, only where it occurs: the lanes in which the
  program or the reference lacks one of the configuration's
  ``check_stats``. Limit 0: such a statistic is never skipped.

Within a group the smallest gap, because the schedule is
ill-conditioned: a change in the last bit of an event time can flip one
scheduling decision and move a lane's statistics by percent, and the
chip, whose float64 is emulated, makes such flips in some lanes. Across
groups the largest, so that a fault in one policy kernel, one capacity
or one stream, which moves every lane of its group, fails. A lower
precision rounds every lane, so it fails too. `PERF.md` gives the
readings the limits were set from.
"""
from __future__ import annotations

import importlib

import numpy as np

LIMITS = {"done_gap": 0, "group_gap": 1e-9}
GAP_STATS = ("mean_response", "mean_slowdown", "cold_time", "cold_starts",
             "evictions", "deadline_miss")


def draw(n_units: int, k: int, seed: int) -> list:
    """``k`` distinct units of ``n_units`` drawn from the seed."""
    rng = np.random.default_rng([int(seed), 0x636865636B])
    return sorted(int(u) for u in rng.choice(n_units, size=min(k, n_units),
                                             replace=False))


def reference(cell, streams: list, lane: tuple, r=float) -> dict:
    """The reference's statistics of ``lane = (policy, capacity, k)``
    of a unit over ``streams``; ``r`` is the arithmetic
    (`float`, or `bench.reference.node.float32` for the control)."""
    module = importlib.import_module(f"bench.reference.{cell.reference}")
    return module.lane_stats(cell, streams, lane, r)


def lane_gap(prog: dict, ref: dict, stats=()) -> float:
    """Largest relative difference of the lane's statistics: those of
    `GAP_STATS` and ``stats`` that both sides report (`compare` fails a
    lane that lacks one of ``stats``)."""
    gaps = []
    for m in GAP_STATS + tuple(stats):
        if m in prog and m in ref:
            p, q = float(prog[m]), float(ref[m])
            gaps.append(abs(p - q) / max(abs(q), 1e-300) if p != q else 0.0)
    return max(gaps)


def group_gaps(rows: list, stats=()) -> dict:
    """``{(coordinate, value): smallest lane gap}`` over ``rows`` of
    ``(lane, program, reference)``."""
    out = {}
    for (policy, capacity, k), prog, ref in rows:
        gap = lane_gap(prog, ref, stats)
        for key in (("policy", policy), ("capacity", capacity),
                    ("stream", k)):
            out[key] = min(out.get(key, gap), gap)
    return out


def compare(rows: list, stats=()) -> dict:
    """``{name: {"value", "limit"}}`` over ``rows`` of
    ``(lane, program statistics, reference statistics)``; ``stats``
    are the configuration's own."""
    done = max(abs(int(p["done"]) - int(q["done"])) for _, p, q in rows)
    gap = max(group_gaps(rows, stats).values())
    checks = {"done_gap": {"value": done, "limit": LIMITS["done_gap"]},
              "group_gap": {"value": gap, "limit": LIMITS["group_gap"]}}
    for m in stats:
        missing = sum(m not in p or m not in q for _, p, q in rows)
        if missing:
            checks[f"missing.{m}"] = {"value": missing, "limit": 0}
    return checks


def passed(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
