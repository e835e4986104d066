"""A benchmark cell read from its files: the configuration, the traffic
mix and the workload, and the units of work they define.

A unit is one `repro.api.run_experiment` call on a set of request
streams, followed by `ResultSet.check()`. The traffic mix fixes a pool
of units, whose streams come from the configuration's sampler with the
seed ``[pool seed, unit, stream]``; a run's seed orders the pool. Every
run thus does the same work, in another order. This is the only module
that builds the program's inputs.

A configuration brings a mechanism of its own as new files, through
four optional keys:

* ``trace.sampler``: the module of `bench.traffic` whose
  ``stream(config, seed)`` makes a stream (default ``azure``); extra
  columns pass through to the program;
* ``spec``: options given to `repro.api.ExperimentSpec` as they are,
  after the fixed arguments; a workload's ``spec`` overrides the
  configuration's. An option the program does not know raises there;
* ``reference``: the module of `bench.reference` whose
  ``lane_stats(cell, streams, lane, r)`` is the plain reference
  (default ``node``, or ``cluster`` with ``n_nodes``);
* ``check_stats``: `ResultSet` metrics compared beside
  `bench.check.GAP_STATS`, which both sides must report.
"""
from __future__ import annotations

import importlib
import json
import os
from dataclasses import dataclass

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
# the `ExperimentSpec` arguments `Cell.spec` always sets itself
FIXED_SPEC = frozenset(("traces", "policies", "capacities", "queue_cap",
                        "stream", "prior", "deadlines", "cluster"))


def load_json(kind: str, name: str, root: str = BENCH) -> dict:
    """``<root>/<kind>/<name>.json``."""
    path = os.path.join(root, kind, f"{name}.json")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} file {path}")
    with open(path) as f:
        return json.load(f)


def churn_windows(churn: dict, n_nodes: int):
    """Per node ``None`` (always up) or the explicit ``(down, up)``
    windows of a node that is up for the first ``duty`` of every
    ``period_s``, with phases staggered evenly over the period, up to
    ``until_s``. Explicit windows keep the churn operand's shape fixed
    whatever the length of a stream."""
    period, duty = float(churn["period_s"]), float(churn["duty"])
    out = []
    for k in range(n_nodes):
        if k in churn["always_up"]:
            out.append(None)
            continue
        phase = k * period / n_nodes
        wins, start = [], phase - period
        while start < churn["until_s"]:
            down, up = start + duty * period, start + period
            if up > 0.0:
                wins.append((max(down, 0.0), up))
            start += period
        out.append(tuple(wins))
    return out


@dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    workload: dict

    def __post_init__(self):
        fixed = sorted(FIXED_SPEC & set(self.options))
        if fixed:
            raise ValueError(f"cell {self.name}: spec option(s) {fixed} "
                             f"are the harness's own arguments")

    @staticmethod
    def load(name: str, root: str = BENCH) -> "Cell":
        """The cell ``name`` from the files under ``root``."""
        wl = load_json("workloads", name, root)
        return Cell(name, load_json("configs", wl["config"], root),
                    load_json("traffic", wl["traffic"], root), wl)

    # ------------------------------------------------------------ traffic
    def stream(self, unit: int, k: int) -> dict:
        """Request stream ``k`` of pool unit ``unit``."""
        sampler = self.config["trace"].get("sampler", "azure")
        return importlib.import_module(f"bench.traffic.{sampler}").stream(
            self.config, [self.traffic["pool_seed"], int(unit), int(k)])

    def pool(self) -> list:
        """The streams of every unit of the pool."""
        return [[self.stream(u, k)
                 for k in range(self.traffic["streams_per_unit"])]
                for u in range(self.traffic["pool_units"])]

    def order(self, seed: int) -> list:
        """The pool's units in the order a run of ``seed`` runs them."""
        rng = np.random.default_rng([int(seed), 0x6F72646572])
        return [int(u) for u in rng.permutation(self.traffic["pool_units"])]

    # ---------------------------------------------------------- topology
    @property
    def clustered(self) -> bool:
        return "n_nodes" in self.config

    def cluster_kw(self) -> dict:
        """Keyword arguments of the cluster: the reference's and, less
        the churn format, `repro.api.ClusterSpec`'s."""
        cfg, wl = self.config, self.workload
        K = cfg["n_nodes"]
        churn = self.traffic.get("churn")
        return dict(n_nodes=K, node_capacity=cfg["node_capacity"],
                    router=wl["router"], net_delay=tuple(cfg["net_delay_s"]),
                    seed=cfg["router_seed"],
                    churn=None if churn is None else churn_windows(churn, K))

    @property
    def options(self) -> dict:
        """The program's options: the configuration's ``spec``, updated
        by the workload's."""
        return {**self.config.get("spec", {}), **self.workload.get("spec", {})}

    @property
    def reference(self) -> str:
        """The module of `bench.reference` that simulates this cell."""
        return self.config.get("reference",
                               "cluster" if self.clustered else "node")

    @property
    def check_stats(self) -> tuple:
        """The configuration's own statistics to compare."""
        return tuple(self.config.get("check_stats", ()))

    @property
    def capacities(self) -> tuple:
        if self.clustered:
            return (self.config["n_nodes"] * self.config["node_capacity"],)
        return tuple(self.config["capacities"])

    @property
    def requests_per_unit(self) -> int:
        """Requests x lanes: the simulated work of one unit."""
        return self.config["n_requests"] * len(self.lanes)

    def spec(self, streams=()):
        """The unit's `repro.api.ExperimentSpec` over ``streams``; with
        none, it only checks the options."""
        from repro.api import ArrayTrace, ClusterSpec, ExperimentSpec
        traces = [ArrayTrace.make(a, f"stream{k}")
                  for k, a in enumerate(streams)]
        kw = dict(traces=traces, policies=tuple(self.workload["policies"]),
                  capacities=self.capacities,
                  queue_cap=self.config["n_requests"], stream=True,
                  prior=self.config["prior_s"])
        if self.traffic.get("deadline_s") is not None:
            kw["deadlines"] = float(self.traffic["deadline_s"])
        if self.clustered:
            ck = self.cluster_kw()
            K = ck["n_nodes"]
            kw["cluster"] = [ClusterSpec(
                n_nodes=K, router=ck["router"],
                node_capacity=(ck["node_capacity"],) * K,
                net_delay=ck["net_delay"], seed=ck["seed"],
                churn=None if ck["churn"] is None else tuple(ck["churn"]))]
        return ExperimentSpec(**kw, **self.options)

    @property
    def lanes(self) -> list:
        """``(policy, capacity, stream index)`` of every lane of a
        unit."""
        return [(p, c, k) for p in self.workload["policies"]
                for k in range(self.traffic["streams_per_unit"])
                for c in self.capacities]


def lane_values(rs, policy: str, capacity: int, k: int, stats=()) -> dict:
    """The statistics of one lane of a unit's `ResultSet`, with those of
    ``stats`` that it reports."""
    which = dict(policy=policy, capacity=capacity,
                 trace=rs.coords["trace"][k])
    if "cluster" in rs.coords:
        which["cluster"] = rs.coords["cluster"][0]
    cell = rs.sel(**which)
    out = {m: np.asarray(cell.value(m)).item() for m in
           ("done", "cold_starts", "evictions", "mean_response",
            "mean_slowdown", "cold_time")}
    if "deadline_miss" in rs.data:
        out["deadline_miss"] = int(np.asarray(
            cell.value("deadline_miss")).sum())
    for m in stats:
        if m in rs.data:
            out[m] = np.asarray(cell.value(m)).sum().item()
    return out
