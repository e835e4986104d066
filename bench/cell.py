"""A benchmark cell read from its files: the configuration, the traffic
mix and the workload, and the units of work they define.

A unit is one `repro.api.run_experiment` call on a set of request
streams, followed by `ResultSet.check()`. The traffic mix fixes a pool
of units, whose streams come from `bench.traffic.azure` with the seed
``[pool seed, unit, stream]``; a run's seed orders the pool. Every run
thus does the same work, in another order. This is the only module
that builds the program's inputs.
"""
from __future__ import annotations

import json
import os
from dataclasses import dataclass

import numpy as np

from bench.traffic.azure import sample

BENCH = os.path.dirname(os.path.abspath(__file__))


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

    @staticmethod
    def load(name: str, root: str = BENCH) -> "Cell":
        """The cell ``name`` from the files under ``root``."""
        wl = load_json("workloads", name, root)
        return Cell(name, load_json("configs", wl["config"], root),
                    load_json("traffic", wl["traffic"], root), wl)

    # ------------------------------------------------------------ traffic
    def stream(self, unit: int, k: int) -> dict:
        """Request stream ``k`` of pool unit ``unit``."""
        c = self.config["trace"]
        return sample(self.config["n_functions"], self.config["n_requests"],
                      utilization=c["utilization"],
                      capacity_ref=c["capacity_ref"], zipf_a=c["zipf_a"],
                      exec_median=c["exec_median_s"],
                      exec_sigma=c["exec_sigma"],
                      jitter_sigma=c["jitter_sigma"],
                      cold_range=tuple(c["cold_range_s"]),
                      burst_frac=c["burst_frac"],
                      diurnal_amp=c["diurnal_amp"],
                      seed=[self.traffic["pool_seed"], int(unit), int(k)])

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
    def capacities(self) -> tuple:
        if self.clustered:
            return (self.config["n_nodes"] * self.config["node_capacity"],)
        return tuple(self.config["capacities"])

    @property
    def requests_per_unit(self) -> int:
        """Requests x lanes: the simulated work of one unit."""
        return self.config["n_requests"] * len(self.lanes)

    def spec(self, streams: list):
        """The unit's `repro.api.ExperimentSpec` over ``streams``."""
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
        return ExperimentSpec(**kw)

    @property
    def lanes(self) -> list:
        """``(policy, capacity, stream index)`` of every lane of a
        unit."""
        return [(p, c, k) for p in self.workload["policies"]
                for k in range(self.traffic["streams_per_unit"])
                for c in self.capacities]


def lane_values(rs, policy: str, capacity: int, k: int) -> dict:
    """The statistics of one lane of a unit's `ResultSet`."""
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
    return out
