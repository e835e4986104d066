"""The comparison that decides ``correct`` fails its control and every
fault a cell can have, and passes a sound run.

The harness runs here on the CPU at a small size: the chip check is
skipped by setting ``REQUIRED_PLATFORM`` and the cell's streams are cut
to ``N`` requests. The timed path is broken underneath by wrapping
`repro.api.run_experiment`. Besides the benchmark's cell, two fixture
cells under ``data/cells`` drive the harness's cluster path."""
import json
import os

import numpy as np
import pytest

from bench import check
from bench import run as bench_run
from bench.cell import BENCH, Cell, lane_values
from bench.reference import node

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "data", "cells")
ROOTS = {"paper_edge.fig5": BENCH, "test_cluster.dynamic": FIXTURES,
         "test_cluster.static": FIXTURES}
CELLS = tuple(ROOTS)
N = 200
LOAD = Cell.load


def small(name, n=N):
    cell = LOAD(name, ROOTS[name])
    cell.config = dict(cell.config, n_requests=n)
    return cell


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_float32_control_fails(name, seed):
    """The reference in float32 in the program's place is not correct,
    in every group of lanes."""
    cell = small(name, 400)
    cell.traffic = dict(cell.traffic, pool_seed=seed)
    streams = cell.pool()[0]
    rows = [(lane, check.reference(cell, streams, lane, r=node.float32),
             check.reference(cell, streams, lane))
            for lane in cell.lanes]
    checks = check.compare(rows)
    assert not check.passed(checks)
    assert min(check.group_gaps(rows).values()) > check.LIMITS["group_gap"]


def zero_state(rs):
    """A step that returns its state unchanged: nothing simulated."""
    for v in rs.data.values():
        v[...] = 0
    return rs


def alter(rs, **which):
    """The mean response and slowdown of the lanes at ``which`` (every
    lane where empty) off by one part in 1e6 where they are produced."""
    index = [slice(None)] * len(rs.dims)
    for dim, value in which.items():
        index[rs.dims.index(dim)] = list(rs.coords[dim]).index(value)
    for m in ("mean_response", "mean_slowdown"):
        rs.data[m][tuple(index)] *= 1.0 + 1e-6
    return rs


def half_batch(spec):
    """Half of every stream left out; the means over the rest."""
    from dataclasses import replace
    return replace(spec, traces=[t.head(t.n_requests // 2)
                                 for t in spec.traces])


def harness(monkeypatch, capsys, name, wrap=None, before=None):
    import repro.api
    real = repro.api.run_experiment

    def broken(spec):
        rs = real(before(spec) if before else spec)
        return wrap(rs) if wrap else rs

    monkeypatch.setattr(repro.api, "run_experiment", broken)
    monkeypatch.setattr(bench_run, "REQUIRED_PLATFORM", "cpu")
    monkeypatch.setattr(bench_run.Cell, "load",
                        staticmethod(lambda n: small(n)))
    bench_run.main(["--workload", name, "--seed", "11", "--seconds", "0.5",
                    "--trace", "0"])
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(monkeypatch, capsys, name):
    out = harness(monkeypatch, capsys, name)
    assert out["correct"] is True
    assert list(out)[-1] == "checks"
    assert out["device"]["platform"] == "cpu"


def trace_altered(rs):
    """The lanes of the unit's last stream altered."""
    return alter(rs, trace=rs.coords["trace"][-1])


FAULTS = {
    "state_unchanged": dict(wrap=zero_state),
    "half_batch": dict(before=half_batch),
    "answer_altered": dict(wrap=alter),
    "one_policy_altered": dict(wrap=lambda rs: alter(rs, policy="esff")),
    "one_capacity_altered": dict(
        wrap=lambda rs: alter(rs, capacity=rs.coords["capacity"][0])),
    "one_stream_altered": dict(wrap=trace_altered),
}


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("fault", list(FAULTS))
def test_fault_is_not_correct(monkeypatch, capsys, name, fault):
    out = harness(monkeypatch, capsys, name, **FAULTS[fault])
    assert out["correct"] is False


def test_a_group_needs_one_sound_lane():
    """A group passes while one of its lanes agrees; the number is the
    worst group's."""
    sound, off = {"done": 5, "mean_response": 1.0}, {
        "done": 5, "mean_response": 1.1}
    rows = [(("esff", 8, 0), off, sound), (("esff", 16, 0), sound, sound),
            (("sff", 8, 0), sound, sound), (("sff", 16, 0), sound, sound)]
    assert check.compare(rows)["group_gap"]["value"] == 0.0
    rows[1] = (("esff", 16, 0), off, sound)
    assert check.compare(rows)["group_gap"]["value"] == pytest.approx(0.1)


def test_lane_values_read_the_drawn_lane():
    """`lane_values` reads the lane that `Cell.lanes` names."""
    import repro.api
    cell = small("paper_edge.fig5", 100)
    cell.workload = dict(cell.workload, policies=["esff", "sff"])
    streams = cell.pool()[0]
    rs = repro.api.run_experiment(cell.spec(streams))
    for lane in cell.lanes:
        prog = lane_values(rs, *lane)
        ref = check.reference(cell, streams, lane)
        assert prog["cold_starts"] == ref["cold_starts"]
        assert np.isclose(prog["mean_response"], ref["mean_response"],
                          rtol=1e-12)
