"""`keepalive_node.ram_grid`: a node whose resource is memory, added as
files only (`bench/configs/keepalive_node.json`, the `azure_mem`
sampler, the `keepalive` reference).

* the sampler's arrivals are `azure`'s for the same seed;
* the reference agrees with the program on the host, every lane of a
  unit;
* its float32 control is not correct on any pair of pool units a seed
  can draw (`PERF.md` section 2 records the smallest reading);
* the harness passes a sound run and fails a run whose megabytes
  evicted, or one policy's means, are altered.

Host only: nothing runs on a device."""
import itertools
import json

import numpy as np
import pytest

from bench import check
from bench import run as bench_run
from bench.cell import Cell, lane_values
from bench.reference import keepalive, node
from bench.traffic import azure, azure_mem

NAME = "keepalive_node.ram_grid"
LOAD = Cell.load


def small(n):
    cell = LOAD(NAME)
    cell.config = dict(cell.config, n_requests=n)
    return cell


def test_sampler_adds_footprints_to_the_same_arrivals():
    cell = LOAD(NAME)
    seed = [cell.traffic["pool_seed"], 3, 0]
    a, m = azure.stream(cell.config, seed), azure_mem.stream(cell.config,
                                                             seed)
    assert sorted(m) == sorted(a) + ["mem_mb"]
    for k, v in a.items():
        np.testing.assert_array_equal(m[k], v, err_msg=k)
    mem = m["mem_mb"]
    assert mem.dtype == np.int32 and mem.shape == (cell.config["n_functions"],)
    assert mem.min() >= 128 and mem.max() <= 512
    # a third at OpenWhisk's 128 MB floor, the median near 170 MB
    assert 0.2 < np.mean(mem == 128) < 0.5
    assert 140 <= np.median(mem) <= 200


def test_reference_matches_the_program_on_the_host():
    """Every lane of one unit at N=1,000: `done_gap` 0 and `group_gap`
    at most 1e-12."""
    import repro.api
    cell = small(1000)
    streams = cell.pool()[0]
    rs = repro.api.run_experiment(cell.spec(streams)).check()
    rows = [(lane, lane_values(rs, *lane, cell.check_stats),
             check.reference(cell, streams, lane)) for lane in cell.lanes]
    assert len(rows) == 21
    checks = check.compare(rows, cell.check_stats)
    assert checks["done_gap"]["value"] == 0
    assert checks["group_gap"]["value"] <= 1e-12
    assert set(checks) == {"done_gap", "group_gap"}
    # the smallest node works the mechanism in every policy
    for (policy, capacity, _), prog, ref in rows:
        if capacity == 8:
            assert prog["evict_mb"] > 0
            assert ref["multi_victim_starts"] > 0 and ref["mem_waits"] > 0


@pytest.fixture(scope="module")
def control_rows():
    """Per pool unit, every lane's (float32, float64) reference."""
    cell = LOAD(NAME)
    pool = cell.pool()
    return [[(lane, check.reference(cell, streams, lane, r=node.float32),
              check.reference(cell, streams, lane)) for lane in cell.lanes]
            for streams in pool]


@pytest.mark.parametrize("pair", list(itertools.combinations(range(5), 2)),
                         ids=lambda p: f"{p[0]}-{p[1]}")
def test_float32_control_fails(control_rows, pair):
    rows = control_rows[pair[0]] + control_rows[pair[1]]
    stats = LOAD(NAME).check_stats
    checks = check.compare(rows, stats)
    assert not check.passed(checks)
    assert checks["group_gap"]["value"] > check.LIMITS["group_gap"]


def harness(monkeypatch, capsys, wrap=None):
    import repro.api
    real = repro.api.run_experiment

    def broken(spec):
        rs = real(spec)
        return wrap(rs) if wrap else rs

    monkeypatch.setattr(repro.api, "run_experiment", broken)
    monkeypatch.setattr(bench_run, "REQUIRED_PLATFORM", "cpu")
    monkeypatch.setattr(bench_run.Cell, "load",
                        staticmethod(lambda n: small(300)))
    bench_run.main(["--workload", NAME, "--seed", "3000000019",
                    "--seconds", "0.5", "--trace", "0"])
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def evict_mb_altered(rs):
    rs.data["evict_mb"] = rs.data["evict_mb"] + 1
    return rs


def faascache_altered(rs):
    i = rs.coords["policy"].index("faascache")
    for m in ("mean_response", "mean_slowdown"):
        rs.data[m][i] *= 1.0 + 1e-6
    return rs


@pytest.mark.parametrize("wrap,correct", [
    (None, True), (evict_mb_altered, False), (faascache_altered, False)],
    ids=["sound", "evict_mb_altered", "faascache_altered"])
def test_harness_decides_correct(monkeypatch, capsys, wrap, correct):
    out = harness(monkeypatch, capsys, wrap)
    assert out["correct"] is correct
    assert set(out["checks"]) == {"done_gap", "group_gap"}


def test_reference_never_starts_what_does_not_fit():
    """A footprint above the node's memory waits for ever: nothing
    starts and nothing completes."""
    cell = small(50)
    streams = cell.pool()[0]
    big = dict(streams[0], mem_mb=np.full_like(streams[0]["mem_mb"], 4096))
    out = keepalive.simulate(big, "openwhisk", 8, 256)
    assert out["done"] == 0 and out["cold_starts"] == 0
    assert out["mem_waits"] == 50
