"""A cell, a configuration, a traffic mix, a per-layer metric, a
request sampler and a reference added as files only are found by name,
with no edit to the harness.

The files go into a copy of ``bench/``; one process imports that copy
and reads every case, and each case asserts its part."""
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# a sampler that adds a per-function column to the default streams
WITH_MEMORY = '''
import numpy as np
from bench.traffic import azure


def stream(config, seed):
    out = azure.stream(config, seed)
    rng = np.random.default_rng([*seed, 1])
    out["mem_mb"] = rng.choice([128.0, 512.0], config["n_functions"])
    return out
'''
# a sampler no run may call before the program took the options
NO_STREAM = '''
def stream(config, seed):
    raise RuntimeError("a stream was made")
'''
# a reference that records each call and reports one more statistic
TINY_REF = '''
from bench.reference import node

CALLS = []


def lane_stats(cell, streams, lane, r=float):
    CALLS.append([list(lane), cell.options])
    return dict(node.lane_stats(cell, streams, lane, r), mb_evicted=0.0)
'''

PROBE = """
import contextlib, io, json
import bench.run as run
from bench.cell import Cell
from bench.trace import TraceData, load_metrics

run.REQUIRED_PLATFORM = "cpu"


def main(workload):
    buf = io.StringIO()
    try:
        with contextlib.redirect_stdout(buf):
            run.main(["--workload", workload, "--seed", "2718281828459",
                      "--seconds", "0.1", "--trace", "0"])
    except Exception as e:
        return {"error": type(e).__name__, "message": str(e)}
    return json.loads(buf.getvalue().strip().splitlines()[-1])


cell = Cell.load("tiny_node.short")
data = TraceData(modules=[], host=[], window=(0.0, 1.0), steps=10)
plugin = Cell.load("tiny_plugin.short")
stream = plugin.stream(0, 0)
spec = plugin.spec(plugin.pool()[0])
out = {
    "capacities": list(cell.capacities),
    "requests_per_unit": cell.requests_per_unit,
    "pool": [len(u) for u in cell.pool()],
    "metrics": sorted(m.NAME for m in load_metrics()),
    "read": data.metrics().get("steps_per_unit"),
    "columns": sorted(stream),
    "mem_mb": len(stream["mem_mb"]),
    "spec_columns": sorted(k for k, _ in spec.traces[0].arrays_in),
    "options": {"lane_chunk": spec.lane_chunk, "threshold": spec.threshold},
    "plugin_run": main("tiny_plugin.short"),
}
from bench.reference import tiny_ref
out["calls"] = list(tiny_ref.CALLS)
out["missing_run"] = main("tiny_missing.short")
out["unknown_run"] = main("tiny_unknown.short")
try:
    Cell.load("tiny_fixed.short")
    out["fixed"] = None
except ValueError as e:
    out["fixed"] = str(e)
print(json.dumps(out))
"""


def write(path, doc):
    path.write_text(doc if isinstance(doc, str) else json.dumps(doc))


@pytest.fixture(scope="module")
def got(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("checkout")
    bench = tmp / "bench"
    shutil.copytree(os.path.join(ROOT, "bench"), bench,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    base = json.loads((bench / "configs" / "paper_edge.json").read_text())
    base.update(n_requests=50, capacities=[2, 3])
    configs = {
        "tiny_node": {},
        "tiny_plugin": dict(reference="tiny_ref",
                            check_stats=["max_response"],
                            spec={"lane_chunk": 4, "threshold": 0.2}),
        "tiny_missing": dict(reference="tiny_ref",
                             check_stats=["mb_evicted"]),
        "tiny_unknown": dict(spec={"node_ram_mb": 4096}),
        "tiny_fixed": {},
    }
    samplers = {"tiny_plugin": "with_memory", "tiny_missing": "with_memory",
                "tiny_unknown": "no_stream"}
    workloads = {"tiny_plugin": {"lane_chunk": 2},
                 "tiny_fixed": {"queue_cap": 10}}
    for name, extra in configs.items():
        trace = dict(base["trace"], sampler=samplers.get(name, "azure"))
        write(bench / "configs" / f"{name}.json",
              dict(base, name=name, trace=trace, **extra))
        write(bench / "workloads" / f"{name}.short.json",
              {"name": f"{name}.short", "config": name,
               "traffic": "two_streams", "policies": ["esff"],
               "check_units": 2, "chips": 1,
               "spec": workloads.get(name, {})})
    write(bench / "traffic" / "two_streams.json",
          {"name": "two_streams", "streams_per_unit": 2, "pool_units": 3,
           "pool_seed": 5, "deadline_s": None, "churn": None})
    write(bench / "traffic" / "with_memory.py", WITH_MEMORY)
    write(bench / "traffic" / "no_stream.py", NO_STREAM)
    write(bench / "reference" / "tiny_ref.py", TINY_REF)
    write(bench / "metrics" / "steps_per_unit.py",
          'NAME = "steps_per_unit"\nUNIT = "events"\nLAYER = "device"\n'
          'MOVES = "sim_req_per_s"\n\n\ndef read(t):\n'
          '    return t.steps / t.units\n')
    out = subprocess.run(
        [sys.executable, "-c", PROBE], cwd=tmp, check=True,
        capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 PYTHONPATH=os.pathsep.join(
                     [str(tmp), os.path.join(ROOT, "src")]))).stdout
    return json.loads(out.strip().splitlines()[-1])


def cell_config_and_metric(got):
    assert got["capacities"] == [2, 3]
    assert got["requests_per_unit"] == 50 * 2 * 2
    assert got["pool"] == [2, 2, 2]
    assert "steps_per_unit" in got["metrics"]
    assert "device_idle_share" in got["metrics"]
    assert got["read"] == {"value": 10.0, "unit": "events"}


def sampler_adds_a_column(got):
    assert got["columns"] == ["arrival", "cold_start", "evict", "exec_time",
                              "fn_id", "mem_mb"]
    assert got["mem_mb"] == 200
    assert "mem_mb" in got["spec_columns"]


def spec_carries_the_options(got):
    """The workload's ``lane_chunk`` overrides the configuration's."""
    assert got["options"] == {"lane_chunk": 2, "threshold": 0.2}


def run_reaches_the_reference(got):
    run = got["plugin_run"]
    assert run["correct"] is True, run
    assert set(run["checks"]) == {"done_gap", "group_gap"}
    assert got["calls"]
    assert all(options == {"lane_chunk": 2, "threshold": 0.2}
               for _, options in got["calls"])


def unknown_option_fails_before_any_stream(got):
    run = got["unknown_run"]
    assert run["error"] == "TypeError"
    assert "node_ram_mb" in run["message"]


def fixed_argument_fails_at_load(got):
    assert "queue_cap" in got["fixed"]


def missing_statistic_is_not_correct(got):
    run = got["missing_run"]
    assert run["correct"] is False
    assert run["checks"]["missing.mb_evicted"] == {"value": 4 * 2,
                                                   "limit": 0}


CASES = [cell_config_and_metric, sampler_adds_a_column,
         spec_carries_the_options, run_reaches_the_reference,
         unknown_option_fails_before_any_stream,
         fixed_argument_fails_at_load, missing_statistic_is_not_correct]


@pytest.mark.parametrize("case", CASES, ids=lambda c: c.__name__)
def test_files_only_cell_config_and_metric(got, case):
    case(got)
