"""A cell, a configuration, a traffic mix and a per-layer metric added
as files only are found by name, with no edit to the harness."""
import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

PROBE = """
import json, sys
from bench.cell import Cell
from bench.trace import TraceData, load_metrics
cell = Cell.load("tiny_node.short")
data = TraceData(modules=[], host=[], window=(0.0, 1.0), steps=10)
print(json.dumps({
    "capacities": list(cell.capacities),
    "requests_per_unit": cell.requests_per_unit,
    "pool": [len(u) for u in cell.pool()],
    "metrics": sorted(m.NAME for m in load_metrics()),
    "read": data.metrics().get("steps_per_unit")}))
"""


def test_files_only_cell_config_and_metric(tmp_path):
    bench = tmp_path / "bench"
    shutil.copytree(os.path.join(ROOT, "bench"), bench,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    cfg = json.loads((bench / "configs" / "paper_edge.json").read_text())
    cfg.update(name="tiny_node", n_requests=50, capacities=[2, 3])
    (bench / "configs" / "tiny_node.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "two_streams.json").write_text(json.dumps(
        {"name": "two_streams", "streams_per_unit": 2, "pool_units": 3,
         "pool_seed": 5, "deadline_s": None, "churn": None}))
    (bench / "workloads" / "tiny_node.short.json").write_text(json.dumps(
        {"name": "tiny_node.short", "config": "tiny_node",
         "traffic": "two_streams", "policies": ["esff"], "check_units": 2,
         "chips": 1}))
    (bench / "metrics" / "steps_per_unit.py").write_text(
        'NAME = "steps_per_unit"\nUNIT = "events"\nLAYER = "device"\n'
        'MOVES = "sim_req_per_s"\n\n\ndef read(t):\n'
        '    return t.steps / t.units\n')
    out = subprocess.run(
        [sys.executable, "-c", PROBE], cwd=tmp_path, check=True,
        capture_output=True, text=True,
        env=dict(os.environ, PYTHONPATH=os.pathsep.join(
            [str(tmp_path), os.path.join(ROOT, "src")]))).stdout
    got = json.loads(out.strip().splitlines()[-1])
    assert got["capacities"] == [2, 3]
    assert got["requests_per_unit"] == 50 * 2 * 2
    assert got["pool"] == [2, 2, 2]
    assert "steps_per_unit" in got["metrics"]
    assert "device_idle_share" in got["metrics"]
    assert got["read"] == {"value": 10.0, "unit": "events"}
