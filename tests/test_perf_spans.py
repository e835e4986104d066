"""The runner's performance spans and the device loop-step counter.

A `jax.profiler` capture of `run_experiment` + `ResultSet.check()`
holds the ``repro.*`` host spans with their stats, and each launch
reports ``loop_steps``: the lane-stacked iterations its event loop
ran, SEG per segment. Neither changes a simulated result."""
import glob
import importlib.util
import json
import math
import os

import jax
import numpy as np
import pytest

from repro.api import ExperimentSpec, SyntheticTrace, run_experiment
from repro.core.jax_engine import SEG

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = SyntheticTrace.make(n_functions=12, n_requests=200, seed=5,
                          utilization=0.3)
SPEC = dict(traces=[SRC], policies=("esff",), capacities=(2, 3, 4),
            queue_cap=256, stream=True, lane_chunk=2)
SPANS = ("repro.run_experiment", "repro.lower", "repro.dispatch",
         "repro.fetch", "repro.assemble", "repro.check")


def profiled(spec, trace_dir):
    """Run ``spec`` and its check under the profiler: the ResultSet
    and the ``repro.*`` host events as (name, stats) pairs."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        rs = run_experiment(spec).check()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(trace_dir / "**" / "*.xplane.pb"),
                      recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    events = [(e.name, dict(e.stats)) for plane in pd.planes
              for line in plane.lines for e in line.events
              if e.name.startswith("repro.")]
    return rs, events


def test_a_profiled_run_holds_the_runner_spans(tmp_path):
    spec = ExperimentSpec(**SPEC)
    plain = run_experiment(spec)
    rs, events = profiled(spec, tmp_path)
    names = [n for n, _ in events]
    chunks = len(rs.meta["loop_steps"])
    assert chunks == 2
    assert {n: names.count(n) for n in SPANS} == {
        "repro.run_experiment": 1, "repro.lower": 1,
        "repro.dispatch": chunks, "repro.fetch": chunks,
        "repro.assemble": 1, "repro.check": 1}
    stats = {n: [s for m, s in events if m == n] for n in SPANS}
    assert stats["repro.run_experiment"] == [{"chunks": 2, "lanes": 3}]
    assert stats["repro.lower"] == [{"n_requests": 200,
                                     "n_functions": 12}]
    dispatch = sorted(stats["repro.dispatch"], key=lambda s: s["chunk"])
    assert dispatch == [{"chunk": 0, "policy": "esff", "lanes": 2},
                        {"chunk": 1, "policy": "esff", "lanes": 1}]
    fetch = sorted(stats["repro.fetch"], key=lambda s: s["chunk"])
    n_events = rs.data["n_events"][0, 0, :, 0]
    assert [s["lanes"] for s in fetch] == [2, 1]
    assert [s["loop_steps"] for s in fetch] == rs.meta["loop_steps"]
    assert [s["lane_events"] for s in fetch] == [
        int(n_events[:2].sum()), int(n_events[2])]
    # the spans change no result, and the counter is not a metric
    assert "loop_steps" not in rs.data
    assert rs.meta["loop_steps"] == plain.meta["loop_steps"]
    for k, v in plain.data.items():
        np.testing.assert_array_equal(rs.data[k], v, err_msg=k)


@pytest.mark.parametrize("policy", ["esff", "sff", "openwhisk_v2"])
def test_single_window_loop_steps_cover_the_longest_lane(policy):
    """One window: the loop runs until its busiest lane has processed
    its last event, so it ran SEG * ceil(max n_events / SEG) steps."""
    rs = run_experiment(ExperimentSpec(**dict(SPEC, policies=(policy,),
                                              lane_chunk=8)))
    n_events = rs.data["n_events"][0, 0, :, 0]
    steps, = rs.meta["loop_steps"]
    assert steps == SEG * math.ceil(int(n_events.max()) / SEG)


def test_windowed_loop_steps_count_parked_spins():
    """Across windows a lane parks until the slowest lane leaves the
    window: the loop runs more steps than one window would, always
    whole segments, and every result stays bitwise the same."""
    one = run_experiment(ExperimentSpec(**dict(SPEC, lane_chunk=8)))
    win = run_experiment(ExperimentSpec(**dict(SPEC, lane_chunk=8,
                                               window=64)))
    s1, = one.meta["loop_steps"]
    sw, = win.meta["loop_steps"]
    assert sw % SEG == 0 and sw >= s1
    assert sw >= int(win.data["n_events"].max())
    for k, v in one.data.items():
        np.testing.assert_array_equal(win.data[k], v, err_msg=k)


@pytest.mark.parametrize("trace", [False, True])
def test_named_scopes_mark_the_event_step(trace):
    """The pick, the policy handlers and the metric fold carry named
    scopes into the lowered module (the rail flush only when traced),
    so a default-mode device trace can split a step by them."""
    from repro.analysis.entrypoints import _single_args
    from repro.analysis.markers import MARKERS as m
    from repro.core.jax_engine import _sweep_metrics
    from repro.core.jax_policies import KERNELS
    text = _sweep_metrics.trace(
        *_single_args(m), kernel=KERNELS["esff"], n_fns=m.F,
        capacity=m.C, queue_cap=m.Q, stream=True,
        trace=trace).lower().as_text(debug_info=True)
    for scope in ("repro.pick", "repro.policy", "repro.fold"):
        assert scope in text
    assert ("repro.flush" in text) is trace


def test_span_report_reduces_a_benchmark_unit(tmp_path, monkeypatch):
    """`scripts/span_report.py` on a fig5 unit cut to 150 requests:
    one dispatch per policy, 7 lanes each, and the derived numbers in
    range. On the CPU the trace holds no TPU op, so the scope split is
    left out here."""
    spec = importlib.util.spec_from_file_location(
        "span_report", os.path.join(ROOT, "scripts", "span_report.py"))
    report = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(report)
    from bench.cell import Cell
    load = Cell.load

    def small(name):
        cell = load(name)
        cell.config = dict(cell.config, n_requests=150)
        return cell

    monkeypatch.setattr(Cell, "load", staticmethod(small))
    out = tmp_path / "report.json"
    report.main(["--scopes-n", "0", "--out", str(out)])
    got = json.loads(out.read_text())
    assert [d["lanes"] for d in got["dispatch"]] == [7] * 6
    assert sorted(d["policy"] for d in got["dispatch"]) == sorted(
        small("paper_edge.fig5").workload["policies"])
    assert got["spans"]["repro.fetch"]["count"] == 6
    assert 0 < got["lane_occupancy.single"] <= 100
    assert 0 < got["runner_serial_share"] < 100
    assert got["fetch_union_s"] <= got["window_s"]
    assert "scopes" not in got
