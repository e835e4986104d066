"""The reduction from a profile to per-layer metrics."""
import glob
import os

import pytest

from bench.trace import Event, TraceData, executions, load_metrics

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def synthetic():
    """A unit from 0 to 10 s: two single-node launches executing at 1-3
    s and 2.5-6 s (overlapping), a tiny conversion at 7 s, host spans."""
    modules = [Event("jit__sweep_metrics(1)", 1.0, 3.0),
               Event("jit__sweep_metrics(1)", 2.5, 6.0),
               Event("jit_convert_element_type(2)", 7.0, 7.5)]
    host = [Event("bench.unit", 0.0, 10.0),
            Event("bench.run_experiment", 0.0, 8.0),
            Event("bench.check", 8.0, 10.0),
            Event("PjitFunction(_sweep_metrics)", 0.5, 0.9, "worker0"),
            Event("PjitFunction(_sweep_metrics)", 0.51, 0.89, "worker0"),
            Event("PjitFunction(_sweep_metrics)", 0.6, 1.0, "worker1"),
            Event("PjitFunction(convert_element_type)", 6.9, 7.0),
            Event("Delinearize", 6.0, 6.9)]
    return TraceData(modules, host, (0.0, 10.0), units=1, steps=1000)


def test_busy_is_the_union_of_program_executions():
    t = synthetic()
    assert t.busy() == [[1.0, 6.0], [7.0, 7.5]]
    assert t.busy_s == pytest.approx(5.5)
    assert t.window_s == 10.0
    assert t.gaps() == [(0.0, 1.0), (6.0, 7.0), (7.5, 10.0)]


def test_metrics():
    got = synthetic().metrics()
    assert got["launches_per_unit"] == {"value": 2.0, "unit": "launches"}
    assert got["device_us_per_event.single"]["value"] == pytest.approx(
        5.5 / 1000 * 1e6)
    assert got["device_idle_share"]["value"] == pytest.approx(45.0)


def test_a_metric_with_nothing_to_read_is_left_out():
    t = TraceData([], [], (0.0, 1.0), steps=0)
    assert t.metrics() == {}


def test_breakdown_names_gaps_by_the_host():
    b = synthetic().breakdown()
    assert b["device_ops"][0] == ["jit__sweep_metrics(1)", pytest.approx(5.5)]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    names = dict((round(s, 6), n) for n, s in b["idle_gaps"])
    assert names[2.5] == "bench.check"
    assert names[1.0] == "bench.run_experiment / Delinearize"


def test_executions_from_the_runtime_host_events():
    """Launch k and completion k bound execution k; an execution starts
    no earlier than the previous one ends."""
    host = [Event("tpu::System::Execute", 1.0, 1.1),
            Event("tpu::System::Execute", 1.2, 1.3),
            Event("tpu::System::Execute=>Done", 3.0, 3.1),
            Event("tpu::System::Execute=>Done", 5.0, 5.1)]
    got = [(e.start, e.end) for e in executions(host)]
    assert got == [(1.0, 3.0), (3.0, 5.0)]


def test_every_metric_declares_its_layer():
    for m in load_metrics():
        assert m.MOVES == "sim_req_per_s" and m.LAYER and m.UNIT


class _Line:
    def __init__(self, d):
        self.name = d["name"]
        self.events = [type("E", (), dict(name=n, start_ns=s, duration_ns=d))
                       for n, s, d in d["events"]]


class _Plane:
    def __init__(self, d):
        self.name = d["name"]
        self.lines = [_Line(x) for x in d["lines"]]


def recorded():
    """The committed chip trace, in `jax.profiler.ProfileData`'s shape."""
    import json
    with open(os.path.join(DATA, "fig5_esff_n100.trace.json")) as f:
        doc = json.load(f)
    return type("P", (), dict(planes=[_Plane(p) for p in doc["planes"]]))


def device_line(doc):
    """The device's own program executions in a default-mode trace,
    in seconds: what the host events have to bound."""
    return [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
            for p in doc.planes if p.name.startswith("/device")
            for line in p.lines if line.name == "XLA Modules"
            for e in line.events]


def test_recorded_chip_trace(monkeypatch):
    """One esff launch of 7 lanes at N=100 on a TPU v5 lite, in a host
    span named ``unit``: the device ran `_sweep_metrics` for 20.97 ms
    and two tiny conversion programs inside a 34.10 ms span. The
    runtime's host events give the same three executions, each no
    shorter than on the device (the device's clock runs about 1 ms
    apart from the host's): 21.96 ms for the loop program and about
    0.7 ms for each conversion of 2 us."""
    from bench import trace
    monkeypatch.setattr(trace, "UNIT_SPAN", "unit")
    doc = recorded()
    t = TraceData.from_profile(doc, units=1, steps=1)
    dev = sorted(device_line(doc))
    assert len(t.modules) == len(dev) == 3
    for e, (s, end) in zip(t.modules, dev):
        assert e.end - e.start >= end - s
    longest = max(e.end - e.start for e in t.modules)
    assert 0.02097079 <= longest <= 0.0221
    assert t.launches(("_sweep_metrics",)) == 1
    assert t.window_s == pytest.approx(0.034102968)
    busy = t.busy_s
    assert 0.02097079 <= busy < t.window_s
    m = t.metrics()
    assert m["launches_per_unit"]["value"] == 1.0
    assert m["device_idle_share"]["value"] == pytest.approx(
        100 * (1 - busy / 0.034102968))
    b = t.breakdown()
    assert b["device_ops"][0][0] == "PjitFunction(_sweep_metrics)"
    assert all(name.startswith(("unit", "none")) for name, _ in
               b["idle_gaps"])


def test_a_profile_without_the_unit_span_is_refused():
    with pytest.raises(RuntimeError, match="bench.unit"):
        TraceData.from_profile(recorded())


def test_a_trace_run_missing_a_listed_metric_fails(monkeypatch, capsys):
    """On the CPU the trace has no TPU runtime events, so the metrics
    of device time find nothing to read: the run exits without a
    result."""
    from bench import run as bench_run
    from bench.cell import Cell
    load = Cell.load

    def small(name):
        cell = load(name)
        cell.config = dict(cell.config, n_requests=100)
        return cell

    monkeypatch.setattr(bench_run, "REQUIRED_PLATFORM", "cpu")
    monkeypatch.setattr(bench_run.Cell, "load", staticmethod(small))
    with pytest.raises(SystemExit, match="device_idle_share"):
        bench_run.main(["--workload", "paper_edge.fig5", "--seed", "4",
                        "--seconds", "0.1", "--trace", "1"])
    assert '"correct"' not in capsys.readouterr().out
