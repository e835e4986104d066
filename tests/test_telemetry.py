"""repro.telemetry (PR 10): in-loop event tracing, span reassembly,
the streaming metrics bus and the Perfetto export — the disabled path
must leave every metric bitwise unchanged on every tier, the enabled
path must conserve work (one ARRIVAL per request, one completing EXEC
per done), a traced K=4 churn+retry run must match the Python
reference cluster event-for-event, and the event stream must be
invariant to the engine's cache-window size."""
import json

import numpy as np
import pytest

from repro.api import (ClusterSpec, ExperimentSpec, RetryPolicy,
                       SyntheticTrace, run_experiment)
from repro.telemetry import (TraceKind, TraceRun, assemble_spans,
                             events_summary, save_trace,
                             timeline_to_csv, to_prometheus,
                             validate_trace)
from repro.telemetry.perfetto import load_trace
from repro.telemetry.rail import AUX_FAIL_EXHAUSTED, AUX_FAIL_RETRY

SRC = SyntheticTrace.make(n_functions=12, n_requests=400, seed=3,
                          utilization=0.25)
N = 400
ARR = SRC.arrays()["arrival"]
SPAN = float(ARR.max())
FAULTS = dict(fail_prob=0.2, timeouts=8.0,
              retry=RetryPolicy(max_attempts=3, base=0.05, cap=1.0,
                                jitter=0.3),
              on_overflow="shed", fail_seed=99)
BASE = dict(traces=[SRC], policies=("esff",), capacities=(3,),
            queue_cap=64, stream=True)


def _churn_spec(k=4, router="jsq2"):
    t30 = float(np.quantile(ARR, 0.3))
    t60 = float(np.quantile(ARR, 0.6))
    return ClusterSpec(n_nodes=k, router=router,
                       churn=(((t30, t60),),) + (None,) * (k - 1))


def _assert_bitwise(kw):
    r0 = run_experiment(ExperimentSpec(**kw))
    r1 = run_experiment(ExperimentSpec(**kw, trace_events=True))
    for m in r0.data:
        assert np.array_equal(r0.data[m], r1.data[m],
                              equal_nan=True), m
    assert r1.trace is not None and r0.trace is None
    return r0, r1


# ------------------------------------------------ spec hardening
def test_trace_events_spec_validation():
    with pytest.raises(ValueError, match="host_shard"):
        ExperimentSpec(**BASE, trace_events=True,
                       host_shard=(1, 2)).validate()
    with pytest.raises(ValueError, match="devices"):
        ExperimentSpec(**BASE, trace_events=True,
                       devices=2).validate()
    ExperimentSpec(**BASE, trace_events=True, devices=1).validate()


# ------------------------------- disabled tracing is bitwise free
def test_bitwise_single_node():
    _assert_bitwise(dict(traces=[SRC], policies=("esff", "sff"),
                         capacities=(3, 8), queue_cap=64,
                         stream=True))


def test_bitwise_single_node_exact():
    _assert_bitwise(dict(traces=[SRC], policies=("esff",),
                         capacities=(3,), queue_cap=64, stream=False,
                         keep_per_request=True))


@pytest.mark.parametrize("entry", [
    ClusterSpec(n_nodes=2, router="hash"),       # static tier
    ClusterSpec(n_nodes=2, router="jsq2"),       # dynamic tier
    _churn_spec(),                               # churn rail
])
def test_bitwise_cluster_tiers(entry):
    _assert_bitwise(dict(**BASE, cluster=[entry]))


def test_bitwise_cluster_resilience():
    _assert_bitwise(dict(**BASE, cluster=[_churn_spec()], **FAULTS))


# -------------------------------------- conservation + span model
def test_event_conservation_and_spans():
    r0, r1 = _assert_bitwise(dict(traces=[SRC],
                                  policies=("esff", "sff"),
                                  capacities=(3,), queue_cap=64,
                                  stream=True))
    for pol in ("esff", "sff"):
        ev = r1.trace.events(policy=pol)
        done = int(r0.value("done", policy=pol))
        assert int((ev["kind"] == TraceKind.ARRIVAL).sum()) == N
        assert int((ev["kind"] == TraceKind.EXEC).sum()) == done
        assert int((ev["kind"] == TraceKind.COLD).sum()) == int(
            r0.value("cold_starts", policy=pol))
        spans = r1.trace.spans(policy=pol)
        comp = [s for s in spans.values() if s.completion >= 0]
        assert len(comp) == done
        # span responses reproduce the engine's response-sum metric
        # exactly (the engine's *mean* divides by N, not done)
        np.testing.assert_allclose(
            float(np.sum([s.response for s in comp])),
            float(r0.value("resp_sum", policy=pol)), rtol=1e-9)
        assert all(0 <= s.rid < N and 0 <= s.fn < 12 for s in comp)


def test_static_tier_rid_remap_and_nodes():
    _, r1 = _assert_bitwise(dict(
        **BASE, cluster=[ClusterSpec(n_nodes=3, router="hash")]))
    ev = r1.trace.events()
    # sub-stream-local rids were remapped to global request ids and
    # the per-node sub-streams were patched with their node id
    am = ev["kind"] == TraceKind.ARRIVAL
    assert sorted(ev["rid"][am].tolist()) == list(range(N))
    assert set(np.unique(ev["node"][am]).tolist()) <= {0, 1, 2}
    assert len(set(np.unique(ev["node"][am]).tolist())) == 3


# ------------------------- event-for-event parity vs the reference
def test_reference_parity_churn_retry_k4():
    from repro.cluster.reference import simulate_cluster_reference
    cs = _churn_spec(k=4, router="jsq2")
    rs = run_experiment(ExperimentSpec(**BASE, cluster=[cs],
                                       trace_events=True, **FAULTS))
    ev = rs.trace.events()

    log = []
    ref = simulate_cluster_reference(
        SRC.to_trace(), "esff", cs.validate(), capacity=3,
        queue_cap=64, horizon=SPAN, event_log=log, **FAULTS)
    assert int(rs.value("done")) == ref["done"]
    assert int(rs.value("retried")) == ref["retried"]
    assert len(ev["kind"]) == len(log)

    eng = np.stack([ev["kind"], ev["rid"], ev["fn"], ev["node"]],
                   axis=1).astype(np.int64)
    eng_t = np.asarray(ev["t"], np.float64)
    rlog = np.array([(k, r, f, n) for k, r, f, n, _ in log], np.int64)
    ref_t = np.array([t for *_, t in log], np.float64)

    def order(t, rec):
        return np.lexsort((rec[:, 1], rec[:, 3], rec[:, 0],
                           np.round(t, 9)))

    oe, orf = order(eng_t, eng), order(ref_t, rlog)
    eng, eng_t, rlog, ref_t = eng[oe], eng_t[oe], rlog[orf], ref_t[orf]
    np.testing.assert_array_equal(eng[:, 0], rlog[:, 0],
                                  err_msg="kind")
    np.testing.assert_allclose(eng_t, ref_t, rtol=1e-9, atol=1e-9,
                               err_msg="t")
    np.testing.assert_array_equal(eng[:, 1], rlog[:, 1],
                                  err_msg="rid")
    np.testing.assert_array_equal(eng[:, 2], rlog[:, 2], err_msg="fn")
    m = rlog[:, 3] >= 0    # reference leaves node unset on some kinds
    np.testing.assert_array_equal(eng[m, 3], rlog[m, 3],
                                  err_msg="node")
    # the fault run actually exercised the rails under audit
    kinds = eng[:, 0]
    assert (kinds == TraceKind.RETRY).sum() > 0
    assert (kinds == TraceKind.CHURN).sum() >= 2


# ----------------------------------- window/segment invariance
def test_event_stream_window_invariant():
    kw = dict(**BASE, trace_events=True)
    e1 = run_experiment(
        ExperimentSpec(**kw, window=64)).trace.events()
    e2 = run_experiment(
        ExperimentSpec(**kw, window=256)).trace.events()
    for f in e1:
        np.testing.assert_array_equal(e1[f], e2[f], err_msg=f)


# --------------------------------------- Perfetto JSON round-trip
def test_perfetto_schema_roundtrip(tmp_path):
    rs = run_experiment(ExperimentSpec(**BASE, trace_events=True,
                                       cluster=[_churn_spec()],
                                       **FAULTS))
    ev = rs.trace.events()
    path = tmp_path / "trace.json"
    trace = save_trace(ev, path, label="test")
    n = validate_trace(trace)
    assert n == len(trace["traceEvents"]) > 0
    loaded = load_trace(path)
    assert validate_trace(loaded) == n
    with open(path) as fh:
        raw = json.load(fh)
    assert raw["displayTimeUnit"] == "ms"
    xs = [e for e in raw["traceEvents"] if e["ph"] == "X"]
    ok = ((ev["kind"] == TraceKind.EXEC)
          & ((ev["aux"] & (AUX_FAIL_RETRY | AUX_FAIL_EXHAUSTED)) == 0))
    assert len(xs) == int((ev["kind"] == TraceKind.EXEC).sum())
    assert all(e["dur"] >= 0 for e in xs)
    assert ok.sum() <= len(xs)

    bad = dict(trace, traceEvents=[{"ph": "X", "name": "x"}])
    with pytest.raises(ValueError):
        validate_trace(bad)


# ------------------------------------------- TraceRun persistence
def test_tracerun_npz_roundtrip(tmp_path):
    rs = run_experiment(ExperimentSpec(
        traces=[SRC], policies=("esff", "sff"), capacities=(3,),
        queue_cap=64, stream=True, trace_events=True))
    path = tmp_path / "trace.npz"
    rs.trace.save_npz(path)
    back = TraceRun.load_npz(path)
    assert back.dims == rs.trace.dims
    assert set(back.cells) == set(rs.trace.cells)
    for key, ev in rs.trace.cells.items():
        for f in ev:
            np.testing.assert_array_equal(back.cells[key][f], ev[f])
    assert back.n_events == rs.trace.n_events


# ------------------------------------------------ metrics bus
def test_timeline_metrics_and_exporters(tmp_path):
    rs = run_experiment(ExperimentSpec(**BASE, trace_events=True,
                                       cluster=[ClusterSpec(
                                           n_nodes=2,
                                           router="jsq2")]))
    tl = rs.timeline(bucket=30.0, deadlines=10.0)
    B = len(tl["t"])
    assert tl["arrivals"].shape == (B, 2)
    assert int(tl["arrivals"].sum()) == N
    assert tl["queue_depth"].shape == (B, 2)
    assert np.min(tl["queue_depth"]) >= 0
    assert np.max(tl["queue_depth"]) <= 64   # bounded by queue_cap
    # node depths decompose the global total; warm/busy bounded by
    # per-node slots
    np.testing.assert_allclose(tl["queue_depth"].sum(axis=1),
                               tl["queue_total"])
    assert np.max(tl["busy"]) <= 2 * 3
    assert tl["utilization"].shape == (B, 2)
    assert np.all(tl["utilization"] >= 0)
    # capacity-normalised: a 3-slot node cannot exceed 100% busy
    assert np.all(tl["utilization"] <= 1 + 1e-9)
    thr = float((tl["throughput"] * 30.0).sum())
    assert thr == int(rs.value("done"))
    sr = tl["slo_rolling"]
    assert np.isnan(sr[0]) or 0 <= sr[0] <= 1
    assert 0 <= sr[-1] <= 1

    csv = tmp_path / "tl.csv"
    timeline_to_csv(tl, csv)
    header = csv.read_text().splitlines()[0].split(",")
    assert "queue_depth_k0" in header and "throughput" in header
    assert len(csv.read_text().splitlines()) == B + 1

    ev = rs.trace.events()
    summ = events_summary(ev)
    assert summ["arrivals"] == N
    text = to_prometheus(ev, tl=tl, labels=dict(policy="esff"))
    assert "# TYPE repro_arrivals_total counter" in text
    assert f'repro_arrivals_total{{policy="esff"}} {N}' in text
    assert 'queue_depth{policy="esff",node="1"}' in text


def test_span_assembly_from_raw_events():
    # hand-built stream: arrival -> failed attempt -> retry -> done
    ev = dict(
        kind=np.array([TraceKind.ARRIVAL, TraceKind.EXEC,
                       TraceKind.RETRY, TraceKind.EXEC], np.int32),
        rid=np.array([7, 7, 7, 7], np.int32),
        fn=np.array([2, 2, 2, 2], np.int32),
        node=np.array([0, 0, 0, 1], np.int32),
        aux=np.array([0, AUX_FAIL_RETRY, 0, 0], np.int32),
        qlen=np.zeros(4, np.int32), busy=np.zeros(4, np.int32),
        warm=np.zeros(4, np.int32),
        seq=np.arange(1, 5, dtype=np.int32),
        t=np.array([1.0, 3.0, 3.5, 6.0]),
        dt=np.array([0.0, 2.0, 0.0, 2.0]))
    spans = assemble_spans(ev)
    s = spans[7]
    assert s.arrival == 1.0 and s.completion == 6.0
    assert s.response == 5.0
    assert s.n_attempts == 2 and s.node == 1
    assert s.attempts[0][3] & AUX_FAIL_RETRY
    assert any(k == "RETRY" for k, _, _ in s.children)


# ---------------------------------------------- profiling hooks
def test_profiling_hooks():
    from repro.telemetry import provenance, spec_hash
    spec = ExperimentSpec(**BASE).validate()
    prov = provenance(spec)
    for k in ("backend", "jax_version", "x64", "spec_hash",
              "trace_events"):
        assert k in prov
    assert prov["spec_hash"] == spec_hash(spec)
    assert prov["trace_events"] is False
