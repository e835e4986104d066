"""Streaming-metrics mode (`repro.core.jax_engine`): equivalence with
the exact per-request mode, positional-queue behaviour under deep
backlogs, cache-window bitwise invariance (including queue links and
timers spanning window boundaries), backend-adaptive lane batching,
the minute-binned timeline fold, and the columnar trace fast path."""
import numpy as np
import pytest

from repro.core import simulate
from repro.core.jax_engine import (HIST_PER_DECADE, LANE_CHUNKS,
                                   default_lane_chunk, hist_edges,
                                   resolve_lane_chunk,
                                   simulate_policy_from_trace,
                                   simulate_policy_jax, sweep)
from repro.traces import (synth_azure_arrays, synth_azure_trace,
                          trace_from_lists)

POLICIES = ("esff", "sff", "openwhisk", "faascache")
BIN_RATIO = 10.0 ** (1.0 / HIST_PER_DECADE)


def test_stream_vs_exact_equivalence():
    """Means bitwise-equal (identical fold path), p99 within one
    histogram bin, across >= 3 policies and two capacities."""
    tr = synth_azure_trace(n_functions=20, n_requests=600,
                           utilization=0.25, seed=21)
    exact = sweep(tr, policies=POLICIES, capacities=(4, 8),
                  queue_cap=256, stream=False)
    strm = sweep(tr, policies=POLICIES, capacities=(4, 8),
                 queue_cap=256, stream=True)
    assert int(strm["overflow"].sum()) == 0
    assert int(strm["stalled"].sum()) == 0
    assert np.array_equal(strm["mean_response"],
                          exact["mean_response"])
    assert np.array_equal(strm["mean_slowdown"],
                          exact["mean_slowdown"])
    assert np.all(strm["p99_response"]
                  <= exact["p99_response"] * BIN_RATIO + 1e-12)
    assert np.all(strm["p99_response"]
                  >= exact["p99_response"] / BIN_RATIO - 1e-12)


def test_stream_accumulators_match_per_request_records():
    """The folded accumulators agree with recomputing the metrics from
    the exact mode's per-request arrays; the histogram counts every
    completed request exactly once."""
    tr = synth_azure_trace(n_functions=15, n_requests=500,
                           utilization=0.3, seed=8)
    a = tr.to_arrays()
    import jax.numpy as jnp
    args = (jnp.asarray(a["fn_id"]), jnp.asarray(a["arrival"]),
            jnp.asarray(a["exec_time"]), jnp.asarray(a["cold_start"]),
            jnp.asarray(a["evict"]))
    kw = dict(policy="sff", n_fns=tr.n_functions, capacity=8,
              queue_cap=256)
    ex = simulate_policy_jax(*args, stream=False, **kw)
    st = simulate_policy_jax(*args, stream=True, **kw)
    assert "completion" not in st          # O(N) outputs really gone
    n = len(tr)
    assert int(np.asarray(st["resp_hist"]).sum()) == n
    resp = np.asarray(ex["completion"]) - a["arrival"]
    np.testing.assert_allclose(float(st["resp_sum"]) / n, resp.mean(),
                               rtol=1e-12)
    assert float(st["max_response"]) == pytest.approx(resp.max(),
                                                      rel=1e-12)
    # both modes fold identically -> bitwise-equal accumulators
    assert float(st["resp_sum"]) == float(ex["resp_sum"])
    assert float(st["slow_sum"]) == float(ex["slow_sum"])


def test_positional_queues_survive_starvation():
    """SFF starves long functions, so a request can stay queued for
    most of the trace — the positional queues (cursors into the
    loop-invariant arrival order) must reproduce the Python engine
    exactly even then."""
    tr = synth_azure_trace(n_functions=20, n_requests=2000,
                           utilization=0.25, seed=4)
    py = simulate(tr, "sff", capacity=8)
    jx = simulate_policy_from_trace(tr, "sff", 8, queue_cap=2048)
    assert int(jx["overflow"]) == 0
    assert int(jx["stalled"]) == 0
    assert int(jx["cold_starts"]) == py.server.cold_starts
    resp_py = np.array([r.response for r in tr.requests])
    np.testing.assert_allclose(jx["response"], resp_py, rtol=1e-9,
                               atol=1e-9)


def test_hist_edges_shape():
    edges = hist_edges()
    assert len(edges) == 65
    assert edges[HIST_PER_DECADE] / edges[0] == pytest.approx(10.0)


def test_saturated_histogram_reports_true_tail():
    """Responses past the histogram's top edge (1e4 s) land in the
    last bin; the streamed p99 must fall back to the exact carried
    maximum instead of silently capping at the bin edge."""
    n = 8
    tr = trace_from_lists(
        fn_ids=[0] * n,
        arrivals=[float(i) for i in range(n)],
        exec_times=[20_000.0] * n,     # every response > 1e4 s
        cold=[0.5], evict=[0.2])
    out = sweep(tr, policies=("openwhisk",), capacities=(1,),
                queue_cap=64, stream=True)
    assert int(out["overflow"].sum()) == 0
    assert int(out["stalled"].sum()) == 0
    p99 = float(out["p99_response"][0, 0, 0, 0])
    assert p99 > 2e4                   # not capped at hist_edges()[-1]
    assert p99 == float(out["max_response"][0, 0, 0, 0])


def test_under_range_histogram_reports_true_tail():
    """All-fast traces (every response below the 1e-4 s floor) must
    not report the floor edge as p99 — the carried max clamps it."""
    n = 8
    tr = trace_from_lists(
        fn_ids=[0] * n,
        arrivals=[float(i) for i in range(n)],
        exec_times=[1e-5] * n,
        cold=[0.0], evict=[0.0])
    out = sweep(tr, policies=("openwhisk",), capacities=(1,),
                queue_cap=64, stream=True)
    assert int(out["stalled"].sum()) == 0
    p99 = float(out["p99_response"][0, 0, 0, 0])
    assert p99 == float(out["max_response"][0, 0, 0, 0])
    assert p99 < 2e-5                  # not the 1.33e-4 floor edge


BITWISE_KEYS = ("mean_response", "mean_slowdown", "p99_response",
                "max_response", "resp_hist", "cold_starts",
                "evictions", "overflow", "stalled")


def _assert_bitwise(a, b):
    for k in BITWISE_KEYS:
        np.testing.assert_array_equal(np.asarray(a[k]),
                                      np.asarray(b[k]), err_msg=k)


def test_window_boundary_bitwise_invariance():
    """A window size that splits a busy queue mid-window must not move
    a single bit of the streamed metrics: queue links spanning the
    boundary fall back to the full positional operand, and the
    per-event metric fold is order-identical at any window size. SFF
    starves long functions, so backlogged entries really do cross
    every boundary of a 64-request window here."""
    tr = synth_azure_trace(n_functions=16, n_requests=900,
                           utilization=0.45, seed=11)
    kw = dict(policies=("sff",), capacities=(6,), queue_cap=1024)
    ref = sweep(tr, stream=True, window=10**9, **kw)   # single window
    assert int(ref["stalled"].sum()) == 0
    win = sweep(tr, stream=True, window=64, **kw)
    _assert_bitwise(win, ref)
    # ... and the exact mode through the same small windows agrees
    # bitwise with the streamed mode (the shared per-event fold)
    exact = sweep(tr, stream=False, window=64, **kw)
    assert np.array_equal(win["mean_response"],
                          exact["mean_response"])
    assert np.array_equal(win["mean_slowdown"],
                          exact["mean_slowdown"])


def test_windowed_exact_mode_matches_python_under_starvation():
    """Exact per-request parity with the Python event engine when the
    windows are far smaller than the starved backlog."""
    tr = synth_azure_trace(n_functions=20, n_requests=1000,
                           utilization=0.3, seed=4)
    py = simulate(tr, "sff", capacity=8)
    jx = simulate_policy_from_trace(tr, "sff", 8, queue_cap=2048,
                                    window=101)
    assert int(jx["overflow"]) == 0
    assert int(jx["stalled"]) == 0
    assert int(jx["cold_starts"]) == py.server.cold_starts
    resp_py = np.array([r.response for r in tr.requests])
    np.testing.assert_allclose(jx["response"], resp_py, rtol=1e-9,
                               atol=1e-9)


def test_owv2_timer_fires_across_window_boundary():
    """An openwhisk_v2 head-wait timer armed in one window and firing
    after the arrival cursor has moved to the next window (its rail
    reads then cross the slab boundary) must reproduce the Python
    policy exactly, and streamed metrics must stay bitwise equal to
    the unwindowed run."""
    # capacity 1; f0 holds the slot while f1 arrivals queue right at
    # the window-4 boundary: r3 (t=0.30, window 0) arms a timer for
    # t=0.40, which fires after r4 (t=0.35, window 1) has arrived
    fn_ids = [0, 1, 1, 1, 1, 1, 0, 1]
    arrivals = [0.0, 0.10, 0.20, 0.30, 0.35, 0.45, 3.0, 3.5]
    execs = [2.0, 0.05, 0.05, 0.05, 0.05, 0.05, 0.2, 0.05]
    tr = trace_from_lists(fn_ids, arrivals, execs,
                          cold=[0.4, 0.3], evict=[0.2, 0.1])
    py = simulate(tr, "openwhisk_v2", capacity=1)
    jx = simulate_policy_from_trace(tr, "openwhisk_v2", 1,
                                    queue_cap=64, window=4)
    assert int(jx["stalled"]) == 0
    resp_py = np.array([r.response for r in tr.requests])
    np.testing.assert_allclose(jx["response"], resp_py, rtol=1e-9,
                               atol=1e-9)
    kw = dict(policies=("openwhisk_v2",), capacities=(1,),
              queue_cap=64)
    _assert_bitwise(sweep(tr, stream=True, window=4, **kw),
                    sweep(tr, stream=True, window=10**9, **kw))


def test_lane_chunk_settings_do_not_change_results():
    """Sweep results are invariant to how lanes are batched into
    device calls: chunk sizes 1 and 16 and the ``auto`` probe must
    agree exactly on a small policy x capacity grid."""
    tr = synth_azure_trace(n_functions=12, n_requests=400,
                           utilization=0.25, seed=3)
    kw = dict(policies=("esff", "sff"), capacities=(4, 6),
              queue_cap=512, stream=True)
    ref = sweep(tr, lane_chunk=16, **kw)
    for setting in (1, "auto"):
        out = sweep(tr, lane_chunk=setting, **kw)
        _assert_bitwise(out, ref)


def test_resolve_lane_chunk_auto_probe_is_cached():
    c1 = resolve_lane_chunk("auto")
    assert isinstance(c1, int) and c1 >= 1
    assert resolve_lane_chunk("auto") == c1      # cached, no re-probe
    assert resolve_lane_chunk(7) == 7
    assert resolve_lane_chunk("") >= 1           # backend table


@pytest.mark.parametrize("backend", sorted(LANE_CHUNKS))
def test_default_lane_chunk_reads_the_table(backend):
    assert default_lane_chunk(backend) == LANE_CHUNKS[backend]


def test_default_lane_chunk_rejects_unknown_backend():
    with pytest.raises(ValueError, match="no LANE_CHUNKS entry"):
        default_lane_chunk("metal")


def test_timeline_fold_matches_python_timeline():
    """The engine's minute-binned accumulator reproduces the Python
    engine's Fig. 8 timeline (same bins, counts, and means)."""
    tr = synth_azure_trace(n_functions=12, n_requests=400,
                           utilization=0.25, seed=3)
    a = tr.to_arrays()
    n_bins = int(a["arrival"].max() // 60.0) + 1
    out = sweep(tr, policies=("esff",), capacities=(6,),
                queue_cap=512, stream=True, tl_bins=n_bins,
                tl_bucket=60.0)
    assert int(out["stalled"].sum()) == 0
    cnt = np.asarray(out["tl_count"][0, 0, 0, 0], np.int64)
    rsum = np.asarray(out["tl_resp_sum"][0, 0, 0, 0])
    esum = np.asarray(out["tl_exec_sum"][0, 0, 0, 0])
    assert int(cnt.sum()) == len(tr)
    res = simulate(tr, "esff", capacity=6)
    tl = res.timeline(60.0)
    n_py = len(tl["minute"])
    np.testing.assert_array_equal(cnt[:n_py], tl["n_requests"])
    nz = cnt[:n_py] > 0
    np.testing.assert_allclose(
        (rsum[:n_py][nz] / cnt[:n_py][nz]), tl["mean_response"][nz],
        rtol=1e-12)
    np.testing.assert_allclose(
        (esum[:n_py][nz] / cnt[:n_py][nz]), tl["mean_exec"][nz],
        rtol=1e-12)


def test_synth_azure_arrays_matches_trace_path():
    tr = synth_azure_trace(n_functions=10, n_requests=300, seed=5)
    a = tr.to_arrays()
    b = synth_azure_arrays(n_functions=10, n_requests=300, seed=5)
    for k in ("fn_id", "arrival", "exec_time", "cold_start", "evict"):
        np.testing.assert_array_equal(a[k], b[k])


@pytest.mark.slow
def test_large_trace_parity_with_python_engine():
    """10^5-request spot check: the streaming engine (bounded carried
    state) agrees with the Python event engine end to end."""
    tr = synth_azure_trace(n_functions=100, n_requests=100_000,
                           utilization=0.2, seed=7)
    py = simulate(tr, "esff", capacity=16)
    jx = simulate_policy_from_trace(tr, "esff", 16, queue_cap=4096)
    assert int(jx["overflow"]) == 0
    assert int(jx["stalled"]) == 0
    assert int(jx["cold_starts"]) == py.server.cold_starts
    resp_py = np.array([r.response for r in tr.requests])
    np.testing.assert_allclose(jx["response"], resp_py, rtol=1e-9,
                               atol=1e-9)
    st = sweep(tr, policies=("esff",), capacities=(16,),
               queue_cap=4096, stream=True)
    np.testing.assert_allclose(st["mean_response"][0, 0, 0, 0],
                               py.mean_response, rtol=1e-9)
