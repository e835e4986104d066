"""Benchmark aggregator — one section per paper table/figure plus kernel
and simulator microbenches. Prints ``name,us_per_call,derived`` CSV
blocks; REPRO_BENCH_SCALE scales trace sizes. Every section run also
emits a machine-readable ``BENCH_<stamp>.json`` (per-section wall time
plus each section's rows — req/s per config for the throughput and
engine-scale sections) so the perf trajectory is tracked across PRs.

    PYTHONPATH=src python -m benchmarks.run [--only fig5,kernels]
    PYTHONPATH=src python -m benchmarks.run --smoke   # <60s CI gate
    PYTHONPATH=src python -m benchmarks.run --baseline BENCH_x.json

``--smoke`` runs every scheduling policy on a tiny trace through both
engines and exits non-zero on any Python/JAX mismatch — including the
streaming-vs-exact gate (bitwise-equal means, p99 within one histogram
bin), the ``sweep()``-shim bitwise-parity gate against the
`repro.api.ExperimentSpec` path, the resilience gates (trivial fault
knobs lower bitwise onto the unchanged engine; faults + load shedding
conserve every request; the circuit breaker trips and recovers), a
forced 2-device CPU subprocess
(``--xla_force_host_platform_device_count=2``) asserting the sharded
runner is bitwise-identical to single-device, and a static scan that
fails on DeprecationWarning-free use of the old entry points
(``sweep`` imports / ``REPRO_AZURE_NPZ``) creeping back into
benchmarks/examples/src — cheap enough to sit next to tier-1 in CI.

``--baseline`` compares this run's per-row ``req_s`` against a
previous BENCH json and exits non-zero if any matching row dropped
more than 20% (``--regress-tol``) — the perf counterpart of the smoke
gate: run ``--smoke`` for correctness, then
``--only enginescale,simthroughput --baseline <last BENCH json>`` to
catch throughput regressions before merging.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

SECTIONS = ("fig5", "fig6", "fig7", "fig8", "ablation", "cluster",
            "churn", "resilience", "kernels", "simthroughput",
            "enginescale", "telemetry")


def smoke() -> int:
    import warnings

    import numpy as np

    from benchmarks.common import POLICIES
    from repro.api import ExperimentSpec, SyntheticTrace, run_experiment
    from repro.core import simulate
    from repro.core.jax_engine import (hist_edges,
                                       simulate_policy_from_trace,
                                       sweep)

    src = SyntheticTrace.make(n_functions=12, n_requests=400,
                              utilization=0.25, seed=3)
    tr = src.to_trace()
    capacity = 6
    failures = 0
    for policy in POLICIES:
        py = simulate(tr, policy, capacity)
        jx = simulate_policy_from_trace(tr, policy, capacity,
                                        queue_cap=256)
        resp_py = np.array([r.response for r in tr.requests])
        ok = (int(jx["overflow"]) == 0
              and int(jx["stalled"]) == 0
              and int(jx["cold_starts"]) == py.server.cold_starts
              and np.allclose(jx["response"], resp_py, rtol=1e-9,
                              atol=1e-9))
        failures += 0 if ok else 1
        print(f"{policy:13s} python={py.mean_response:8.4f}s  "
              f"jax={jx['mean_response']:8.4f}s  "
              + ("OK" if ok else "MISMATCH"))

    # streaming-vs-exact equivalence gate on the ExperimentSpec grid:
    # identical fold path => means must agree bitwise; histogram p99
    # within one log bin of exact
    bin_ratio = hist_edges()[1] / hist_edges()[0]
    grid = dict(traces=[src], policies=POLICIES,
                capacities=(capacity,), queue_cap=256)
    exact = run_experiment(ExperimentSpec(stream=False, **grid))
    strm = run_experiment(ExperimentSpec(stream=True, **grid))
    ok = (np.array_equal(strm["mean_response"],
                         exact["mean_response"])
          and np.array_equal(strm["mean_slowdown"],
                             exact["mean_slowdown"])
          and bool(np.all(strm["p99_response"]
                          <= exact["p99_response"] * bin_ratio + 1e-12))
          and bool(np.all(strm["p99_response"]
                          >= exact["p99_response"] / bin_ratio - 1e-12)))
    failures += 0 if ok else 1
    print("stream-vs-exact: means "
          + ("bitwise-equal, p99 within one bin  OK" if ok
             else "MISMATCH"))

    # sweep() deprecation shim: must warn, and must be bitwise-equal
    # to the ExperimentSpec path it now wraps
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        legacy = sweep(tr, policies=POLICIES, capacities=(capacity,),
                       queue_cap=256, stream=True)
    warned = any(issubclass(w.category, DeprecationWarning)
                 for w in caught)
    parity = all(np.array_equal(legacy[k], strm[k])
                 for k in strm.data)
    failures += 0 if (warned and parity) else 1
    print("sweep() shim: "
          + ("DeprecationWarning + bitwise parity  OK"
             if warned and parity else
             f"MISMATCH (warned={warned}, parity={parity})"))

    # K=1 cluster gate: a 1-node cluster with zero network delay must
    # be bitwise the single-node engine — through the static
    # sub-stream fast path AND the dynamic routers' K-node event loop,
    # timer-rail policies (openwhisk_v2) included
    from repro.api import ClusterSpec
    cl_policies = ("esff", "sff", "openwhisk_v2")
    cl = run_experiment(ExperimentSpec(
        traces=[src], policies=cl_policies, capacities=(capacity,),
        queue_cap=256,
        cluster=[ClusterSpec(n_nodes=1, router="hash"),
                 ClusterSpec(n_nodes=1, router="jsq2"),
                 ClusterSpec(n_nodes=1, router="cold_aware")]))
    ref = run_experiment(ExperimentSpec(
        traces=[src], policies=cl_policies,
        capacities=(capacity,), queue_cap=256))
    ok = all(
        np.array_equal(ref.data[m], np.take(cl.data[m], u, axis=4))
        for u in range(len(cl.coords["cluster"])) for m in ref.data)
    failures += 0 if ok else 1
    print("cluster K=1 (static + dynamic, incl. timer rail): "
          + ("bitwise-identical to single node  OK" if ok
             else "MISMATCH"))

    # dynamic-tier conservation: openwhisk_v2 over a 3-node jsq2
    # cluster with heterogeneous per-node delays must complete every
    # request exactly once (no overflow, no stalls, node_done sums to
    # done) — the deferred-event rail cannot drop or duplicate work
    cv = run_experiment(ExperimentSpec(
        traces=[src], policies=("openwhisk_v2",),
        capacities=(capacity,), queue_cap=256,
        cluster=[ClusterSpec(n_nodes=3, router="jsq2",
                             net_delay=(0.0, 0.002, 0.005))]))
    done = cv.data["done"]
    ok = (bool(np.all(done == src.n_requests))
          and not np.any(cv.data["overflow"])
          and not np.any(cv.data["stalled"])
          and bool(np.all(cv.data["node_done"].sum(axis=-1) == done)))
    failures += 0 if ok else 1
    print("dynamic openwhisk_v2 + net_delay conservation: "
          + ("every request completes exactly once  OK" if ok
             else "MISMATCH"))

    # churn gates: the fault-injection rail must (a) conserve every
    # request across mid-flight node deaths, (b) lower trivial
    # always-up schedules onto the plain dynamic loop bitwise, and
    # (c) park arrivals while every node is down and drain them all
    # once a node returns
    from repro.api import PeriodicChurn
    arr = src.arrays()["arrival"]
    t30, t60 = (float(np.quantile(arr, q)) for q in (0.30, 0.60))
    ck = dict(traces=[src], policies=("esff",),
              capacities=(capacity,), queue_cap=256)
    churned = run_experiment(ExperimentSpec(
        cluster=[ClusterSpec(n_nodes=3, router="jsq2",
                             churn=(None, ((t30, t60),), None))],
        **ck))
    done = churned.data["done"]
    ok = (bool(np.all(done == src.n_requests))
          and not np.any(churned.data["overflow"])
          and not np.any(churned.data["stalled"])
          and bool(np.all(
              churned.data["node_done"].sum(axis=-1) == done)))
    failures += 0 if ok else 1
    print("churn conservation (mid-window node death): "
          + ("every request completes exactly once  OK" if ok
             else "MISMATCH"))

    plain1 = run_experiment(ExperimentSpec(
        cluster=[ClusterSpec(n_nodes=1, router="jsq2")], **ck))
    triv = run_experiment(ExperimentSpec(
        cluster=[ClusterSpec(
            n_nodes=1, router="jsq2",
            churn=(PeriodicChurn(period=10.0, duty=1.0),))], **ck))
    ok = all(np.array_equal(plain1.data[m], triv.data[m])
             for m in plain1.data)
    failures += 0 if ok else 1
    print("trivial churn lowering (K=1, duty=1.0): "
          + ("bitwise-identical to plain dynamic loop  OK" if ok
             else "MISMATCH"))

    t45 = float(np.quantile(arr, 0.45))
    alldown = run_experiment(ExperimentSpec(
        cluster=[ClusterSpec(n_nodes=2, router="jsq2",
                             churn=(((t30, t45),), ((t30, t45),)))],
        keep_per_request=True, stream=False, **ck))
    resp = np.asarray(alldown.data["response"]).reshape(-1)[
        : src.n_requests]
    inside = (arr >= t30) & (arr < t45)
    done = alldown.data["done"]
    ok = (bool(np.all(done == src.n_requests))
          and not np.any(alldown.data["overflow"])
          and bool(np.all(arr[inside] + resp[inside] >= t45)))
    failures += 0 if ok else 1
    print("all-down window parks and resumes: "
          + ("parked arrivals complete after the window  OK" if ok
             else "MISMATCH"))

    # resilience gates: the request-level fault rail must (a) leave
    # trivial-knob specs on the unchanged code path bitwise, (b)
    # conserve every request as exactly one of done/shed/
    # failed-exhausted under faults + load shedding across the
    # dynamic AND static tiers, and (c) trip the circuit breaker
    # under a high failure rate and keep completing work afterwards
    from repro.api import RetryPolicy
    rk = dict(traces=[src], policies=("esff",),
              capacities=(capacity,), queue_cap=256,
              cluster=(None, ClusterSpec(n_nodes=2, router="hash"),
                       ClusterSpec(n_nodes=2, router="jsq2")))
    r0 = run_experiment(ExperimentSpec(**rk))
    r1 = run_experiment(ExperimentSpec(
        **rk, fail_prob=0.0, timeouts=None, on_overflow="error"))
    ok = (set(r0.data) == set(r1.data)
          and all(np.array_equal(r0.data[m], r1.data[m])
                  for m in r0.data)
          and "shed" not in r0.data)
    failures += 0 if ok else 1
    print("trivial fault knobs: "
          + ("lower onto the unchanged engine bitwise  OK" if ok
             else "MISMATCH"))

    faults = dict(fail_prob=0.2, timeouts=8.0, fail_seed=99,
                  retry=RetryPolicy(max_attempts=3, base=0.05,
                                    cap=1.0, jitter=0.3),
                  on_overflow="shed")
    sh = run_experiment(ExperimentSpec(
        traces=[src], policies=("esff",), capacities=(capacity,),
        queue_cap=8, **faults,
        cluster=(None, ClusterSpec(n_nodes=2, router="hash"),
                 ClusterSpec(n_nodes=2, router="jsq2")))).check()
    tot = (sh.data["done"] + sh.data["shed"]
           + sh.data["failed_exhausted"])
    ok = (bool(np.all(tot == src.n_requests))
          and bool(np.all(sh.data["goodput"]
                          == sh.data["done"] / src.n_requests)))
    failures += 0 if ok else 1
    print("shed-mode conservation (dynamic + static tiers): "
          + ("done+shed+failed_exhausted == N  OK" if ok
             else "MISMATCH"))

    br = run_experiment(ExperimentSpec(
        traces=[src], policies=("esff",), capacities=(capacity,),
        queue_cap=256, **dict(faults, fail_prob=0.6),
        cluster=[ClusterSpec(n_nodes=4, router="breaker")])).check()
    trips = int(br.data["breaker_trips"].sum())
    tot = (br.data["done"] + br.data["shed"]
           + br.data["failed_exhausted"])
    ok = (trips > 0 and int(br.data["done"].sum()) > 0
          and bool(np.all(tot == src.n_requests)))
    failures += 0 if ok else 1
    print("breaker trips and recovers: "
          + (f"{trips} trips, work still completes  OK" if ok
             else "MISMATCH"))

    # NpzTrace round-trip: save_npz -> NpzTrace -> run must match the
    # in-memory source bitwise (keeps the real-Azure path covered in
    # containers without the dataset)
    import tempfile

    from repro.api import NpzTrace
    with tempfile.TemporaryDirectory() as td:
        npz_path = os.path.join(td, "smoke_trace.npz")
        tr.save_npz(npz_path)
        kw = dict(policies=("esff",), capacities=(capacity,),
                  queue_cap=256)
        via_npz = run_experiment(ExperimentSpec(
            traces=[NpzTrace(path=npz_path)], **kw))
        direct = run_experiment(ExperimentSpec(traces=[src], **kw))
    ok = all(np.array_equal(via_npz.data[m], direct.data[m])
             for m in direct.data)
    failures += 0 if ok else 1
    print("npz trace round-trip: "
          + ("save_npz -> NpzTrace bitwise  OK" if ok
             else "MISMATCH"))

    # telemetry gates: (a) trace_events=False is the default and
    # trace_events=True must leave every metric bitwise unchanged on
    # every tier (plain + static + dynamic cluster) — the rail only
    # *observes*; (b) the traced event stream must conserve work:
    # one ARRIVAL per request, one EXEC-done per completion, span
    # reassembly agreeing with the done counters; (c) the Perfetto
    # export must validate (the written file is the CI trace artifact)
    from repro.telemetry import save_trace, validate_trace
    tk = dict(traces=[src], policies=("esff",),
              capacities=(capacity,), queue_cap=256,
              cluster=(None, ClusterSpec(n_nodes=2, router="hash"),
                       ClusterSpec(n_nodes=2, router="jsq2")))
    t0r = run_experiment(ExperimentSpec(**tk))
    t1r = run_experiment(ExperimentSpec(**tk, trace_events=True))
    ok = all(np.array_equal(t0r.data[m], t1r.data[m])
             for m in t0r.data)
    failures += 0 if ok else 1
    print("disabled/enabled tracing: "
          + ("metrics bitwise unchanged on all tiers  OK" if ok
             else "MISMATCH"))
    ok = True
    for lab in t1r.coords["cluster"]:
        ev = t1r.trace.events(cluster=lab)
        spans = t1r.trace.spans(cluster=lab)
        dn = int(t0r.value("done", cluster=lab))
        ok = (ok and int((ev["kind"] == 0).sum()) == src.n_requests
              and int((ev["kind"] == 1).sum()) == dn
              and sum(1 for s in spans.values()
                      if s.completion >= 0) == dn)
    try:
        n_ev = validate_trace(save_trace(
            t1r.trace.events(cluster=t1r.coords["cluster"][-1]),
            "trace_sample_perfetto.json", label="smoke"))
    except ValueError:
        ok, n_ev = False, 0
    failures += 0 if ok else 1
    print("traced-run conservation + Perfetto schema: "
          + (f"spans match done counters, {n_ev} trace events  OK"
             if ok else "MISMATCH"))

    failures += _sharded_parity_check()
    failures += deprecation_scan()
    print(f"# smoke: {len(POLICIES)} policies, "
          f"{len(POLICIES)} engine-equivalence checks + streaming, "
          f"shim-parity, cluster-K=1 (incl. timer rail), dynamic "
          f"conservation, churn (conservation, trivial lowering, "
          f"all-down park), resilience (trivial lowering, shed "
          f"conservation, breaker), telemetry (bitwise-off, "
          f"conservation, Perfetto), npz round-trip, 2-device and "
          f"deprecation gates, {failures} failures")
    return failures


_SHARDED_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=2")
import numpy as np
import jax
from repro.api import ExperimentSpec, SyntheticTrace, run_experiment
assert len(jax.local_devices()) >= 2, jax.local_devices()
src = SyntheticTrace.make(n_functions=12, n_requests=400, seed=3,
                          utilization=0.25)
kw = dict(traces=[src], policies=("esff", "sff"), capacities=(4, 6),
          queue_cap=256, lane_chunk=2)
one = run_experiment(ExperimentSpec(devices=1, **kw))
two = run_experiment(ExperimentSpec(devices=2, **kw))
assert two.meta["n_devices"] == 2
for k in one.data:
    assert np.array_equal(one.data[k], two.data[k]), k
print("SHARDED_OK")
"""


def _sharded_parity_check() -> int:
    """Forced 2-CPU-device subprocess: the sharded runner must produce
    bitwise-identical ResultSet metrics to the single-device run. The
    child runs on the CPU backend only: on a TPU host this process
    already holds the chip, which a second process cannot open."""
    import subprocess

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = (os.path.join(root, "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    r = subprocess.run([sys.executable, "-c", _SHARDED_SCRIPT],
                       env=env, cwd=root, capture_output=True,
                       text=True, timeout=600)
    ok = r.returncode == 0 and "SHARDED_OK" in r.stdout
    print("2-device sharded parity: " + ("OK" if ok else "MISMATCH"))
    if not ok:
        print(r.stdout[-2000:] + r.stderr[-2000:], file=sys.stderr)
    return 0 if ok else 1


def deprecation_scan() -> int:
    """Fail on use of the old driving surface (importing ``sweep``
    from the engine, the ``REPRO_AZURE_NPZ`` env var, benchmarks
    driving the Python event engine) anywhere in benchmarks/,
    examples/, scripts/ or src/ — tests are exempt (they exercise the
    shim deliberately).

    Since PR 9 this delegates to the AST-level lint in
    `repro.analysis.lint` (same allowlists, same one-line-per-hit
    failure surface): real import statements, attribute calls and
    string constants are matched structurally, so prose can't
    false-positive and a reformatted import can't dodge the gate."""
    from repro.analysis.lint import scan

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    bad = scan(root)
    print("deprecation scan: " + ("OK" if not bad
                                  else f"{bad} hit(s)"))
    return bad


def _provenance() -> dict:
    """Run-provenance metadata folded into every BENCH report (and
    from there into BENCH_history.jsonl): backend/device, jax
    version, x64 flag and the engines' jit cache sizes — enough to
    tell apart rows produced on different machines or lowering
    configurations when reading the perf trajectory."""
    from repro.telemetry import provenance
    return provenance()


def append_history(path: str, report: dict) -> None:
    """Append one compact summary row of ``report`` to the cumulative
    ``BENCH_history.jsonl`` — one json object per line, so the perf
    trajectory across PRs is a single greppable file (CI appends to a
    persisted copy on every run)."""
    row = dict(stamp=report.get("stamp"),
               smoke=bool(report.get("smoke", False)),
               failures=report.get("failures"),
               wall_s=report.get("wall_s"),
               backend=report.get("provenance", {}).get("backend"),
               req_s={f"{sec}/{r['name']}": round(float(r["req_s"]))
                      for sec, sd in report.get("sections", {}).items()
                      for r in sd.get("rows", [])
                      if isinstance(r, dict) and "req_s" in r
                      and r.get("name")})
    with open(path, "a") as f:
        f.write(json.dumps(row, default=str) + "\n")
    print(f"# appended history row to {path}", file=sys.stderr)


def check_regression(baseline_path: str, report: dict,
                     tol: float = 0.20) -> int:
    """Compare ``req_s`` rows against a baseline BENCH json.

    Rows are matched by section + ``name``; a row is a regression when
    its req/s falls below ``(1 - tol)`` of the baseline's. Returns the
    number of regressed rows (and prints each)."""
    with open(baseline_path) as f:
        base = json.load(f)
    regressions = checked = 0
    for sec, sdata in report.get("sections", {}).items():
        brows = {r["name"]: r
                 for r in base.get("sections", {})
                           .get(sec, {}).get("rows", [])
                 if isinstance(r, dict) and "name" in r
                 and "req_s" in r}
        for r in sdata.get("rows", []):
            if not (isinstance(r, dict) and "req_s" in r
                    and r.get("name")):
                continue
            if r["name"] not in brows:
                # new rows (fresh benchmarks, renamed configs) have
                # no baseline yet — warn and skip instead of silently
                # ignoring or failing the gate
                print(f"BASELINE MISSING {sec}/{r['name']}: not in "
                      f"{baseline_path} — skipping (new row?)",
                      file=sys.stderr)
                continue
            checked += 1
            now = float(r["req_s"])
            was = float(brows[r["name"]]["req_s"])
            if now < (1.0 - tol) * was:
                regressions += 1
                print(f"REGRESSION {sec}/{r['name']}: "
                      f"{now:.0f} req/s vs baseline {was:.0f} "
                      f"(-{100 * (1 - now / was):.0f}%)",
                      file=sys.stderr)
    if checked == 0:
        # a gate that compared nothing must not pass silently (row
        # renames / --only selections without req_s rows would turn it
        # vacuous and let real regressions ship)
        print(f"REGRESSION GATE VACUOUS: no req_s rows of this run "
              f"matched {baseline_path} — treating as failure",
              file=sys.stderr)
        return 1
    print(f"# baseline check vs {baseline_path}: {checked} rows, "
          f"{regressions} regression(s) beyond {tol:.0%}",
          file=sys.stderr)
    return regressions


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny trace, all policies, both engines; "
                         "exits non-zero on mismatch (<60s)")
    ap.add_argument("--json", default="",
                    help="path of the BENCH json report "
                         "(default BENCH_<stamp>.json)")
    ap.add_argument("--baseline", default="",
                    help="previous BENCH json; exit non-zero if any "
                         "section row's req_s drops > --regress-tol")
    ap.add_argument("--regress-tol", type=float, default=0.20,
                    help="allowed fractional req/s drop (default 0.20)")
    ap.add_argument("--history", default="",
                    help="append a one-line summary of this run to a "
                         "cumulative BENCH_history.jsonl")
    args = ap.parse_args()
    from benchmarks.common import enable_compilation_cache
    enable_compilation_cache()
    if args.smoke:
        import contextlib
        import io

        class _Tee(io.TextIOBase):
            def write(self, s):
                sys.__stdout__.write(s)
                buf.write(s)
                return len(s)

            def flush(self):
                sys.__stdout__.flush()

        t0 = time.perf_counter()
        buf = io.StringIO()
        with contextlib.redirect_stdout(_Tee()):
            failures = smoke()
        wall = time.perf_counter() - t0
        print(f"# smoke total: {wall:.1f}s", file=sys.stderr)
        # machine-readable gate report: CI uploads it as an artifact
        # so the smoke trajectory (gates + wall) is tracked per run
        report = dict(stamp=time.strftime("%Y%m%d_%H%M%S"),
                      smoke=True, wall_s=round(wall, 1),
                      failures=failures,
                      provenance=_provenance(),
                      gates=[ln for ln in buf.getvalue().splitlines()
                             if ln and not ln.startswith("#")])
        path = args.json or f"BENCH_smoke_{report['stamp']}.json"
        with open(path, "w") as f:
            json.dump(report, f, indent=1)
        print(f"# wrote {path}", file=sys.stderr)
        if args.history:
            append_history(args.history, report)
        sys.exit(1 if failures else 0)
    only = set(args.only.split(",")) if args.only else set(SECTIONS)

    from benchmarks import (ablation_esffh, engine_scale, fig5_capacity,
                            fig6_intensity, fig7_cdf, fig8_timeline,
                            fig_churn, fig_cluster, fig_resilience,
                            kernels_bench, sim_throughput,
                            telemetry_bench)
    scale = float(os.environ.get("REPRO_BENCH_SCALE", "1.0"))
    mods = dict(fig5=fig5_capacity.main, fig6=fig6_intensity.main,
                fig7=fig7_cdf.main, fig8=fig8_timeline.main,
                ablation=ablation_esffh.main,
                cluster=lambda: fig_cluster.main(
                    ["--quick"] if scale < 1.0 else []),
                churn=lambda: fig_churn.main(
                    ["--quick"] if scale < 1.0 else []),
                resilience=lambda: fig_resilience.main(
                    ["--quick"] if scale < 1.0 else []),
                kernels=kernels_bench.main,
                simthroughput=sim_throughput.main,
                # scaled-down aggregate runs skip the 10^6 tier
                enginescale=lambda: engine_scale.main(
                    ["--quick"] if scale < 1.0 else []),
                telemetry=lambda: telemetry_bench.main(
                    ["--n", str(max(int(30_000 * scale), 2_000))]))
    report = dict(stamp=time.strftime("%Y%m%d_%H%M%S"), scale=scale,
                  provenance=_provenance(), sections={})
    for name in SECTIONS:
        if name not in only:
            continue
        print(f"\n===== {name} =====")
        t0 = time.perf_counter()
        rows = mods[name]()
        wall = time.perf_counter() - t0
        print(f"# section {name}: {wall:.1f}s", file=sys.stderr)
        report["sections"][name] = dict(
            wall_s=round(wall, 3),
            rows=rows if isinstance(rows, list) else [])
    path = args.json or f"BENCH_{report['stamp']}.json"
    with open(path, "w") as f:
        json.dump(report, f, indent=1, default=str)
    print(f"# wrote {path}", file=sys.stderr)
    if args.history:
        append_history(args.history, report)
    if args.baseline:
        sys.exit(1 if check_regression(args.baseline, report,
                                       args.regress_tol) else 0)


if __name__ == '__main__':
    main()
