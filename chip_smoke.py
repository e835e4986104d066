"""Chip smoke test: the scheduling engines on a TPU, end to end.

    python3 chip_smoke.py             # phases (a)-(f) on one chip
    python3 chip_smoke.py --chips 4   # lane chunks across four chips

Everything runs in this one process, through the entry points a user
calls (`repro.api.run_experiment` and the ``benchmarks.run`` smoke
gates), with the persistent compilation cache on
(`repro.utils.jit_cache`). The phases, at the paper's scenario
(`repro.configs.paper_edge`: F=200 functions, seed 0):

  (a) the first JAX device is a TPU, or the script exits non-zero;
  (b) ``benchmarks.run.smoke()``: request-for-request parity with the
      Python engine and every bitwise gate, 0 failures required;
  (c) all six policies x capacities (8..32), streaming, clean
      ``check()`` and every request done;
  (d) a four-node cluster of aggregate capacity 32: static ``hash``,
      dynamic ``jsq2``, and ``slo_aware`` under churn, failures and
      retries (``done + shed + failed_exhausted == N``);
  (e) one traced esff lane at C=16: one ARRIVAL per request and one
      EXEC per completion;
  (f) the esff row of (c) again on the host CPU backend: the number of
      metrics whose bits differ from the chip's is printed, not gated.

``--chips 4`` runs only the four-chip phase: a fig5-shaped grid in
single-lane chunks on four chips and on one, bitwise equal, with
chunks placed on every chip.

Each phase prints one line with its wall-clock and compile seconds
(the backend compile time JAX reports), the simulated event count and
the request count N it ran. The last line of standard output is one
JSON object, ``{"ok": true, "device": {...}}``; a failed phase raises
instead, and the script exits non-zero without printing it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

REQUIRED_PLATFORM = "tpu"
# requests of the paper trace run in phases (c)-(f)
N_REQUESTS = 60_000
# requests per lane of the four-chip grid
N_REQUESTS_4 = 6_000
CLUSTER_NODES = 4
CLUSTER_CAPACITY = 32          # aggregate slots across the nodes
TRACED_CAPACITY = 16
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileClock:
    """Sums the backend compile seconds JAX reports, on any thread."""

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.seconds += duration


def run_phase(clock, name, n, fn):
    """Run one phase; print its line and return what ``fn`` returns
    (a dict with ``events`` and an optional ``note``)."""
    c0, t0 = clock.seconds, time.perf_counter()
    res = fn()
    wall = time.perf_counter() - t0
    events = res.get("events")
    print(f"phase {name}: wall_s={wall!r} compile_s={clock.seconds - c0!r}"
          f" events={'n/a' if events is None else int(events)} N={n}"
          + (f" {res['note']}" if res.get("note") else ""), flush=True)
    return res


def paper_source(n):
    from repro.api import SyntheticTrace
    from repro.configs.paper_edge import paper_edge
    cfg = paper_edge()
    return cfg, SyntheticTrace.make(
        n_functions=cfg.n_functions, n_requests=n, seed=cfg.seed,
        utilization=cfg.utilization, exec_median=cfg.exec_median,
        exec_sigma=cfg.exec_sigma, burst_frac=cfg.burst_frac,
        cold_range=cfg.cold_range)


def require(cond, what):
    if not cond:
        raise RuntimeError(f"chip_smoke: {what}")


def all_done(rs, n):
    """Clean `check()` and, without a failure model, every request
    done."""
    rs.check()
    if rs.meta.get("resilience") is None:
        require(bool(np.all(rs.data["done"] == n)),
                f"done != {n}: {np.unique(rs.data['done'])}")
    return rs


def phase_smoke():
    from benchmarks.run import smoke
    failures = smoke()
    require(failures == 0, f"smoke reported {failures} failure(s)")
    return dict(events=None, note="failures=0")


def phase_single(cfg, src, n):
    from benchmarks.common import POLICIES
    from repro.api import ExperimentSpec, run_experiment
    spec = ExperimentSpec(traces=[src], policies=POLICIES,
                          capacities=cfg.capacities, queue_cap=n,
                          stream=True)
    rs = all_done(run_experiment(spec), n)
    return dict(events=rs.data["n_events"].sum(), rs=rs, spec=spec,
                note=f"cells={rs.data['done'].size}")


def phase_cluster(src, n):
    from repro.api import (ClusterSpec, ExperimentSpec, PeriodicChurn,
                           RetryPolicy, run_experiment)
    k = CLUSTER_NODES
    grid = dict(traces=[src], policies=("esff",),
                capacities=(CLUSTER_CAPACITY // k,), queue_cap=n)
    plain = all_done(run_experiment(ExperimentSpec(
        cluster=[ClusterSpec(n_nodes=k, router="hash"),
                 ClusterSpec(n_nodes=k, router="jsq2")], **grid)), n)
    # node 0 stays up; the others are up 70% of each minute, staggered
    churn = (None,) + tuple(
        PeriodicChurn(period=60.0, duty=0.7, phase=i * 60.0 / k)
        for i in range(1, k))
    faulty = all_done(run_experiment(ExperimentSpec(
        fail_prob=0.1, retry=RetryPolicy(max_attempts=3, base=0.05,
                                         cap=1.0, jitter=0.3),
        cluster=[ClusterSpec(n_nodes=k, router="slo_aware",
                             churn=churn)], **grid)), n)
    events = plain.data["n_events"].sum() + faulty.data["n_events"].sum()
    return dict(events=events, note=(
        f"K={k} done={plain.data['done'].ravel().tolist()}"
        f" slo_aware+churn+retry done={int(faulty.data['done'].sum())}"
        f" shed={int(faulty.data['shed'].sum())}"
        f" failed_exhausted={int(faulty.data['failed_exhausted'].sum())}"))


def phase_traced(src, n):
    from repro.api import ExperimentSpec, run_experiment
    from repro.telemetry.rail import TraceKind
    rs = all_done(run_experiment(ExperimentSpec(
        traces=[src], policies=("esff",), capacities=(TRACED_CAPACITY,),
        queue_cap=n, trace_events=True)), n)
    kind = rs.trace.events()["kind"]
    arrivals = int((kind == TraceKind.ARRIVAL).sum())
    execs = int((kind == TraceKind.EXEC).sum())
    done = int(rs.data["done"].sum())
    require(arrivals == n, f"traced ARRIVAL events {arrivals} != N={n}")
    require(execs == done, f"traced EXEC events {execs} != done={done}")
    return dict(events=len(kind),
                note=f"ARRIVAL={arrivals} EXEC={execs} done={done}")


def phase_cpu_bits(single):
    """The esff row of (c) on the host CPU backend. The default device
    is set globally, not by a `jax.default_device` context, because the
    runner's worker threads do not see a context entered here."""
    from dataclasses import replace

    from repro.api import run_experiment
    spec = replace(single["spec"], policies=("esff",))
    chip = single["rs"].sel(policy="esff")
    jax.config.update("jax_default_device", jax.devices("cpu")[0])
    try:
        host = run_experiment(spec)
    finally:
        jax.config.update("jax_default_device", None)
    placed = {d for ids in host.meta["chunk_devices"] for d in ids}
    require(all(d.startswith("cpu:") for d in placed),
            f"CPU rerun ran on {sorted(placed)}")
    differ = sorted(m for m in host.data
                    if not np.array_equal(
                        np.asarray(chip.data[m]).reshape(-1),
                        np.asarray(host.data[m]).reshape(-1)))
    return dict(events=host.data["n_events"].sum(), note=(
        f"metrics_differing={len(differ)}/{len(host.data)}"
        f" {differ}"))


def phase_four_chips(n):
    from benchmarks.common import POLICIES
    from repro.api import ExperimentSpec, run_experiment
    devs = jax.devices()
    require(len(devs) >= 4, f"--chips 4 needs 4 devices, found {len(devs)}")
    cfg, src = paper_source(n)
    kw = dict(traces=[src], policies=POLICIES[:2],
              capacities=cfg.capacities, queue_cap=n, lane_chunk=1)
    one = all_done(run_experiment(ExperimentSpec(devices=1, **kw)), n)
    four = all_done(run_experiment(ExperimentSpec(devices=4, **kw)), n)
    chunks = four.meta["chunk_devices"]
    placed = {d for ids in chunks for d in ids}
    want = {f"{d.platform}:{d.id}" for d in devs[:4]}
    require(four.meta["n_devices"] == 4,
            f"n_devices={four.meta['n_devices']}")
    require(len(chunks) >= 8, f"only {len(chunks)} chunks")
    require(placed == want, f"chunks on {sorted(placed)}, want {sorted(want)}")
    differ = [m for m in one.data
              if not np.array_equal(one.data[m], four.data[m])]
    require(not differ, f"devices=4 differs from devices=1 in {differ}")
    return dict(events=one.data["n_events"].sum(), note=(
        f"chunks={len(chunks)} on {sorted(placed)}; devices=4 bitwise"
        f" equal to devices=1 on {len(one.data)} metrics"))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip lane-sharding phase")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != REQUIRED_PLATFORM:
        raise SystemExit(f"chip_smoke: the first JAX device is "
                         f"{dev.platform!r}, not {REQUIRED_PLATFORM!r}")
    from repro.utils.jit_cache import enable_compilation_cache
    enable_compilation_cache()
    clock = CompileClock()
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}"
          f" jax={jax.__version__}", flush=True)

    if args.chips == 4:
        run_phase(clock, "four_chips", N_REQUESTS_4,
                  lambda: phase_four_chips(N_REQUESTS_4))
    else:
        cfg, src = paper_source(N_REQUESTS)
        run_phase(clock, "b_smoke", 400, phase_smoke)
        single = run_phase(clock, "c_single", N_REQUESTS,
                           lambda: phase_single(cfg, src, N_REQUESTS))
        run_phase(clock, "d_cluster", N_REQUESTS,
                  lambda: phase_cluster(src, N_REQUESTS))
        run_phase(clock, "e_traced", N_REQUESTS,
                  lambda: phase_traced(src, N_REQUESTS))
        run_phase(clock, "f_cpu_bits", N_REQUESTS,
                  lambda: phase_cpu_bits(single))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
