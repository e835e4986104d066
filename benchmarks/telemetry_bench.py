"""Telemetry overhead microbench.

Times the same grid with tracing off and on, reports the in-loop
trace rail's overhead (the disabled path is *bitwise free* — gated in
``--smoke`` — so the interesting number is the enabled path's cost:
one record scatter per event plus one ordered host flush per
segment), and records run provenance for the BENCH report.

    PYTHONPATH=src python -m benchmarks.telemetry_bench [--n N]
"""
from __future__ import annotations

import argparse

from benchmarks.common import (bench_repeats, default_trace_source,
                               emit, enable_compilation_cache, timed)
from repro.api import ExperimentSpec, run_experiment
from repro.telemetry import provenance, save_trace

N_REQUESTS = 30_000
CAPACITY = 16


def run(n: int = N_REQUESTS, trace_json: str = ""):
    src = default_trace_source(seed=0, n_requests=n)
    src.arrays()
    rows = []
    rs_traced = None
    for traced in (False, True):
        spec = ExperimentSpec(traces=[src], policies=("esff",),
                              capacities=(CAPACITY,),
                              queue_cap=1 << 17, stream=True,
                              trace_events=traced)
        run_experiment(spec)                      # warm the jit cache
        rs, dt = timed(run_experiment, spec,
                       repeats=bench_repeats(n))
        rs.check()
        if traced:
            rs_traced = rs
        rows.append(dict(
            name=f"esff_N{n}_{'traced' if traced else 'untraced'}",
            n_requests=n, us_per_call=dt * 1e6, req_s=n / dt,
            events=(rs.trace.n_events if traced else 0),
            derived=f"{n / dt:.0f} req/s "
                    + ("(trace rail on)" if traced else "(baseline)")))
    base, tr = rows[0]["req_s"], rows[1]["req_s"]
    rows.append(dict(name=f"esff_N{n}_overhead", n_requests=n,
                     us_per_call=0.0, req_s=tr, events=rows[1]["events"],
                     derived=f"enabled-tracing overhead "
                             f"{100 * (base / tr - 1):.0f}% "
                             f"({rows[1]['events']} events)"))
    if trace_json and rs_traced is not None:
        ev = rs_traced.trace.events(policy="esff")
        save_trace(ev, trace_json, label=f"esff_N{n}")
    return rows


def main(argv=None):
    enable_compilation_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=N_REQUESTS)
    ap.add_argument("--trace-json", default="",
                    help="also export the traced run as Perfetto "
                         "trace_event JSON")
    args = ap.parse_args(argv)
    rows = run(n=args.n, trace_json=args.trace_json)
    emit(rows, ("name", "n_requests", "us_per_call", "req_s",
                "events", "derived"))
    prov = provenance()
    print(f"# provenance: backend={prov['backend']} "
          f"x64={prov['x64']} jit_caches={prov['jit_cache_sizes']}")
    return rows


if __name__ == "__main__":
    main()
