"""Where a benchmark unit's wall time goes, read from the runner's own
spans and loop-step counter, and how the event step splits by named
scope. Needs a TPU; run it from the root of the checkout:

    python3 scripts/span_report.py --workload paper_edge.fig5 \
        --scopes-n 300 --out span_report.json
    python3 scripts/span_report.py --workload keepalive_node.ram_grid \
        --scopes-policy faascache --out span_report.json

Without ``--out`` the whole report goes to standard output.

Part 1 runs one pool unit of the cell (`run_experiment` + `check()`)
warm without the profiler, then under the benchmark's host-mode
profiler (`bench.trace.capture`), then again without it; it reduces the
``repro.*`` spans and their stats (docs/observability.md, "Performance
spans") and sets them beside what `bench.trace` reads from the same
trace, with the launches' counters summed (``loop_steps``, and on a node
of memory ``multi_victim_starts`` and ``mem_waits``). Part 2 traces one
launch of ``--scopes-policy`` (esff by default) over the cell's
capacities and options and the first ``--scopes-n`` requests of that
stream in the profiler's default mode, which records every device op,
and sums the device time of the ops under each of the event step's
named scopes; ``repro.admit`` (a memory-mode cold start) is counted
apart from the ``repro.policy`` scope it sits in.
"""
import argparse
import glob
import json
import os
import re
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

SERIAL = ("repro.lower", "repro.assemble", "repro.check")
# the first that an op's scope path names: repro.admit nests in
# repro.policy
SCOPES = ("repro.pick", "repro.admit", "repro.policy", "repro.fold",
          "repro.flush")
COUNTERS = ("loop_steps", "multi_victim_starts", "mem_waits")


def union_s(intervals) -> float:
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e > end:
            total += e - max(s, end)
            end = e
    return total


def profile(trace_dir):
    import jax
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return jax.profiler.ProfileData.from_file(path)


def spans(pd) -> dict:
    """``repro.*`` and ``bench.unit`` host events: name -> list of
    (start_s, end_s, stats)."""
    out = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro.") or e.name == "bench.unit":
                    out.setdefault(e.name, []).append(
                        (e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9,
                         dict(e.stats)))
    return out


def reduce_unit(pd) -> dict:
    from bench import trace
    sp = spans(pd)
    (lo, hi, _), = sp["bench.unit"]
    fetch = sp.get("repro.fetch", [])
    steps = sum(st["loop_steps"] for _, _, st in fetch)
    lane_steps = sum(st["loop_steps"] * st["lanes"] for _, _, st in fetch)
    fetch_s = union_s((s, e) for s, e, _ in fetch)
    serial_s = union_s((s, e) for n in SERIAL for s, e, _ in sp.get(n, []))
    td = trace.TraceData.from_profile(pd)
    return {
        "window_s": hi - lo,
        "spans": {n: {"count": len(v), "union_s": union_s(
            (s, e) for s, e, _ in v)} for n, v in sorted(sp.items())},
        "dispatch": [st for _, _, st in sp.get("repro.dispatch", [])],
        "counters": {k: sum(st.get(k, 0) for _, _, st in fetch)
                     for k in COUNTERS},
        "fetch": [dict(st, start_s=s - lo, end_s=e - lo)
                  for s, e, st in fetch],
        "device_us_per_step.single": 1e6 * fetch_s / steps,
        "lane_occupancy.single": 100.0 * sum(
            st["lane_events"] for _, _, st in fetch) / lane_steps,
        "runner_serial_share": 100.0 * serial_s / (hi - lo),
        "fetch_union_s": fetch_s,
        "reducer": {"busy_s": td.busy_s, "window_s": td.window_s,
                    "launches": td.launches(("_sweep_metrics",)),
                    "executions": [[e.name, e.start - lo, e.end - lo]
                                   for e in td.modules],
                    "breakdown": td.breakdown()},
    }


HLO_OP = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?op_name="([^"]*)"', re.M)
# a device op event is named by its HLO instruction:
# "%fusion.12 = f32[7]{0} fusion(...), kind=kLoop, ..."
OP_EVENT = re.compile(r"%?([\w.\-]+) = .*?\s([a-z][\w\-]*)\(")
CONTAINERS = ("while", "conditional", "call")


def scope_of(name: str, op_names: dict) -> str:
    """The named scope an instruction ran under, from the compiled
    program's op metadata."""
    text = op_names.get(name, "")
    return next((s for s in SCOPES if s in text), "other")


def scope_split(cell, stream, n: int, policy: str = "esff") -> dict:
    """Device time per named scope of one ``policy`` launch over the
    first ``n`` requests of ``stream``, in a default-mode trace."""
    import jax
    from repro.api import ArrayTrace, ExperimentSpec, run_experiment
    from repro.core import jax_engine
    head = {k: (v[:n] if len(v) == len(stream["fn_id"]) else v)
            for k, v in stream.items()}
    spec = ExperimentSpec(traces=[ArrayTrace.make(head, "head")],
                          policies=(policy,), capacities=cell.capacities,
                          queue_cap=n, stream=True,
                          prior=cell.config["prior_s"], **cell.options)
    sweep, calls = jax_engine._sweep_metrics, []

    def recorded(*a, **kw):
        calls.append((a, kw))
        return sweep(*a, **kw)

    jax_engine._sweep_metrics = recorded
    try:
        run_experiment(spec).check()
    finally:
        jax_engine._sweep_metrics = sweep
    (a, kw), = calls
    hlo = sweep.lower(*a, **kw).compile().as_text()
    op_names = dict(HLO_OP.findall(hlo))
    tmp = tempfile.mkdtemp(prefix="scopes-")
    jax.profiler.start_trace(tmp)
    try:
        rs = run_experiment(spec)
    finally:
        jax.profiler.stop_trace()
    pd = profile(tmp)
    shutil.rmtree(tmp, ignore_errors=True)
    tot, by_op, ops, module_us = {}, {}, 0, 0.0
    for plane in pd.planes:
        if not plane.name.startswith("/device:TPU"):
            continue
        for line in plane.lines:
            if line.name == "XLA Modules":
                module_us += sum(e.duration_ns for e in line.events) * 1e-3
            if line.name != "XLA Ops":
                continue
            for e in line.events:
                m = OP_EVENT.match(e.name)
                name, opcode = m.groups() if m else (e.name, "?")
                if opcode in CONTAINERS:
                    continue
                ops += 1
                us = e.duration_ns * 1e-3
                s = scope_of(name, op_names)
                tot[s] = tot.get(s, 0.0) + us
                by_op[(s, opcode)] = by_op.get((s, opcode), 0.0) + us
    steps, = rs.meta["loop_steps"]
    top = sorted(by_op.items(), key=lambda x: -x[1])[:15]
    return {"policy": policy, "n_requests": n,
            "lanes": len(cell.capacities),
            "loop_steps": steps, "op_events": ops,
            "module_us": module_us, "device_us": tot,
            "device_us_per_step": {k: v / steps for k, v in tot.items()},
            "top_ops_us": [[s, op, us] for (s, op), us in top]}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", default="paper_edge.fig5")
    ap.add_argument("--unit", type=int, default=0)
    ap.add_argument("--scopes-n", type=int, default=300)
    ap.add_argument("--scopes-policy", default="esff")
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    import jax
    from bench import trace
    from bench.cell import Cell
    from repro.api import run_experiment
    cell = Cell.load(args.workload)
    streams = [cell.stream(args.unit, k)
               for k in range(cell.traffic["streams_per_unit"])]
    spec = cell.spec(streams)

    def unit():
        t0 = time.perf_counter()
        run_experiment(spec).check()
        return time.perf_counter() - t0

    report = {"device": jax.devices()[0].device_kind, "unit": args.unit,
              "warmup_s": unit(), "untraced_before_s": unit()}
    tmp = tempfile.mkdtemp(prefix="unit-")
    with trace.capture(tmp):
        report["traced_s"] = unit()
    report["untraced_after_s"] = unit()
    report.update(reduce_unit(profile(tmp)))
    shutil.rmtree(tmp, ignore_errors=True)
    if args.scopes_n:
        report["scopes"] = scope_split(cell, streams[0], args.scopes_n,
                                       args.scopes_policy)
    if args.out is None:
        print(json.dumps(report, indent=1))
        return
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    brief = {k: v for k, v in report.items()
             if k not in ("fetch", "dispatch", "reducer")}
    print(json.dumps(brief, indent=1))


if __name__ == "__main__":
    main()
