"""Benchmark entry point: one cell of `BENCHMARK.json` on the chips of
this machine.

    python3 bench/run.py --workload paper_edge.fig5 --seed 7 \
        --seconds 30 --trace 0

A unit of work is one `repro.api.run_experiment` over the cell's spec
on a set of request streams, followed by `ResultSet.check()` (see
`bench.cell`). The traffic mix fixes a pool of units; ``--seed`` orders
it. Set-up makes the streams of the pool, loads or compiles the
programs and runs one warm-up unit. The window then runs the pool in
that order, back to back, cycle after cycle, until a cycle ends after
``--seconds`` have passed; so every run does the same work:

* ``--trace 0``: ``sim_req_per_s``, the requests x lanes of the units
  of the window over the wall time from the first unit's start to the
  last unit's end, and ``setup_s``, process start to the first timed
  unit;
* ``--trace 1``: one unit under the profiler, reduced to the per-layer
  metrics of ``bench/metrics/`` and a breakdown of device time and idle
  gaps.

After the window, every lane of units drawn from the seed is compared
with the plain reference (`bench.check`). The last line of standard output is one JSON
object; its last key, ``checks``, gives each number compared beside its
limit, which the last lines of standard error repeat. The run exits
non-zero, printing no result, when JAX finds no TPU or fewer chips than
the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import check  # noqa: E402
from bench.cell import Cell, lane_values  # noqa: E402

REQUIRED_PLATFORM = "tpu"
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"


class CompileClock:
    """Backend compiles and persistent-cache loads JAX reports, on any
    thread: their count and seconds."""

    def __init__(self, jax):
        self.compiles = self.loads = 0
        self.compile_s = self.load_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.compiles += 1
            self.compile_s += duration
        elif event == CACHE_LOAD_EVENT:
            self.loads += 1
            self.load_s += duration

    @property
    def programs(self) -> int:
        return self.compiles + self.loads


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def per_layer(workload: str) -> list:
    """The per-layer metrics `BENCHMARK.json` has the cell report."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    return [m["name"] for m in doc["per_layer"]
            if workload in m.get("workloads", [workload])]


def open_devices(jax, chips: int):
    """The cell's devices; exits non-zero without a result when JAX
    finds no TPU or too few chips."""
    devs = jax.devices()
    if devs[0].platform != REQUIRED_PLATFORM or len(devs) < chips:
        raise SystemExit(
            f"bench: the cell needs {chips} {REQUIRED_PLATFORM} chip(s); "
            f"JAX found {len(devs)} {devs[0].platform} device(s)")
    return devs[:chips]


def run_unit(run_experiment, cell, streams):
    """One unit: ``(ResultSet, ok)``; a failed ``check()`` is a failed
    unit, not a crash. The host spans show in a traced unit."""
    from jax.profiler import TraceAnnotation
    with TraceAnnotation("bench.run_experiment"):
        rs = run_experiment(cell.spec(streams))
    try:
        with TraceAnnotation("bench.check"):
            rs.check()
    except RuntimeError as e:
        print(f"bench: unit failed its check: {e}", file=sys.stderr)
        return rs, False
    return rs, True


def correctness(cell, pool, results, seed):
    """Compare every lane of the units drawn from the seed among the
    pool units the window ran (``results``: unit -> its first
    `ResultSet`) with the reference."""
    units, rows = sorted(results), []
    for i in check.draw(len(units), cell.workload["check_units"], seed):
        u = units[i]
        for lane in cell.lanes:
            rows.append((lane,
                         lane_values(results[u], *lane, cell.check_stats),
                         check.reference(cell, pool[u], lane)))
    return check.compare(rows, cell.check_stats)


def main(argv=None):
    args = parse(argv)
    cell = Cell.load(args.workload)
    os.makedirs(CACHE_DIR, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax
    devs = open_devices(jax, cell.workload["chips"])
    # eviction off: with a size limit from the environment, a cache
    # entry whose access-time file is missing fails every later write
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    clock = CompileClock(jax)
    cached = len(os.listdir(CACHE_DIR))
    from repro.api import run_experiment

    cell.spec()  # an option the program lacks fails before any stream
    pool, order = cell.pool(), cell.order(args.seed)
    t_warm = time.perf_counter()
    _, ok = run_unit(run_experiment, cell, pool[order[0]])
    if not ok:
        raise SystemExit("bench: the warm-up unit failed its check")
    t0 = time.perf_counter()
    setup_s = t0 - T_START
    print(f"setup: total_s={setup_s!r} warmup_unit_s={t0 - t_warm!r} "
          f"compiles={clock.compiles} compile_s={clock.compile_s!r} "
          f"cache_loads={clock.loads} cache_load_s={clock.load_s!r} "
          f"cache_files_before={cached} "
          f"cache_files_after={len(os.listdir(CACHE_DIR))}",
          file=sys.stderr)
    programs0 = clock.programs

    results, attempted, failed = {}, 0, 0
    trace_dir = breakdown = None
    if args.trace:
        from bench import trace
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        with trace.capture(trace_dir):
            rs, ok = run_unit(run_experiment, cell, pool[order[0]])
        results[order[0]] = rs
        attempted, failed = 1, int(not ok)
        t_end = time.perf_counter()
    else:
        t_end = t0
        while t_end - t0 < args.seconds:
            for u in order:
                rs, ok = run_unit(run_experiment, cell, pool[u])
                attempted += 1
                failed += int(not ok)
                results.setdefault(u, rs)
            t_end = time.perf_counter()
    window_compiles = clock.programs - programs0
    print(f"window: units={attempted} seconds={t_end - t0!r} "
          f"compiles_in_window={window_compiles}", file=sys.stderr)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}

    if args.trace:
        from bench import trace
        data = trace.reduce(trace_dir, list(results.values()))
        metrics = data.metrics()
        missing = [m for m in per_layer(cell.name) if m not in metrics]
        if missing:
            raise SystemExit(f"bench: the trace gave nothing to read for "
                             f"{', '.join(missing)}")
        device.update(busy_s=data.busy_s, window_s=data.window_s)
        breakdown = data.breakdown()
    else:
        rate = attempted * cell.requests_per_unit / (t_end - t0)
        metrics = {"sim_req_per_s": {"value": rate, "unit": "req/s"},
                   "setup_s": {"value": setup_s, "unit": "s"}}

    checks = correctness(cell, pool, results, args.seed)
    correct = check.passed(checks) and failed == 0
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    out = {"correct": correct,
           "attempted": attempted * cell.requests_per_unit,
           "failed": failed * cell.requests_per_unit,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
