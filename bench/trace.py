"""Profiler capture of one unit and its reduction to per-layer metrics.

`capture` wraps the traced unit in `jax.profiler` with the TPU tracer
in host mode (``tpu_trace_mode=TRACE_ONLY_HOST``): an event loop runs
tens of thousands of while-iterations of hundreds of small ops, and a
per-op device trace of one unit is hundreds of megabytes (one esff
launch at N=6,000 wrote 3.4 million op events). The benchmark's own
host spans (``bench.*`` annotations) land on the same clock.

`reduce` reads the written ``.xplane.pb`` with
`jax.profiler.ProfileData` and returns a `TraceData`: the program
launches (the host's ``PjitFunction(<name>)`` events), the device's
program executions, the traced window (the ``bench.unit`` span) and
the host spans. Host mode writes no device line, so the TPU runtime's
host events give the executions: the k-th ``tpu::System::Execute``
(the launch onto the device's in-order queue) and the k-th
``tpu::System::Execute=>Done`` (its completion seen by the host) bound
the k-th execution, which starts no earlier than the one before it
ends. The per-layer metrics of ``bench/metrics/`` read it.
"""
from __future__ import annotations

import contextlib
import glob
import importlib.util
import os
import shutil
from dataclasses import dataclass

import numpy as np

METRICS_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "metrics")
UNIT_SPAN = "bench.unit"
TRACE_MODE = "TRACE_ONLY_HOST"
LAUNCH = "PjitFunction("
EXECUTE = "tpu::System::Execute"
DONE = "tpu::System::Execute=>Done"


@contextlib.contextmanager
def capture(trace_dir: str):
    """Profile the block into ``trace_dir`` as one ``bench.unit``."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.advanced_configuration = {"tpu_trace_mode": TRACE_MODE}
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(UNIT_SPAN):
            yield
    finally:
        jax.profiler.stop_trace()


@dataclass
class Event:
    name: str
    start: float      # seconds on the trace's clock
    end: float
    line: str = ""    # the host thread's line


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


@dataclass
class TraceData:
    """What one traced unit left in the profile."""

    modules: list               # device program executions (Event)
    host: list                  # host events (Event)
    window: tuple               # (start, end) of the traced unit
    units: int = 1
    steps: int = 0      # event-loop steps of the traced units

    @staticmethod
    def from_profile(pd, units: int = 1, steps: int = 0) -> "TraceData":
        host = [Event(e.name, e.start_ns * 1e-9,
                      (e.start_ns + e.duration_ns) * 1e-9,
                      f"{plane.name}|{line.name}")
                for plane in pd.planes if not plane.name.startswith("/device:")
                for line in plane.lines for e in line.events]
        spans = [e for e in host if e.name == UNIT_SPAN]
        if not spans:
            raise RuntimeError(f"the profile holds no {UNIT_SPAN} span")
        window = (min(e.start for e in spans), max(e.end for e in spans))
        return TraceData(executions(host), host, window, units, steps)

    # ------------------------------------------------------------ device
    def launches(self, programs) -> int:
        """Calls of the jitted functions named in ``programs`` (such as
        ``_sweep_metrics``), from the host's launch events; a launch
        event nested in another of its thread is the same call."""
        return len(outermost(e for e in self.host
                             if e.name.startswith(LAUNCH)
                             and e.name[len(LAUNCH):-1] in programs))

    def busy(self):
        lo, hi = self.window
        return _union((max(e.start, lo), min(e.end, hi))
                      for e in self.modules if e.end > lo and e.start < hi)

    @property
    def busy_s(self) -> float:
        return float(sum(e - s for s, e in self.busy()))

    @property
    def window_s(self) -> float:
        return float(self.window[1] - self.window[0])

    def gaps(self):
        """Idle intervals of the device inside the window."""
        lo, hi = self.window
        out, t = [], lo
        for s, e in self.busy():
            if s > t:
                out.append((t, s))
            t = max(t, e)
        if hi > t:
            out.append((t, hi))
        return out

    # -------------------------------------------------------- reporting
    def host_at(self, start: float, end: float) -> str:
        """What the host was doing in ``[start, end]``: the innermost
        benchmark span at its middle, and the other host event that
        covers most of it."""
        mid = 0.5 * (start + end)
        bench = [e for e in self.host if e.name.startswith("bench.")
                 and e.start <= mid <= e.end]
        label = (min(bench, key=lambda e: e.end - e.start).name
                 if bench else "none")

        def cover(e):
            return min(e.end, end) - max(e.start, start)
        other = [e for e in self.host if not e.name.startswith("bench.")
                 and cover(e) > 0]
        if other:
            label += " / " + max(other, key=lambda e: (
                cover(e), e.start - e.end)).name
        return label

    def breakdown(self) -> dict:
        """Up to ten device programs by total time and the ten longest
        idle gaps, each named by what the host was doing."""
        tot = {}
        for e in self.modules:
            tot[e.name] = tot.get(e.name, 0.0) + (e.end - e.start)
        ops = sorted(tot.items(), key=lambda x: -x[1])[:10]
        gaps = sorted(self.gaps(), key=lambda g: g[0] - g[1])[:10]
        return {"device_ops": [[n, float(s)] for n, s in ops],
                "idle_gaps": [[self.host_at(s, e), float(e - s)]
                              for s, e in gaps]}

    def metrics(self) -> dict:
        """Every per-layer metric of ``bench/metrics/`` that finds
        something to read in this trace."""
        out = {}
        for mod in load_metrics():
            value = mod.read(self)
            if value is not None:
                out[mod.NAME] = {"value": float(value), "unit": mod.UNIT}
        return out


def executions(host: list) -> list:
    """Device executions from the TPU runtime's host events: launch k
    and completion k bound execution k on the in-order queue. They take
    the names of the jitted calls, in order, where the counts agree."""
    launch = sorted((e for e in host if e.name == EXECUTE),
                    key=lambda e: e.start)
    done = sorted((e for e in host if e.name == DONE), key=lambda e: e.start)
    calls = outermost(e for e in host if e.name.startswith(LAUNCH))
    names = ([e.name for e in sorted(calls, key=lambda e: e.start)]
             if len(calls) == len(launch) else ["device execution"] * len(launch))
    out, prev = [], float("-inf")
    for a, b, name in zip(launch, done, names):
        start = max(a.start, prev)
        out.append(Event(name, start, b.start))
        prev = b.start
    return out


def outermost(events) -> list:
    """``events`` less those nested in another of their thread's."""
    out = []
    for e in sorted(events, key=lambda e: (e.line, e.start, -e.end)):
        if not out or e.line != out[-1].line or e.end > out[-1].end:
            out.append(e)
    return out


def load_metrics() -> list:
    """The per-layer metric readers, one module per ``*.py`` file,
    each with ``NAME``, ``UNIT``, ``LAYER``, ``MOVES`` and ``read``."""
    mods = []
    for path in sorted(glob.glob(os.path.join(METRICS_DIR, "*.py"))):
        name = os.path.basename(path)[:-3]
        spec = importlib.util.spec_from_file_location(
            f"bench_metric_{name.replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        if mod.NAME != name:
            raise ValueError(f"{path} declares NAME={mod.NAME!r}")
        mods.append(mod)
    return mods


def reduce(trace_dir: str, traced: list) -> TraceData:
    """Read the profile under ``trace_dir``, then delete it;
    ``traced`` are the `ResultSet`s of the traced units."""
    import jax
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise RuntimeError(f"no profile written under {trace_dir}")
    pd = jax.profiler.ProfileData.from_file(files[0])
    steps = int(sum(np.asarray(rs.data["n_events"]).sum() for rs in traced))
    data = TraceData.from_profile(pd, units=len(traced), steps=steps)
    shutil.rmtree(trace_dir, ignore_errors=True)
    return data
