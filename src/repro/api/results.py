"""Labeled experiment results: the `ResultSet`.

Every metric array carries the grid axes ``(policy, trace, capacity,
beta)`` in that order — plus a trailing ``cluster`` axis when the
producing `ExperimentSpec` declared one (`repro.cluster.ClusterSpec`
entries; its coords are the entries' router-first labels). Trailing
metric-specific dims — histogram bins, timeline bins, per-node counts,
per-request N — follow the grid axes, with the axis values in
``coords``. Selection (`sel` / `value`), tidy-row iteration (`rows`),
CSV emission (`to_csv`) and an npz round-trip (`save_npz`/`load_npz`)
replace the per-benchmark CSV/dict plumbing; `merge` reassembles
``host_shard`` partials computed on different machines. A ``computed``
mask tracks which grid cells this ResultSet actually holds (all of
them unless the producing run was host-sharded).
"""
from __future__ import annotations

import csv
import io
import json
import sys
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Optional, Sequence

import numpy as np

DIMS = ("policy", "trace", "capacity", "beta")
CLUSTER_DIM = "cluster"     # optional trailing axis of cluster runs

# metrics that must be zero on every computed cell for a run to be
# valid (mirrors the overflow/stalled checks the figure scripts used
# to hand-roll)
HEALTH_METRICS = ("overflow", "stalled")


@dataclass
class ResultSet:
    """Metric arrays over the labeled experiment grid."""

    data: Dict[str, np.ndarray]
    coords: Dict[str, list]
    computed: Optional[np.ndarray] = None    # (P, T, K, B) bool
    meta: dict = field(default_factory=dict)
    # per-cell event streams of a trace_events=True run
    # (`repro.telemetry.TraceRun`); not part of the npz payload —
    # export separately with `trace.save_npz`
    trace: Optional[object] = None

    def __post_init__(self):
        shape = self.grid_shape
        nd = len(shape)
        if self.computed is None:
            self.computed = np.ones(shape, bool)
        for k, v in self.data.items():
            if tuple(v.shape[:nd]) != shape:
                raise ValueError(
                    f"ResultSet: metric {k!r} shape {v.shape} does not "
                    f"lead with the grid shape {shape}")

    # ----------------------------------------------------------- basics
    @property
    def dims(self):
        """Grid axis names: the four core dims, plus ``cluster`` when
        the producing spec declared a cluster axis."""
        return (DIMS + (CLUSTER_DIM,) if CLUSTER_DIM in self.coords
                else DIMS)

    @property
    def grid_shape(self):
        return tuple(len(self.coords[d]) for d in self.dims)

    @property
    def metrics(self) -> List[str]:
        return sorted(self.data)

    def __getitem__(self, metric: str) -> np.ndarray:
        try:
            return self.data[metric]
        except KeyError:
            raise KeyError(f"ResultSet: no metric {metric!r}; have "
                           f"{self.metrics}") from None

    def __contains__(self, metric: str) -> bool:
        return metric in self.data

    # -------------------------------------------------------- selection
    def _axis_indices(self, dim: str, want) -> List[int]:
        values = self.coords[dim]
        singular = not isinstance(want, (list, tuple, np.ndarray))
        wants = [want] if singular else list(want)
        idx = []
        for w in wants:
            matches = [i for i, v in enumerate(values)
                       if v == w or (isinstance(v, float)
                                     and isinstance(w, (int, float))
                                     and float(v) == float(w))]
            if not matches:
                raise KeyError(
                    f"ResultSet.sel: {dim}={w!r} not on the {dim} axis "
                    f"{values}")
            if singular and len(matches) > 1:
                raise KeyError(
                    f"ResultSet.sel: {dim}={w!r} is ambiguous "
                    f"({len(matches)} axis entries match) — pass a "
                    f"list to select all of them")
            idx.extend(matches)
        return idx

    def sel(self, **which) -> "ResultSet":
        """Subset by coordinate *value* (scalar or list per dim), e.g.
        ``rs.sel(policy="esff", capacity=[8, 16])``. Axes are retained
        (scalar selections become size-1) so any selection round-trips
        through ``save_npz``/``merge``; use `value` for one cell."""
        dims = self.dims
        unknown = set(which) - set(dims)
        if unknown:
            raise KeyError(f"ResultSet.sel: unknown dim(s) "
                           f"{sorted(unknown)}; dims are {dims}")
        index = [slice(None)] * len(dims)
        coords = dict(self.coords)
        for d, want in which.items():
            ax = dims.index(d)
            ids = self._axis_indices(d, want)
            index[ax] = ids
            coords[d] = [self.coords[d][i] for i in ids]
        data = {}
        for k, v in self.data.items():
            out = v
            for ax, ids in enumerate(index):
                if not isinstance(ids, slice):
                    out = np.take(out, ids, axis=ax)
            data[k] = out
        comp = self.computed
        for ax, ids in enumerate(index):
            if not isinstance(ids, slice):
                comp = np.take(comp, ids, axis=ax)
        return ResultSet(data=data, coords=coords, computed=comp,
                         meta=dict(self.meta))

    def value(self, metric: str, **which):
        """The one cell of ``metric`` selected by ``which`` (every grid
        axis must resolve to a single entry). Returns a python scalar
        for scalar metrics, an ndarray for metrics with trailing dims
        (``resp_hist``, ``tl_*``, ``response``)."""
        sub = self.sel(**which) if which else self
        nd = len(sub.dims)
        if sub.grid_shape != (1,) * nd:
            raise KeyError(
                f"ResultSet.value({metric!r}): selection leaves grid "
                f"{dict(zip(sub.dims, sub.grid_shape))}, need exactly "
                "one cell — add coords")
        if not sub.computed.reshape(-1)[0]:
            raise ValueError(
                f"ResultSet.value({metric!r}): cell not computed (this "
                "is a host shard — merge() the other shards first)")
        cell = sub[metric][(0,) * nd]
        return cell.item() if np.ndim(cell) == 0 else np.asarray(cell)

    # -------------------------------------------------------- telemetry
    def timeline(self, bucket: float = 60.0, *, deadlines=None,
                 **sel) -> Dict[str, np.ndarray]:
        """Streaming per-bin time series of one traced grid cell.

        Requires a run with ``trace_events=True`` (the attached
        `repro.telemetry.TraceRun`). ``sel`` selects one cell exactly
        like `value` (axes of length one resolve implicitly); returns
        the `repro.telemetry.metrics.timeline` dict — per-node queue
        depth, warm occupancy, utilization, throughput, goodput and
        SLO attainment per ``bucket``-second bin. ``deadlines``
        defaults to the producing spec's (from ``meta``)."""
        if self.trace is None:
            raise ValueError(
                "ResultSet.timeline: no event streams attached — run "
                "with ExperimentSpec(trace_events=True)")
        from repro.telemetry import metrics as _tmet
        ev = self.trace.events(**sel)
        key = self.trace._cell_key(**sel)
        tr_coords = self.trace.coords
        cap = None
        if "capacity" in tr_coords:
            c = tr_coords["capacity"][
                key[list(tr_coords).index("capacity")]]
            if isinstance(c, (int, np.integer)):
                cap = int(c)
        if deadlines is None:
            deadlines = self.meta.get("deadlines")
        return _tmet.timeline(ev, bucket=bucket, capacity=cap,
                              deadlines=deadlines)

    # ------------------------------------------------------- tidy rows
    def rows(self, metrics: Optional[Sequence[str]] = None
             ) -> Iterator[dict]:
        """Tidy iteration: one dict per computed grid cell carrying
        every grid coordinate (the four core dims, plus ``cluster``
        when the producing spec declared one) and every scalar metric
        (vector metrics are skipped unless named explicitly in
        ``metrics``)."""
        dims = self.dims
        names = list(metrics) if metrics is not None else [
            m for m in self.metrics if self.data[m].ndim == len(dims)]
        for cell_ix in np.ndindex(*self.grid_shape):
            if not self.computed[cell_ix]:
                continue
            row = {d: self.coords[d][i]
                   for d, i in zip(dims, cell_ix)}
            for m in names:
                cell = self.data[m][cell_ix]
                row[m] = (cell.item() if np.ndim(cell) == 0
                          else np.asarray(cell))
            yield row

    def to_csv(self, out=None,
               metrics: Optional[Sequence[str]] = None) -> str:
        """Write the tidy rows as CSV to ``out`` (path, file object, or
        None for stdout); returns the header line for convenience."""
        rows = list(self.rows(metrics))
        if not rows:
            raise ValueError("ResultSet.to_csv: no computed cells")
        header = list(rows[0].keys())

        def _write(fh):
            w = csv.DictWriter(fh, fieldnames=header)
            w.writeheader()
            for r in rows:
                w.writerow({k: (f"{v:.6g}" if isinstance(v, float)
                                else v) for k, v in r.items()})
        if out is None:
            _write(sys.stdout)
        elif isinstance(out, (str, bytes)) or hasattr(out, "__fspath__"):
            with open(out, "w", newline="") as fh:
                _write(fh)
        else:
            _write(out)
        return ",".join(header)

    # ----------------------------------------------------------- health
    def _cell_label(self, cell_ix) -> str:
        """One grid cell's full spec coordinate, e.g.
        ``policy='esff', trace='zipf[n8000]', capacity=16,
        beta='default'`` (plus ``cluster=...`` on cluster grids)."""
        return ", ".join(f"{d}={self.coords[d][i]!r}"
                         for d, i in zip(self.dims, cell_ix))

    def _bad_cells(self, bad: np.ndarray, limit: int = 8) -> str:
        cells = np.argwhere(bad)[:limit]
        named = "; ".join(self._cell_label(tuple(c)) for c in cells)
        more = int(bad.sum()) - len(cells)
        return named + (f"; ... {more} more" if more > 0 else "")

    def check(self) -> "ResultSet":
        """Raise if any computed cell is invalid; returns self for
        chaining.

        Invalid means: nonzero ``overflow`` (a queue overran with
        shedding *disabled* — requests silently dropped; deliberate
        drops under ``on_overflow="shed"``/``"shed_oldest"`` land in
        the ``shed`` counter instead and are by design, never an
        error), nonzero ``stalled`` (the event loop hit its iteration
        cap — an engine invariant violation), or — on fault-injected
        runs — a broken conservation identity
        ``done + shed + failed_exhausted != n_requests``. Every error
        names the offending cells by their full spec coordinate."""
        from jax.profiler import TraceAnnotation

        with TraceAnnotation("repro.check"):
            self._check()
        return self

    def _check(self) -> None:
        resil = self.meta.get("resilience") or None
        for m in HEALTH_METRICS:
            if m not in self.data:
                continue
            bad = (self.data[m] != 0) & self.computed
            if not bad.any():
                continue
            if m == "overflow":
                hint = ("queue overran with shedding disabled — "
                        "requests were dropped. Raise queue_cap, or "
                        "opt into load shedding with "
                        'ExperimentSpec(on_overflow="shed" / '
                        '"shed_oldest") to count drops as `shed` '
                        "by design")
            else:
                hint = ("event loop hit its iteration cap before "
                        "draining — engine invariant violation")
            raise RuntimeError(
                f"ResultSet.check: {int(bad.sum())} cell(s) with "
                f"nonzero {m!r} ({hint}): {self._bad_cells(bad)}")
        if resil is not None and "n_requests" in self.meta:
            need = ("done", "shed", "failed_exhausted")
            if all(k in self.data for k in need):
                n = int(self.meta["n_requests"])
                tot = sum(self.data[k].astype(np.int64) for k in need)
                bad = (tot != n) & self.computed
                if bad.any():
                    raise RuntimeError(
                        f"ResultSet.check: {int(bad.sum())} cell(s) "
                        f"break conservation (done + shed + "
                        f"failed_exhausted != n_requests={n}): "
                        f"{self._bad_cells(bad)}")

    # -------------------------------------------------------- npz io
    def save_npz(self, path) -> None:
        payload = {f"m_{k}": v for k, v in self.data.items()}
        payload["computed"] = self.computed
        payload["coords_json"] = np.frombuffer(
            json.dumps(self.coords).encode(), np.uint8)
        payload["meta_json"] = np.frombuffer(
            json.dumps(self.meta, default=str).encode(), np.uint8)
        np.savez_compressed(path, **payload)

    @staticmethod
    def load_npz(path) -> "ResultSet":
        with np.load(path) as z:
            data = {k[2:]: z[k] for k in z.files if k.startswith("m_")}
            coords = json.loads(bytes(z["coords_json"]).decode())
            meta = json.loads(bytes(z["meta_json"]).decode())
            computed = np.asarray(z["computed"], bool)
        return ResultSet(data=data, coords=coords, computed=computed,
                         meta=meta)

    # ----------------------------------------------------------- merge
    def merge(self, *others: "ResultSet") -> "ResultSet":
        """Combine host-sharded partial ResultSets over the same grid.

        Shards must share coords and metric sets; each grid cell must
        be computed by at most one shard (the runner's ``host_shard``
        partitioning guarantees it). Returns a new ResultSet whose
        computed mask is the union."""
        merged = ResultSet(
            data={k: v.copy() for k, v in self.data.items()},
            coords={k: list(v) for k, v in self.coords.items()},
            computed=self.computed.copy(), meta=dict(self.meta))
        for o in others:
            if o.coords != merged.coords:
                raise ValueError("ResultSet.merge: coords differ — "
                                 "shards must come from the same spec")
            if set(o.data) != set(merged.data):
                raise ValueError(
                    f"ResultSet.merge: metric sets differ "
                    f"({sorted(set(o.data) ^ set(merged.data))})")
            overlap = merged.computed & o.computed
            if overlap.any():
                raise ValueError(
                    f"ResultSet.merge: {int(overlap.sum())} cell(s) "
                    "computed by more than one shard")
            take = o.computed
            for k in merged.data:
                merged.data[k][take] = o.data[k][take]
            merged.computed |= take
        return merged

    # ------------------------------------------------------------ repr
    def __repr__(self):
        shape = self.grid_shape
        done = int(self.computed.sum())
        axes = ", ".join(f"{d}={n}"
                         for d, n in zip(self.dims, shape))
        return (f"ResultSet({axes}; {done}/{int(np.prod(shape))} "
                f"cells, metrics={self.metrics})")

    def summary(self) -> str:
        """Small human-readable table of mean_response per cell."""
        buf = io.StringIO()
        self.to_csv(buf, metrics=["mean_response"])
        return buf.getvalue()
