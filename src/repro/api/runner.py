"""Lower an `ExperimentSpec` onto the vectorised engine, sharded.

The grid is flattened exactly the way the legacy ``sweep`` flattened
it — per policy, lanes ordered trace-major, then capacity, then beta,
split into `resolve_lane_chunk`-sized chunks — so the deprecation shim
is bitwise-identical by construction and the jit cache stays warm
across both surfaces. On top of that lowering this runner adds the
scale-out halves the ROADMAP called for:

* **device sharding** — lane chunks round-robin over
  ``jax.local_devices()`` (capped by ``spec.devices``); each device
  gets its own copy of the shared trace operands once, and chunk
  inputs are committed to their device so XLA runs the per-device
  calls concurrently. Lanes are embarrassingly parallel and the engine
  is deterministic per lane, so a multi-device run is bitwise
  identical to the single-device run — gated by the 2-device CPU
  parity checks in ``benchmarks/run.py --smoke`` and
  ``tests/test_api.py``.
* **host sharding** — ``spec.host_shard=(i, n)`` keeps only chunks
  ``i, i+n, i+2n, ...`` of the global chunk list; the resulting
  partial `ResultSet` marks the rest uncomputed and
  `ResultSet.merge` reassembles the full grid from all hosts' shards.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Dict

import numpy as np

from repro.api.registry import get_kernel
from repro.api.results import ResultSet
from repro.api.spec import ExperimentSpec

_BETA_DEFAULT = "default"

# Multi-trace grids whose stacked (T, N) trace operands exceed this
# many elements run one trace row per engine call instead: inside the
# lanes' vmap a (T, N) operand is a *batched* gather operand, and
# XLA:CPU drops batched multi-element gathers onto its generic
# (~25x slower) path once the operand outgrows cache scale. Per-row
# calls keep every shared operand (1, N) — the fast path — and
# per-lane outputs depend only on the lane's own trace row, so the
# grouped grid is bitwise the stacked one (gated in tests/test_api.py).
ROW_SPLIT_ELEMS = 1 << 16


def _unique_labels(labels):
    """Disambiguate repeated source labels positionally (``#k`` suffix)
    so ResultSet coordinate selection stays unambiguous — e.g. four
    same-shape inline traces all labeled ``trace[n5000]`` become
    ``trace[n5000]``, ``trace[n5000]#1``, ..."""
    seen: Dict[str, int] = {}
    out = []
    for lab in labels:
        k = seen.get(lab, 0)
        seen[lab] = k + 1
        out.append(lab if k == 0 else f"{lab}#{k}")
    return out


def _lower_grid(spec: ExperimentSpec):
    """Materialise sources and build the per-policy lane layout."""
    sources = spec.expanded_traces()
    arrs = [src.arrays() for src in sources]
    F = len(arrs[0]["cold_start"])
    N = len(arrs[0]["fn_id"])
    for src, a in zip(sources, arrs):
        if len(a["cold_start"]) != F or len(a["fn_id"]) != N:
            raise ValueError(
                f"ExperimentSpec traces must share shape "
                f"(n_functions, n_requests): {src.label} has "
                f"({len(a['cold_start'])}, {len(a['fn_id'])}), "
                f"{sources[0].label} has ({F}, {N})")
    stacked = {k: np.stack([np.asarray(a[k]) for a in arrs])
               for k in ("fn_id", "arrival", "exec_time", "cold_start",
                         "evict")}
    return sources, stacked, F, N


def _chunk_plan(spec: ExperimentSpec, T: int, chunk: int,
                row_split: bool = False):
    """The global chunk list [(policy_index, lane_lo, lane_hi)] in the
    legacy sweep order (policy-major; lanes trace-major, then capacity,
    then beta). Under ``row_split`` chunks additionally never cross a
    trace boundary, so each engine call sees lanes of a single trace
    row."""
    K = len(spec.capacities)
    B = 1 if spec.betas is None else len(spec.betas)
    bounds = ([(t * K * B, (t + 1) * K * B) for t in range(T)]
              if row_split else [(0, T * K * B)])
    plan = []
    for pi in range(len(spec.policies)):
        for blo, bhi in bounds:
            for lo in range(blo, bhi, chunk):
                plan.append((pi, lo, min(lo + chunk, bhi)))
    return plan, K, B


def run_experiment(spec: ExperimentSpec) -> ResultSet:
    """Execute ``spec`` and return its labeled `ResultSet`.

    A spec with a ``cluster`` axis is delegated to
    `repro.cluster.runner.run_cluster_experiment`, which stacks one
    (policy, trace, capacity, beta) grid per cluster topology into the
    ResultSet's trailing ``cluster`` dim.

    The single-node path writes host spans into an active
    `jax.profiler` trace: ``repro.run_experiment`` around the call,
    ``repro.lower``, one ``repro.dispatch`` and one ``repro.fetch``
    per chunk, ``repro.assemble``, and ``repro.check`` in
    `ResultSet.check` (docs/observability.md, "Performance spans").
    Their stats are computed only while a trace is active."""
    from jax.profiler import TraceAnnotation

    if spec.cluster is not None:
        from repro.cluster.runner import run_cluster_experiment
        return run_cluster_experiment(spec)
    with TraceAnnotation("repro.run_experiment") as span:
        out = _run_single_node(spec)
        if TraceAnnotation.is_enabled():
            span.set_metadata(chunks=len(out.meta["loop_steps"]),
                              lanes=int(out.computed.sum()))
    return out


def _run_single_node(spec: ExperimentSpec) -> ResultSet:
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    from repro.core.jax_engine import _sweep_metrics, resolve_lane_chunk

    spec.validate()
    with TraceAnnotation("repro.lower") as span:
        sources, stacked, F, N = _lower_grid(spec)
        if TraceAnnotation.is_enabled():
            span.set_metadata(n_requests=N, n_functions=F)
        rs = spec.resilience_ops(stacked, F)
        resil = None
        if rs is not None:
            # faults on: the effective (timeout-clipped) exec times
            # replace the exec operand; the pre-planned outcome
            # operands ride the same per-device / per-row slicing as
            # the trace operands
            eff, rs_nfail, rs_tmo, rs_key, resil = rs
            stacked = dict(stacked, exec_time=eff)
        T = len(sources)
        C = max(spec.capacities)
        masks = np.stack([np.arange(C) < c for c in spec.capacities])
        chunk = resolve_lane_chunk(spec.lane_chunk)
        row_split = T > 1 and T * N > ROW_SPLIT_ELEMS
        plan, K, B = _chunk_plan(spec, T, chunk, row_split)

        host_i, host_n = spec.host_shard
        mine = [ci for ci in range(len(plan)) if ci % host_n == host_i]
        if not mine:
            raise ValueError(
                f"ExperimentSpec: host_shard={spec.host_shard} gets no "
                f"chunks (the grid lowers to {len(plan)} chunk(s) of "
                f"{chunk} lanes — lower host count or lane_chunk)")

        devs = jax.local_devices()
        if spec.devices is not None:
            if spec.devices > len(devs):
                raise ValueError(
                    f"ExperimentSpec: devices={spec.devices} but only "
                    f"{len(devs)} local device(s) present")
            devs = devs[: spec.devices]
        if spec.trace_events:
            # traced chunks run serially on the default device so the
            # ordered-callback flushes of different chunks cannot
            # interleave in one collect scope
            devs = devs[:1]
        multi_dev = len(devs) > 1

        # shared (T, ...) trace operands — one committed copy per
        # device (a single uncommitted copy when not sharding, matching
        # the legacy single-device path exactly)
        shared0 = {k: jnp.asarray(v) for k, v in stacked.items()}
        if rs is not None:
            shared0["rs_nfail"] = jnp.asarray(rs_nfail, jnp.int32)
            shared0["rs_tmo"] = jnp.asarray(rs_tmo)
            shared0["rs_key"] = jnp.asarray(rs_key, jnp.int32)
        if multi_dev:
            shared_per_dev = [
                {k: jax.device_put(v, d) for k, v in shared0.items()}
                for d in devs]
        else:
            shared_per_dev = [shared0]

        kernels = {p: get_kernel(p) for p in spec.policies}
        dl = spec.deadline_ops(F)
        dl_op = None if dl is None else jnp.asarray(dl)

        # per-policy lane coordinate columns (identical for every
        # policy: betas=None resolves per kernel at chunk build time)
        tix_col = np.repeat(np.arange(T, dtype=np.int32), K * B)
        mask_col = np.tile(np.repeat(masks, B, axis=0), (T, 1))

        def beta_col(policy: str) -> np.ndarray:
            bs = np.asarray(
                [kernels[policy].default_beta] if spec.betas is None
                else list(spec.betas), np.float64)
            return np.tile(bs, T * K)

        beta_cols = {p: beta_col(p) for p in spec.policies}

    def run_chunk(ci: int):
        pi, lo, hi = plan[ci]
        policy = spec.policies[pi]
        di = mine.index(ci) % len(devs)
        sh = shared_per_dev[di]
        tix_l = jnp.asarray(tix_col[lo:hi])
        if row_split:
            # single-trace chunk: slice the shared operands to this
            # chunk's trace row and renumber the lanes' trace index
            t0 = int(tix_col[lo])
            sh = {k: v[t0:t0 + 1] for k, v in sh.items()}
            tix_l = jnp.zeros((hi - lo,), jnp.int32)
        mask_l = jnp.asarray(mask_col[lo:hi])
        beta_l = jnp.asarray(beta_cols[policy][lo:hi])
        if multi_dev:
            dev = devs[di]
            tix_l = jax.device_put(tix_l, dev)
            mask_l = jax.device_put(mask_l, dev)
            beta_l = jax.device_put(beta_l, dev)
        with TraceAnnotation("repro.dispatch") as span:
            if TraceAnnotation.is_enabled():
                span.set_metadata(chunk=ci, policy=policy, lanes=hi - lo)
            out = _sweep_metrics(
                sh["fn_id"], sh["arrival"], sh["exec_time"],
                sh["cold_start"], sh["evict"], tix_l, mask_l, beta_l,
                jnp.float64(spec.prior), jnp.float64(spec.threshold),
                deadlines=dl_op,
                rs_nfail=sh.get("rs_nfail"), rs_tmo=sh.get("rs_tmo"),
                rs_key=sh.get("rs_key"), resil=resil,
                kernel=kernels[policy], n_fns=F, capacity=C,
                queue_cap=spec.queue_cap, stream=spec.stream,
                window=spec.window, tl_bins=spec.tl_bins,
                tl_bucket=spec.tl_bucket,
                keep_responses=spec.keep_per_request,
                trace=spec.trace_events)
        placed = sorted({f"{d.platform}:{d.id}" for v in out.values()
                         for d in v.devices()})
        with TraceAnnotation("repro.fetch") as span:
            out = jax.device_get(out)
            steps = int(out.pop("loop_steps"))
            if TraceAnnotation.is_enabled():
                span.set_metadata(
                    chunk=ci, lanes=hi - lo, loop_steps=steps,
                    lane_events=int(np.sum(out["n_events"],
                                           dtype=np.int64)))
        return ci, out, placed, steps

    if spec.trace_events:
        # one collect scope per chunk: device_get inside run_chunk
        # blocks, so every ordered flush lands before the scope closes
        from repro.telemetry import rail
        outs, placed, steps = {}, {}, {}
        lane_events: Dict[tuple, dict] = {}
        for ci in mine:
            with rail.collect() as sink:
                _, outs[ci], placed[ci], steps[ci] = run_chunk(ci)
            pi, lo, hi = plan[ci]
            for j in range(hi - lo):
                lane_events[(pi, lo + j)] = sink.lane_events(j)
    else:
        # device calls overlap on the host thread pool (XLA releases
        # the GIL while a computation runs); at least 2 workers even on
        # one device so transfer/compile of chunk k+1 hides behind
        # chunk k
        workers = max(2, len(devs))
        with ThreadPoolExecutor(max_workers=workers) as tp:
            done = list(tp.map(run_chunk, mine))
        outs = {ci: out for ci, out, _, _ in done}
        placed = {ci: ids for ci, _, ids, _ in done}
        steps = {ci: n for ci, _, _, n in done}

    # ------------------------------------------------------- assembly
    with TraceAnnotation("repro.assemble"):
        P = len(spec.policies)
        lanes_per_policy = T * K * B
        flat: Dict[str, np.ndarray] = {}
        computed = np.zeros((P, lanes_per_policy), bool)
        for ci in mine:
            pi, lo, hi = plan[ci]
            out = outs[ci]
            for k, v in out.items():
                v = np.asarray(v)
                if k not in flat:
                    flat[k] = np.zeros(
                        (P, lanes_per_policy) + v.shape[1:], v.dtype)
                flat[k][pi, lo:hi] = v
            computed[pi, lo:hi] = True

        grid = lambda a: a.reshape(  # noqa: E731
            (P, T, K, B) + a.shape[2:])
        data = {k: grid(v) for k, v in flat.items()}
        if dl is not None:
            from repro.core.jax_engine import slo_attainment
            data["slo_attainment"] = slo_attainment(
                data["deadline_miss"], data["done"])
        if resil is not None:
            from repro.core.jax_engine import goodput
            data["goodput"] = goodput(data["done"], N)
        beta_coord = (list(spec.betas) if spec.betas is not None
                      else [_BETA_DEFAULT])
        coords = dict(policy=list(spec.policies),
                      trace=_unique_labels([s.label for s in sources]),
                      capacity=list(spec.capacities),
                      beta=beta_coord)
        meta = dict(spec.meta,
                    n_requests=N, n_functions=F,
                    queue_cap=spec.queue_cap,
                    stream=spec.stream, window=spec.window,
                    tl_bins=spec.tl_bins, tl_bucket=spec.tl_bucket,
                    prior=spec.prior, threshold=spec.threshold,
                    lane_chunk=chunk, host_shard=list(spec.host_shard),
                    row_split=row_split,
                    deadlines=(None if dl is None else
                               (spec.deadlines
                                if isinstance(spec.deadlines, float)
                                else list(spec.deadlines))),
                    n_devices=len(devs), backend=jax.default_backend(),
                    chunk_devices=[placed[ci] for ci in mine],
                    loop_steps=[steps[ci] for ci in mine],
                    resilience=spec.resilience_meta(),
                    seeds=(list(spec.seeds) if spec.seeds is not None
                           else None),
                    trace_events=spec.trace_events,
                    default_betas={p: kernels[p].default_beta
                                   for p in spec.policies})
        trace_run = None
        if spec.trace_events:
            from repro.telemetry.spans import TraceRun
            trace_run = TraceRun(coords)
            for (pi, lane), ev in lane_events.items():
                t_i, rest = divmod(lane, K * B)
                kc, b = divmod(rest, B)
                trace_run.add_cell((pi, t_i, kc, b), ev)
        return ResultSet(data=data, coords=coords,
                         computed=grid(computed), meta=meta,
                         trace=trace_run)


# short alias — `from repro.api import run`
run = run_experiment


def legacy_sweep_dict(rs: ResultSet, n_traces: int) -> dict:
    """Convert a ResultSet into the legacy ``sweep()`` return layout
    (metric arrays keyed by name + the ad-hoc ``"axes"`` dict) for the
    deprecation shim."""
    out = {k: v for k, v in rs.data.items() if k != "response"}
    betas = rs.coords["beta"]
    out["axes"] = dict(policy=list(rs.coords["policy"]),
                       trace=n_traces,
                       capacity=list(rs.coords["capacity"]),
                       beta=(None if betas == [_BETA_DEFAULT]
                             else list(betas)))
    return out


# ---------------------------------------------------------- audit hooks
def jit_cache_sizes() -> Dict[str, int]:
    """Jit cache sizes of every engine entry point (single-node +
    cluster tiers), for `repro.analysis`'s recompilation auditor: run
    a grid, then compare these counts against the padding-sharing
    design's expected specialisation count."""
    from repro.cluster.runner import jit_cache_sizes as _cluster_sizes
    from repro.core.jax_engine import audit_jits
    sizes = {name: fn._cache_size()
             for name, fn in audit_jits().items()}
    sizes.update(_cluster_sizes())
    return sizes


def clear_jit_caches() -> None:
    """Reset every engine entry point's jit cache (single-node +
    cluster tiers) so `jit_cache_sizes` counts only the grid under
    audit."""
    from repro.cluster import engine as _cengine
    from repro.cluster import static as _cstatic
    from repro.core.jax_engine import audit_jits
    for fn in {**audit_jits(), **_cengine.audit_jits(),
               **_cstatic.audit_jits()}.values():
        fn.clear_cache()
