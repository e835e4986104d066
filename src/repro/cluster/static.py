"""Static-routing fast path: pre-partition, simulate, merge exactly.

A static router fixes each request's node from the trace alone, so a
K-node cluster is exactly K independent single-node simulations over
the per-node sub-streams of the arrival stream. This module implements
that as a vectorised pre-pass + the *unmodified* single-node engine:

1. ``build_node_streams`` asks the router for the (N,) node assignment
   (checking every request is routed exactly once), splits the
   columnar trace into K arrival-ordered sub-streams, adds each node's
   network delay to its arrivals (a constant shift keeps the
   sub-stream sorted), and right-pads every sub-stream to the common
   length N — the padded rows share one (T·K, N) operand, and the
   engine's ``n_live`` lane cap (PR 5) keeps the padding inert without
   a recompile per sub-stream length.
2. ``run_static_entry`` lowers (policy × trace × capacity × beta ×
   node) onto `jax_engine._sweep_metrics` lanes — node slot counts
   become per-lane capacity masks, so heterogeneous nodes ride the
   same jit specialisation — and merges the per-node streamed metrics
   back into cluster-level cells.

The merge is *exact*: counters and histograms are integer sums, the
response/slowdown/cold-time sums are float sums taken in **canonical
(value-sorted) order** over the node axis, so the merged metrics are
bitwise invariant to node numbering (gated in tests/test_cluster.py),
and means/quantiles are recomputed from the merged sums/histograms the
same way the engine computes them — a K=1 cluster with zero delay is
bitwise identical to the plain single-node run.

Response-time semantics under ``net_delay``: a request routed to node
k *arrives at the node* at ``t + delay_k`` and its response is
measured from that node-local arrival (the engine's definition). The
delay shifts the node's dynamics; it is not added to the reported
latency (docs/cluster.md discusses both conventions).
"""
from __future__ import annotations

import functools
from typing import Dict, List

import numpy as np

from repro.cluster.spec import ClusterSpec

PAD_ARRIVAL = 1e30      # matches jax_engine.BIG: padding never arrives


@functools.lru_cache(maxsize=None)
def _div_by_n_jit():
    import jax

    @functools.partial(jax.jit, static_argnames=("n",))
    def div(x, n):
        return x / n
    return div


def _mean(x: np.ndarray, n: int) -> np.ndarray:
    """``x / n`` through the same jitted constant-denominator division
    `_sweep_metrics` lowers to (XLA folds division by a constant into
    a reciprocal multiply — a plain numpy divide would differ in the
    last ulp and break the K=1 bitwise gate)."""
    import jax.numpy as jnp
    return np.asarray(_div_by_n_jit()(jnp.asarray(x), max(int(n), 1)))


def build_node_streams(arrays: Dict[str, np.ndarray],
                       cspec: ClusterSpec):
    """Partition one columnar trace into per-node padded sub-streams.

    Returns ``(assign, streams, n_live, index)``: the (N,) node
    assignment, a dict of (K, N) padded ``fn_id``/``arrival``/
    ``exec_time`` rows (node k's requests lead row k, arrival order
    preserved, delays applied), the (K,) live lengths and the K
    original-request-id index arrays (for exact-mode reassembly).
    """
    router = cspec.get_router()
    if router.dynamic:
        raise ValueError(
            f"build_node_streams: router {cspec.router!r} is dynamic; "
            "the static path needs a StaticRouter")
    if cspec.has_churn():
        raise ValueError(
            f"cluster router {cspec.router!r} is static: a fixed "
            "assignment cannot re-route around a down node. Churn "
            "needs a dynamic router (jsq2, cold_aware, slo_aware)")
    if cspec.delay_ops() is not None:
        raise ValueError(
            f"cluster router {cspec.router!r} is static: the "
            "pre-partition fast path only supports constant "
            "net_delay (a time-varying DelaySchedule would unsort "
            "the per-node sub-streams); use a dynamic router")
    fn_id = np.asarray(arrays["fn_id"])
    arrival = np.asarray(arrays["arrival"])
    N, K = len(fn_id), cspec.n_nodes
    assign = np.asarray(router.assign(fn_id, arrival, cspec))
    if assign.shape != (N,):
        raise ValueError(
            f"router {cspec.router!r} returned shape {assign.shape} "
            f"for {N} requests — every request must be routed exactly "
            "once")
    if N and (assign.min() < 0 or assign.max() >= K):
        raise ValueError(
            f"router {cspec.router!r} routed outside [0, {K}): "
            f"range [{assign.min()}, {assign.max()}]")
    delays = cspec.delays()
    node_fn = np.zeros((K, N), np.int32)
    node_arr = np.full((K, N), PAD_ARRIVAL, np.float64)
    node_ex = np.zeros((K, N), np.float64)
    n_live = np.zeros((K,), np.int32)
    index: List[np.ndarray] = []
    for k in range(K):
        idx = np.flatnonzero(assign == k)
        n = len(idx)
        node_fn[k, :n] = fn_id[idx]
        node_arr[k, :n] = arrival[idx] + delays[k]
        node_ex[k, :n] = np.asarray(arrays["exec_time"])[idx]
        n_live[k] = n
        index.append(idx)
    streams = dict(fn_id=node_fn, arrival=node_arr, exec_time=node_ex)
    return assign, streams, n_live, index


# ------------------------------------------------------------ exact merge
# float metrics summed over nodes in canonical (value-sorted) order so
# the merged value is bitwise invariant to node numbering; integer
# metrics sum in any order; max is order-free
_SUM_F = ("resp_sum", "slow_sum", "cold_time", "evict_time")
_SUM_I = ("cold_starts", "evictions", "overflow", "stalled", "done",
          "n_events", "resp_hist", "deadline_miss", "failed",
          "timed_out", "retried", "shed", "failed_exhausted")
_SUM_F_TL = ("tl_resp_sum", "tl_exec_sum")
_SUM_I_TL = ("tl_count",)


def _ordered_sum(a: np.ndarray, axis: int) -> np.ndarray:
    """Sum over ``axis`` with the addends first sorted by value —
    deterministic and permutation-invariant float reduction."""
    return np.sort(a, axis=axis).sum(axis=axis)


def merge_node_metrics(per_node: Dict[str, np.ndarray], node_axis: int,
                       n_total: int, resil: bool = False
                       ) -> Dict[str, np.ndarray]:
    """Fold per-node metric arrays (node axis ``node_axis``) into
    cluster-level metrics over ``n_total`` requests.

    Means and the streamed p99 are recomputed from the merged sums /
    histogram exactly the way `jax_engine._sweep_metrics` computes
    them, so a single-node "cluster" merges to the engine's own
    numbers bit for bit. Under ``resil`` the denominators are the
    merged success counts (``done``) instead of ``n_total`` — an array
    denominator, so plain IEEE division matches the engine's (the
    jitted reciprocal-multiply fold in `_mean` only applies to
    *constant* denominators)."""
    from repro.core.jax_engine import hist_quantile
    out: Dict[str, np.ndarray] = {}
    for m in _SUM_F:
        if m in per_node:
            out[m] = _ordered_sum(per_node[m], node_axis)
    for m in _SUM_I:
        if m in per_node:
            out[m] = per_node[m].sum(axis=node_axis)
    for m in _SUM_F_TL:       # (..., K, bins): sort nodes per bin
        if m in per_node:
            out[m] = _ordered_sum(per_node[m], node_axis - 1
                                  if node_axis < 0 else node_axis)
    for m in _SUM_I_TL:
        if m in per_node:
            out[m] = per_node[m].sum(axis=node_axis - 1
                                     if node_axis < 0 else node_axis)
    out["max_response"] = per_node["max_response"].max(axis=node_axis)
    out["node_done"] = np.moveaxis(per_node["done"], node_axis, -1)
    if resil:
        den = np.maximum(out["done"], 1).astype(np.float64)
        out["mean_response"] = out["resp_sum"] / den
        out["mean_slowdown"] = out["slow_sum"] / den
        out["p99_response"] = np.asarray(hist_quantile(
            out["resp_hist"], 0.99, out["done"][..., None],
            out["max_response"]))
    else:
        out["mean_response"] = _mean(out["resp_sum"], n_total)
        out["mean_slowdown"] = _mean(out["slow_sum"], n_total)
        out["p99_response"] = np.asarray(hist_quantile(
            out["resp_hist"], 0.99, n_total, out["max_response"]))
    return out


def run_static_entry(spec, entry: ClusterSpec,
                     stacked: Dict[str, np.ndarray], F: int, N: int,
                     kernels: dict, beta_cols: Dict[str, np.ndarray],
                     deadlines=None, rs=None,
                     trace_cells=None) -> Dict[str, np.ndarray]:
    """Execute one static `ClusterSpec` over the spec's grid.

    Returns (P, T, KC, B)-shaped metric arrays (plus trailing dims:
    ``node_done`` (.., K), ``resp_hist`` (.., bins), optional
    ``response`` (.., N)) for this cluster entry.

    ``trace_cells`` (a dict, only under ``spec.trace_events``) is
    filled with one merged event stream per (pi, t, kc, b) cell: the
    tier is K independent single-node simulations, so each node's
    stream is collected separately, its node id patched in host-side
    (the single-node rail records node −1), its sub-stream-local
    request ids mapped back to global ids through the partition
    index, and the K streams merged time-ordered
    (`repro.telemetry.rail.merge_events`).
    """
    import jax.numpy as jnp

    from repro.core.jax_engine import _sweep_metrics, resolve_lane_chunk

    T = stacked["fn_id"].shape[0]
    Kn = entry.n_nodes
    KC = len(spec.capacities)
    B = 1 if spec.betas is None else len(spec.betas)
    C = max(max(entry.node_caps(c)) for c in spec.capacities)

    resil = None
    if rs is not None:
        eff, rs_nfail, rs_tmo, _, resil = rs

    # per-trace partition (vectorised pre-pass). Under resilience the
    # timeout-clipped exec times are partitioned instead, and each
    # node's sub-stream carries its requests' pre-planned outcome rows
    # sliced by the same assignment — with the *original* request ids
    # as the jitter keys, so a request's retry backoffs are identical
    # no matter which node (or tier) runs it.
    streams_t: List[Dict[str, np.ndarray]] = []
    n_live_rows = np.zeros((T, Kn), np.int32)
    index: List[List[np.ndarray]] = []
    rs_rows: List[Dict[str, np.ndarray]] = []
    for t in range(T):
        a = {k: stacked[k][t] for k in ("fn_id", "arrival",
                                        "exec_time")}
        if rs is not None:
            a["exec_time"] = eff[t]
        _, streams, n_live, idx = build_node_streams(a, entry)
        streams_t.append(streams)
        n_live_rows[t] = n_live
        index.append(idx)
        if rs is not None:
            nf = np.zeros((Kn, N), np.int32)
            tm = np.zeros((Kn, N), bool)
            ky = np.zeros((Kn, N), np.int32)
            for k in range(Kn):
                i = idx[k]
                nf[k, : len(i)] = rs_nfail[t][i]
                tm[k, : len(i)] = rs_tmo[t][i]
                ky[k, : len(i)] = i
            rs_rows.append(dict(nfail=nf, tmo=tm, key=ky))

    # One engine call per (trace, node) sub-stream row, lanes =
    # capacity x beta. Feeding all T*K rows as one shared (T*K, N)
    # operand batches more lanes per call but falls off XLA:CPU's fast
    # gather path: a multi-row shared operand beyond ~2^16 elements
    # degrades the per-event gathers ~25x (single-row operands of any
    # length stay fast — the N-curve runs 1e6-request rows flat).
    # Per-row calls also collapse every (router, K) topology onto ONE
    # (1, N)-shaped jit specialisation per policy.
    node_masks = {c: np.stack([np.arange(C) < nc
                               for nc in entry.node_caps(c)])
                  for c in spec.capacities}
    L = KC * B
    dl_op = None if deadlines is None else jnp.asarray(deadlines)
    keep_resp = bool(spec.keep_per_request) or not spec.stream
    chunk = resolve_lane_chunk(spec.lane_chunk)
    traced = trace_cells is not None
    if traced:
        from repro.telemetry import rail
    per_policy: Dict[str, Dict[str, np.ndarray]] = {}
    for pi, policy in enumerate(spec.policies):
        outs: Dict[str, list] = {}
        for t in range(T):
            cold = jnp.asarray(stacked["cold_start"][t][None])
            evict = jnp.asarray(stacked["evict"][t][None])
            lane_nodes: Dict[int, list] = {}
            for k in range(Kn):
                shared = tuple(
                    jnp.asarray(streams_t[t][key][k][None])
                    for key in ("fn_id", "arrival", "exec_time")
                ) + (cold, evict)
                masks = np.stack([node_masks[c][k]
                                  for c in spec.capacities
                                  for _ in range(B)])
                beta_l = beta_cols[policy][:L]
                nl = np.full((L,), n_live_rows[t, k], np.int32)
                rs_kw = {}
                if rs is not None:
                    rr = rs_rows[t]
                    rs_kw = dict(
                        rs_nfail=jnp.asarray(rr["nfail"][k][None]),
                        rs_tmo=jnp.asarray(rr["tmo"][k][None]),
                        rs_key=jnp.asarray(rr["key"][k][None]))
                row_outs: Dict[str, list] = {}
                for lo in range(0, L, chunk):
                    hi = min(lo + chunk, L)

                    def call():
                        return _sweep_metrics(
                            *shared, jnp.zeros((hi - lo,), jnp.int32),
                            jnp.asarray(masks[lo:hi]),
                            jnp.asarray(beta_l[lo:hi]),
                            jnp.float64(spec.prior),
                            jnp.float64(spec.threshold),
                            jnp.asarray(nl[lo:hi]), dl_op, **rs_kw,
                            resil=resil,
                            kernel=kernels[policy], n_fns=F,
                            capacity=C, queue_cap=spec.queue_cap,
                            stream=spec.stream, window=spec.window,
                            tl_bins=spec.tl_bins,
                            tl_bucket=spec.tl_bucket,
                            keep_responses=(keep_resp
                                            and not spec.stream),
                            trace=traced)
                    if traced:
                        with rail.collect() as sink:
                            out = {m: np.asarray(v) for m, v
                                   in call().items()}
                        idxk = index[t][k]
                        for j in range(hi - lo):
                            ev = sink.lane_events(j)
                            ev["node"] = np.full_like(ev["node"], k)
                            r = ev["rid"]
                            if len(idxk):
                                gl = idxk[np.clip(r, 0,
                                                  len(idxk) - 1)]
                                ev["rid"] = np.where(
                                    r >= 0, gl, -1).astype(np.int32)
                            lane_nodes.setdefault(lo + j,
                                                  []).append(ev)
                    else:
                        out = call()
                    out.pop("loop_steps")
                    for m, v in out.items():
                        row_outs.setdefault(m, []).append(
                            np.asarray(v))
                for m, v in row_outs.items():
                    outs.setdefault(m, []).append(np.concatenate(v))
            if traced:
                for lane, evs in lane_nodes.items():
                    kc, b = divmod(lane, B)
                    trace_cells[(pi, t, kc, b)] = rail.merge_events(
                        evs)
        # outs[m]: T*Kn blocks of (KC*B, ...) in (t, node) order
        per_policy[policy] = {
            m: np.stack(v).reshape((T, Kn, KC, B) + v[0].shape[1:])
               .transpose((0, 2, 3, 1)
                          + tuple(range(4, 4 + v[0].ndim - 1)))
            for m, v in outs.items()}

    # ------------------------------------------------- node-axis merge
    data: Dict[str, np.ndarray] = {}
    for pi, policy in enumerate(spec.policies):
        pn = per_policy[policy]
        merged = merge_node_metrics(pn, node_axis=3, n_total=N,
                                    resil=resil is not None)
        if "response" in pn:
            resp = np.zeros((T, KC, B, N), np.float64)
            for t in range(T):
                for k in range(Kn):
                    nk = int(n_live_rows[t, k])
                    resp[t, :, :, index[t][k]] = np.moveaxis(
                        pn["response"][t, :, :, k, :nk], -1, 0)
            if resil is not None:
                # shed / retry-exhausted rids carry NaN responses
                merged["p99_response"] = np.nanpercentile(
                    resp, 99.0, axis=-1)
            else:
                merged["p99_response"] = np.percentile(resp, 99.0,
                                                       axis=-1)
            if spec.keep_per_request:
                merged["response"] = resp
        for m, v in merged.items():
            if m not in data:
                data[m] = np.zeros((len(spec.policies),) + v.shape,
                                   v.dtype)
            data[m][pi] = v
    return data


# ---------------------------------------------------------- audit hooks
def audit_jits():
    """Jitted static-tier helpers by name, for `repro.analysis`'s
    recompilation auditor. The tier's design claim -- every (router,
    K, heterogeneity) topology collapses onto ONE (1, N)-shaped
    `_sweep_metrics` specialisation per policy, because node streams
    are PAD-padded back to full length and masked via ``n_live`` --
    is what the auditor checks by counting engine cache entries after
    a representative grid."""
    return {"div_by_n": _div_by_n_jit()}
