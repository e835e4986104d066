"""Plain reference of an edge cluster: K nodes of `node` behind a
router, sharing one event queue.

A copy of the program's Python reference cluster
(`repro.cluster.reference`, with the routers' arithmetic of
`repro.cluster.routers` and the churn expansion of
`repro.cluster.spec.PeriodicChurn`), cut to the two routers the
benchmark's cells run:

* ``hash`` (static): every invocation of function j goes to node
  ``mix32(j, seed) % K`` and reaches it ``delay_k`` later;
* ``slo_aware`` (dynamic): each arrival goes to the up node with the
  least ``delay_k + cold-or-warm start + backlog estimate``, reaches it
  ``delay_k`` later, and is re-routed when its node goes down.

Nodes that go down lose their instances and hand their running, then
queued, requests back to the router; a request that finds every node
down waits for the next one to come up.
"""
from __future__ import annotations

import numpy as np

from bench.reference.node import (BUSY, Estimator, EventKind, EventQueue,
                                  POLICIES, Server, build, fold)

_M32 = 0xFFFFFFFF
_GOLD = 0x9E3779B9
_MIX1, _MIX2 = 0x85EBCA6B, 0xC2B2AE35
_BIG = 1e30


def mix32(x: int, seed: int = 0) -> int:
    """murmur3 finaliser over ``x ^ spread(seed)``."""
    h = (int(x) ^ ((seed * _GOLD) & _M32)) & _M32
    h ^= h >> 16
    h = (h * _MIX1) & _M32
    h ^= h >> 13
    h = (h * _MIX2) & _M32
    h ^= h >> 16
    return h


def hash_assign(fn_id, n_nodes, seed):
    """The static ``hash`` router: (N,) node of every request."""
    nodes = [mix32(j, seed) % n_nodes for j in range(int(max(fn_id)) + 1)]
    return np.asarray(nodes, np.int64)[np.asarray(fn_id)]


def _slo_score(server, pol, est, functions, fn, prior, delay, r):
    """Estimated time until a request of ``fn`` could start on a node,
    plus the node's network delay."""
    gmean = r(est.gsum / max(est.gn, 1)) if est.gn > 0 else prior
    n_j = est.n[fn]
    mean_j = r(est.sum[fn] / max(n_j, 1)) if n_j > 0 else gmean
    has_idle = server.idle_of(fn) is not None
    score = r(r((0.0 if has_idle else functions[fn].cold_start)
                + r(mean_j * len(pol.queues[fn])))
              + r(gmean * (pol.waiting() + server.busy())))
    return r(score + delay)


def simulate(arrays, policy, *, n_nodes, node_capacity, router, net_delay,
             seed=0, churn=None, deadline=None, prior=0.1,
             r=float):
    """Run ``policy`` on every node of the cluster over the columnar
    trace ``arrays``; returns the cluster's statistics.

    ``churn`` is ``None`` or, per node, ``None`` (always up) or a
    tuple of ``(down, up)`` windows."""
    K = n_nodes
    dynamic = router == "slo_aware"
    if router not in ("hash", "slo_aware"):
        raise ValueError(f"reference router {router!r} is not modelled")
    functions, requests = build(arrays, r)
    delays = [r(float(d)) for d in net_delay]
    N = len(requests)
    toggles = [() if c is None else tuple(t for w in c for t in w)
               for c in (churn or [None] * K)]
    has_churn = any(len(t) for t in toggles)
    if has_churn and not dynamic:
        raise ValueError("churn needs a dynamic router")

    events = EventQueue()
    servers = [Server(functions, node_capacity, events, r) for _ in range(K)]
    ests = [Estimator(len(functions), prior, r) for _ in range(K)]
    pols = [POLICIES[policy](servers[k], ests[k]) for k in range(K)]

    def owner(inst):
        for k, srv in enumerate(servers):
            if srv.instances.get(inst.inst_id) is inst:
                return k
        raise RuntimeError(f"instance {inst.inst_id} owned by no node")

    assign = np.full((N,), -1, np.int64)
    static_assign = None if dynamic else hash_assign(arrays["fn_id"], K, seed)
    deferred = dynamic and any(delays)
    for q in requests:
        if static_assign is not None:
            k = int(static_assign[q.req_id])
            events.push(r(q.arrival + delays[k]), EventKind.ARRIVAL, q)
        else:
            events.push(q.arrival, EventKind.ARRIVAL, q)
    up = [True] * K
    for k in range(K):
        for t in toggles[k]:
            events.push(t, EventKind.CHURN, k)
    parked = []

    def route(q, t):
        best_k, best = 0, None
        for k in range(K):
            score = _slo_score(servers[k], pols[k], ests[k], functions,
                               q.fn_id, prior, delays[k], r)
            if has_churn and not up[k]:
                score = _BIG
            if best is None or score < best:
                best_k, best = k, score
        k = best_k
        if has_churn and not up[k]:
            k = up.index(True)
        assign[q.req_id] = k
        if deferred:
            events.push(r(t + delays[k]), EventKind.NODE_ARRIVAL, q)
        else:
            pols[k].on_arrival(q, t)

    while True:
        ev = events.pop()
        if ev is None:
            break
        t, kind, _, payload = ev
        if kind == EventKind.ARRIVAL:
            q = payload
            if static_assign is not None:
                k = int(static_assign[q.req_id])
                assign[q.req_id] = k
                pols[k].on_arrival(q, t)
            elif has_churn and not any(up):
                parked.append(q)
            else:
                route(q, t)
        elif kind == EventKind.NODE_ARRIVAL:
            q = payload
            k = int(assign[q.req_id])
            if has_churn and not up[k]:
                if any(up):
                    events.push(t, EventKind.REROUTE, q)
                else:
                    parked.append(q)
            else:
                pols[k].on_arrival(q, t)
        elif kind == EventKind.REROUTE:
            if not any(up):
                parked.append(payload)
            else:
                route(payload, t)
        elif kind == EventKind.CHURN:
            k = payload
            if up[k]:
                up[k] = False
                srv, pol = servers[k], pols[k]
                running = sorted((i for i in srv.instances.values()
                                  if i.state == BUSY
                                  and i.current is not None),
                                 key=lambda i: i.current.req_id)
                drained = [i.current for i in running]
                for fn in sorted(pol.queues):
                    drained.extend(pol.queues[fn])
                for inst in srv.instances.values():
                    inst.dead = True
                srv.instances.clear()
                srv.by_fn = {f.fn_id: set() for f in functions}
                pols[k] = POLICIES[policy](srv, ests[k])
                for q in drained:
                    events.push(t, EventKind.REROUTE, q)
            else:
                up[k] = True
                for q in parked:
                    events.push(t, EventKind.REROUTE, q)
                parked.clear()
        elif kind == EventKind.EXEC_DONE:
            inst = payload
            if inst.dead:
                continue
            k = owner(inst)
            q = inst.current
            ests[k].observe(q.fn_id, q.exec_time)
            pols[k].on_exec_done(inst, q, t)
        elif kind == EventKind.COLD_DONE:
            inst = payload
            if inst.dead:
                continue
            pols[owner(inst)].on_cold_done(inst, t)
        elif kind == EventKind.TIMER:
            if has_churn:
                raise RuntimeError("timer-armed policies cannot run under "
                                   "churn")
            q = payload
            pols[int(assign[q.req_id])].on_timer(q, t)

    if has_churn:
        base = {q.req_id: q.arrival for q in requests}
    else:
        shift = (delays if static_assign is not None or deferred
                 else [0.0] * K)
        base = {q.req_id: r(q.arrival + shift[int(assign[q.req_id])])
                for q in requests}
    out = fold(requests, lambda q: base[q.req_id], r)
    out.update(cold_starts=sum(s.cold_starts for s in servers),
               evictions=sum(s.evictions for s in servers),
               node_done=np.bincount(
                   assign[[q.req_id for q in requests if q.completion >= 0]],
                   minlength=K))
    cold_time = 0.0
    for s in servers:
        cold_time = r(cold_time + s.cold_time)
    out["cold_time"] = cold_time
    if deadline is not None:
        dl = r(float(deadline))
        out["deadline_miss"] = sum(
            1 for q in requests if q.completion >= 0
            and r(q.completion - base[q.req_id]) > dl)
    return out


def lane_stats(cell, streams, lane, r=float):
    """The statistics of ``lane = (policy, capacity, k)`` of a
    `bench.cell.Cell`'s unit over ``streams``: the entry point of
    `bench.check.reference`."""
    policy, _, k = lane
    return simulate(streams[k], policy, prior=cell.config["prior_s"], r=r,
                    deadline=cell.traffic.get("deadline_s"),
                    **cell.cluster_kw())
