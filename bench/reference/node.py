"""Plain reference of one edge node: a heap-driven discrete-event
simulation of C function slots under one of the six scheduling
policies (paper §V, Algorithms 1-3, and the §VI-A baselines).

A copy of the program's Python event engine (`repro.core.events`,
`server`, `esff`, `esff_h`, `baselines`, `simulator`), cut to what the
benchmark's cells run and independent of the program's code. Every
arithmetic result on a time, an estimate or a metric passes through
``r``: the built-in `float` computes in float64, and `float32` rounds
each result to float32, which is the benchmark's precision control.
"""
from __future__ import annotations

import heapq
import itertools
import math
import struct
from collections import deque
from enum import IntEnum

_F32 = struct.Struct("f")


def float32(x: float) -> float:
    """``x`` rounded to the nearest float32."""
    return _F32.unpack(_F32.pack(x))[0]


class EventKind(IntEnum):
    """Order of simultaneous events: capacity freed at t is visible to
    an arrival at t; node deliveries, re-routes and churn toggles
    resolve before fresh arrivals."""
    EXEC_DONE = 0
    COLD_DONE = 1
    TIMER = 2
    NODE_ARRIVAL = 3
    REROUTE = 4
    CHURN = 5
    ARRIVAL = 7


class EventQueue:
    """Binary heap of ``(time, kind, seq)``; ``seq`` breaks ties FIFO."""

    def __init__(self):
        self._heap = []
        self._seq = itertools.count()

    def push(self, time, kind, payload=None):
        heapq.heappush(self._heap, (time, int(kind), next(self._seq),
                                    payload))

    def pop(self):
        if not self._heap:
            return None
        return heapq.heappop(self._heap)


class Request:
    __slots__ = ("req_id", "fn_id", "arrival", "exec_time", "start",
                 "completion")

    def __init__(self, req_id, fn_id, arrival, exec_time):
        self.req_id = req_id
        self.fn_id = fn_id
        self.arrival = arrival
        self.exec_time = exec_time
        self.start = -1.0
        self.completion = -1.0


class Function:
    __slots__ = ("fn_id", "cold_start", "evict")

    def __init__(self, fn_id, cold_start, evict):
        self.fn_id = fn_id
        self.cold_start = cold_start
        self.evict = evict


def build(arrays, r=float):
    """``(functions, requests)`` from the columnar trace, every time
    passed through ``r``."""
    functions = [Function(j, r(float(c)), r(float(v))) for j, (c, v)
                 in enumerate(zip(arrays["cold_start"], arrays["evict"]))]
    requests = [Request(i, int(f), r(float(t)), r(float(e))) for i, (f, t, e)
                in enumerate(zip(arrays["fn_id"], arrays["arrival"],
                                 arrays["exec_time"]))]
    return functions, requests


COLD, IDLE, BUSY = 0, 1, 2


class Instance:
    __slots__ = ("inst_id", "fn_id", "state", "ready_at", "current", "freq",
                 "priority", "last_used", "dead")

    def __init__(self, inst_id, fn_id, state, ready_at):
        self.inst_id = inst_id
        self.fn_id = fn_id
        self.state = state
        self.ready_at = ready_at
        self.current = None
        self.freq = 0
        self.priority = 0.0
        self.last_used = 0.0
        self.dead = False


class Estimator:
    """Per-function running mean of observed execution times; the
    global mean before a function's first completion, ``prior`` before
    any."""

    def __init__(self, n_functions, prior, r):
        self.n = [0] * n_functions
        self.sum = [0.0] * n_functions
        self.gn = 0
        self.gsum = 0.0
        self.prior = prior
        self.r = r

    def observe(self, fn_id, exec_time):
        self.n[fn_id] += 1
        self.sum[fn_id] = self.r(self.sum[fn_id] + exec_time)
        self.gn += 1
        self.gsum = self.r(self.gsum + exec_time)

    def mean(self, fn_id):
        if self.n[fn_id] > 0:
            return max(self.r(self.sum[fn_id] / self.n[fn_id]), 1e-9)
        if self.gn > 0:
            return max(self.r(self.gsum / self.gn), 1e-9)
        return self.prior


class Server:
    """C slots; an instance holds a slot from its cold start until it
    is evicted."""

    def __init__(self, functions, capacity, events, r):
        self.functions = functions
        self.capacity = capacity
        self.events = events
        self.r = r
        self.instances = {}
        self.by_fn = {f.fn_id: set() for f in functions}
        self.cold_starts = 0
        self.cold_time = 0.0
        self.evictions = 0
        self._ids = itertools.count()

    def has_free_slot(self):
        return len(self.instances) < self.capacity

    def k_count(self, fn_id):
        return len(self.by_fn[fn_id])

    def idle_of(self, fn_id):
        for iid in sorted(self.by_fn[fn_id]):
            inst = self.instances[iid]
            if inst.state == IDLE:
                return inst
        return None

    def idle_instances(self):
        return [i for i in self.instances.values() if i.state == IDLE]

    def busy(self):
        return sum(1 for i in self.instances.values() if i.state == BUSY)

    def dispatch(self, inst, req, t):
        inst.state = BUSY
        inst.current = req
        inst.freq += 1
        inst.last_used = t
        req.start = t
        req.completion = self.r(t + req.exec_time)
        self.events.push(req.completion, EventKind.EXEC_DONE, inst)

    def start_cold(self, fn_id, t, evict=None):
        delay = self.functions[fn_id].cold_start
        if evict is not None:
            delay = self.r(delay + self.functions[evict.fn_id].evict)
            self.evictions += 1
            del self.instances[evict.inst_id]
            self.by_fn[evict.fn_id].discard(evict.inst_id)
        if len(self.instances) >= self.capacity:
            raise RuntimeError("start_cold would exceed capacity")
        inst = Instance(next(self._ids), fn_id, COLD, self.r(t + delay))
        self.instances[inst.inst_id] = inst
        self.by_fn[fn_id].add(inst.inst_id)
        self.cold_starts += 1
        self.cold_time = self.r(self.cold_time
                                + self.functions[fn_id].cold_start)
        self.events.push(inst.ready_at, EventKind.COLD_DONE, inst)
        return inst

    @staticmethod
    def make_idle(inst):
        inst.state = IDLE
        inst.current = None


# ------------------------------------------------------------- policies
class Policy:
    name = "base"

    def __init__(self, server, est):
        self.server = server
        self.est = est
        self.functions = server.functions
        self.r = server.r

    def waiting(self):
        return sum(len(q) for q in self.queues.values())

    def on_timer(self, payload, t):
        pass


class ESFF(Policy):
    """Enhanced Shortest Function First: FCP at arrival (Alg. 2), FRP
    at completion (Alg. 3)."""

    name = "esff"

    def __init__(self, server, est):
        super().__init__(server, est)
        self.queues = {f.fn_id: deque() for f in self.functions}

    def _weight_current(self, fn_id):
        n_w = len(self.queues[fn_id])
        if n_w == 0:
            return math.inf
        f = self.functions[fn_id]
        k = self.server.k_count(fn_id)
        r = self.r
        return r(self.est.mean(fn_id) + r(r(f.evict * k) / n_w))

    def _drain_estimate(self, fn_id, window):
        n_w = len(self.queues[fn_id])
        k = self.server.k_count(fn_id)
        r = self.r
        return r(n_w + 1.0 - r(r(window * k) / self.est.mean(fn_id)))

    def _setup_weight(self, fn_id, n_e):
        f = self.functions[fn_id]
        k = self.server.k_count(fn_id)
        r = self.r
        return r(r(r(f.cold_start + f.evict) * (k + 1)) / n_e)

    def _weight_candidate(self, fn_id, n_e):
        return self.r(self.est.mean(fn_id) + self._setup_weight(fn_id, n_e))

    def _pick_victim(self, fn, srv):
        best, best_exec = None, -1.0
        for inst in srv.idle_instances():
            if inst.fn_id == fn:
                continue
            window = self.r(self.functions[fn].cold_start
                            + self.functions[inst.fn_id].evict)
            if self._drain_estimate(fn, window) > 0:
                mean = self.est.mean(inst.fn_id)
                if mean > best_exec:
                    best, best_exec = inst, mean
        return best

    def on_arrival(self, req, t):
        fn = req.fn_id
        srv = self.server
        idle = srv.idle_of(fn)
        if not self.queues[fn] and idle is not None:
            srv.dispatch(idle, req, t)
            return
        if srv.has_free_slot():
            n_e = self._drain_estimate(fn, self.functions[fn].cold_start)
            if n_e > 0:
                srv.start_cold(fn, t)
        else:
            best = self._pick_victim(fn, srv)
            if best is not None:
                srv.start_cold(fn, t, evict=best)
        self.queues[fn].append(req)

    def on_cold_done(self, inst, t):
        q = self.queues[inst.fn_id]
        self.server.make_idle(inst)
        if q:
            self.server.dispatch(inst, q.popleft(), t)

    def on_exec_done(self, inst, req, t):
        fn = inst.fn_id
        srv = self.server
        w_x = self._weight_current(fn)
        f_x = fn
        for g in self.functions:
            j2 = g.fn_id
            if j2 == fn or not self.queues[j2]:
                continue
            window = self.r(g.cold_start + self.functions[fn].evict)
            n_e = self._drain_estimate(j2, window)
            if n_e <= 0:
                continue
            w = self._weight_candidate(j2, n_e)
            if w < w_x:
                w_x, f_x = w, j2
        if f_x != fn:
            srv.make_idle(inst)
            srv.start_cold(f_x, t, evict=inst)
        elif self.queues[fn]:
            srv.make_idle(inst)
            srv.dispatch(inst, self.queues[fn].popleft(), t)
        else:
            srv.make_idle(inst)


class ESFFH(ESFF):
    """ESFF with hysteresis ``beta`` on the conversion cost, in-flight
    cold starts claiming waiting requests, and an LRU victim."""

    name = "esff_h"
    beta = 2.0

    def _drain_estimate(self, fn_id, window):
        srv = self.server
        cold = sum(1 for i in srv.by_fn[fn_id]
                   if srv.instances[i].state == COLD)
        return self.r(super()._drain_estimate(fn_id, window) - cold)

    def _setup_weight(self, fn_id, n_e):
        f = self.functions[fn_id]
        k = self.server.k_count(fn_id)
        r = self.r
        return r(r(r(self.beta * r(f.cold_start + f.evict)) * (k + 1)) / n_e)

    def _pick_victim(self, fn, srv):
        best, best_lru = None, None
        for inst in srv.idle_instances():
            if inst.fn_id == fn:
                continue
            window = self.r(self.functions[fn].cold_start
                            + self.functions[inst.fn_id].evict)
            if self._drain_estimate(fn, window) > 0:
                if best is None or inst.last_used < best_lru:
                    best, best_lru = inst, inst.last_used
        return best


class CentralQueue(Policy):
    """OpenWhisk-style central queue, kept as one FIFO per function;
    the head is the minimum of ``_key`` over the per-function heads."""

    def __init__(self, server, est):
        super().__init__(server, est)
        self.queues = {f.fn_id: deque() for f in self.functions}

    def _key(self, req):
        return (req.arrival, req.req_id)

    def _head(self):
        best, best_key = None, None
        for q in self.queues.values():
            if q:
                k = self._key(q[0])
                if best_key is None or k < best_key:
                    best, best_key = q[0], k
        return best

    def _victim(self):
        idle = self.server.idle_instances()
        if not idle:
            return None
        return min(idle, key=lambda i: (i.last_used, i.inst_id))

    def _note_evict(self, inst):
        pass

    def _note_use(self, inst):
        pass

    def on_arrival(self, req, t):
        srv = self.server
        idle = srv.idle_of(req.fn_id)
        if idle is not None:
            self._note_use(idle)
            srv.dispatch(idle, req, t)
            return
        self.queues[req.fn_id].append(req)
        if srv.has_free_slot():
            srv.start_cold(req.fn_id, t)
        else:
            victim = self._victim()
            if victim is not None:
                self._note_evict(victim)
                srv.start_cold(req.fn_id, t, evict=victim)

    def on_cold_done(self, inst, t):
        self.server.make_idle(inst)
        q = self.queues[inst.fn_id]
        if q:
            req = q.popleft()
            self._note_use(inst)
            self.server.dispatch(inst, req, t)
            return
        self._serve_or_replace(inst, t)

    def on_exec_done(self, inst, req, t):
        self.server.make_idle(inst)
        self._serve_or_replace(inst, t)

    def _serve_or_replace(self, inst, t):
        """A warm slot serves its own function's earliest request, else
        retargets to the queue head's function, with at most one
        replica of it warming."""
        srv = self.server
        head = self._head()
        if head is None:
            return
        if self.queues[inst.fn_id]:
            head = self.queues[inst.fn_id][0]
        if head.fn_id == inst.fn_id:
            self.queues[head.fn_id].popleft()
            self._note_use(inst)
            srv.dispatch(inst, head, t)
            return
        warming = sum(1 for i in srv.by_fn[head.fn_id]
                      if srv.instances[i].state == COLD)
        if warming < 1:
            self._note_evict(inst)
            srv.start_cold(head.fn_id, t, evict=inst)


class OpenWhisk(CentralQueue):
    name = "openwhisk"


class SFF(CentralQueue):
    """Central queue ordered by the running-mean execution time."""

    name = "sff"

    def _key(self, req):
        return (self.est.mean(req.fn_id), req.arrival, req.req_id)


class FaasCache(CentralQueue):
    """GREEDY-DUAL keep-alive: evict the idle instance of lowest
    ``clock + freq * cold_start``."""

    name = "faascache"

    def __init__(self, server, est):
        super().__init__(server, est)
        self.clock = 0.0

    def _note_use(self, inst):
        inst.priority = self.r(
            self.clock
            + self.r((inst.freq + 1) * self.functions[inst.fn_id].cold_start))

    def _note_evict(self, inst):
        self.clock = max(self.clock, inst.priority)

    def _victim(self):
        idle = self.server.idle_instances()
        if not idle:
            return None
        return min(idle, key=lambda i: (i.priority, i.inst_id))


class OpenWhiskV2(Policy):
    """Per-function queues; a new instance starts only once the queue
    head has waited ``threshold`` seconds."""

    name = "openwhisk_v2"
    threshold = 0.1

    def __init__(self, server, est):
        super().__init__(server, est)
        self.queues = {f.fn_id: deque() for f in self.functions}

    def _arm(self, req, t):
        self.server.events.push(self.r(t + self.threshold), EventKind.TIMER,
                                req)

    def on_arrival(self, req, t):
        srv = self.server
        idle = srv.idle_of(req.fn_id)
        if not self.queues[req.fn_id] and idle is not None:
            srv.dispatch(idle, req, t)
            return
        self.queues[req.fn_id].append(req)
        self._arm(req, t)

    def on_timer(self, req, t):
        if req.start >= 0:
            return
        q = self.queues[req.fn_id]
        if not q or q[0] is not req:
            return
        srv = self.server
        warming = any(srv.instances[i].state == COLD
                      for i in srv.by_fn[req.fn_id])
        if warming:
            self._arm(req, t)
        elif srv.has_free_slot():
            srv.start_cold(req.fn_id, t)
        else:
            idle = srv.idle_instances()
            if idle:
                victim = min(idle, key=lambda i: (i.last_used, i.inst_id))
                srv.start_cold(req.fn_id, t, evict=victim)
            else:
                self._arm(req, t)

    def on_cold_done(self, inst, t):
        self.server.make_idle(inst)
        q = self.queues[inst.fn_id]
        if q:
            self.server.dispatch(inst, q.popleft(), t)

    def on_exec_done(self, inst, req, t):
        self.server.make_idle(inst)
        q = self.queues[inst.fn_id]
        if q:
            self.server.dispatch(inst, q.popleft(), t)


POLICIES = {p.name: p for p in (ESFF, ESFFH, SFF, OpenWhisk, FaasCache,
                                OpenWhiskV2)}


def fold(requests, arrival_of, r=float):
    """The per-lane statistics the engine reports, from the requests'
    completions: ``done``, response and slowdown sums and means, and
    the largest response. ``arrival_of(req)`` is the time a response is
    measured from."""
    resp_sum = slow_sum = 0.0
    max_resp = 0.0
    done = 0
    for q in sorted(requests, key=lambda q: (q.completion, q.req_id)):
        if q.completion < 0:
            continue
        resp = r(q.completion - arrival_of(q))
        done += 1
        resp_sum = r(resp_sum + resp)
        slow_sum = r(slow_sum + r(resp / max(q.exec_time, 1e-9)))
        max_resp = max(max_resp, resp)
    n = max(len(requests), 1)
    return dict(done=done, resp_sum=resp_sum, slow_sum=slow_sum,
                mean_response=r(resp_sum / n), mean_slowdown=r(slow_sum / n),
                max_response=max_resp)


def simulate(arrays, policy, capacity, *, prior=0.1, r=float):
    """Run ``policy`` on a ``capacity``-slot node over the columnar
    trace ``arrays``; returns the lane's statistics."""
    functions, requests = build(arrays, r)
    events = EventQueue()
    server = Server(functions, capacity, events, r)
    est = Estimator(len(functions), prior, r)
    pol = POLICIES[policy](server, est)
    for q in requests:
        events.push(q.arrival, EventKind.ARRIVAL, q)
    while True:
        ev = events.pop()
        if ev is None:
            break
        t, kind, _, payload = ev
        if kind == EventKind.ARRIVAL:
            pol.on_arrival(payload, t)
        elif kind == EventKind.EXEC_DONE:
            q = payload.current
            est.observe(q.fn_id, q.exec_time)
            pol.on_exec_done(payload, q, t)
        elif kind == EventKind.COLD_DONE:
            pol.on_cold_done(payload, t)
        elif kind == EventKind.TIMER:
            pol.on_timer(payload, t)
    out = fold(requests, lambda q: q.arrival, r)
    out.update(cold_starts=server.cold_starts, evictions=server.evictions,
               cold_time=server.cold_time)
    return out


def lane_stats(cell, streams, lane, r=float):
    """The statistics of ``lane = (policy, capacity, k)`` of a
    `bench.cell.Cell`'s unit over ``streams``: the entry point of
    `bench.check.reference`."""
    policy, capacity, k = lane
    return simulate(streams[k], policy, capacity,
                    prior=cell.config["prior_s"], r=r)
