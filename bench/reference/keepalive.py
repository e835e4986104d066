"""Plain reference of one edge node whose resource is memory, under the
central-queue policies (OpenWhisk, SFF, FaasCache).

As OpenWhisk's invoker container pool admits containers: a node of C
slots holds ``C * slot_mb`` MB of user memory, and an instance holds its
function's ``mem_mb`` from the start of its cold start until its
eviction. A cold start whose footprint does not fit in free memory
evicts idle instances in the policy's victim order (least recently used
for OpenWhisk and SFF, GREEDY-DUAL for FaasCache; ties to the
earliest-created) until it does; if all of them would not free enough,
it evicts nothing, starts nothing, and its request waits in the central
queue. A freed instance that retargets to the queue head's function is
the first victim of that cold start. FaasCache's priority is
``clock + (freq + 1) * cold_start / mem_mb`` (Fuerst & Sharma, ASPLOS
'21), and the clock rises to the largest priority evicted.

The subclasses of `bench.reference.node` below change only the
admission, independent of the program's code. Every arithmetic result
on a time, an estimate or a metric passes through ``r``; footprints
are whole MB.
"""
from __future__ import annotations

from bench.reference import node


class Server(node.Server):
    """``node.Server`` with free memory in place of free slots."""

    def __init__(self, functions, capacity, events, r, mem_mb, node_mb):
        super().__init__(functions, capacity, events, r)
        self.mem_mb = mem_mb
        self.free_mb = node_mb
        self.evict_mb = 0
        self.multi_victim_starts = 0
        self.mem_waits = 0

    def start_cold(self, fn_id, t, evict=()):
        """Evict the idle instances ``evict``, in order, then start."""
        delay = self.functions[fn_id].cold_start
        for v in evict:
            delay = self.r(delay + self.functions[v.fn_id].evict)
            self.evictions += 1
            self.evict_mb += self.mem_mb[v.fn_id]
            self.free_mb += self.mem_mb[v.fn_id]
            del self.instances[v.inst_id]
            self.by_fn[v.fn_id].discard(v.inst_id)
        if self.mem_mb[fn_id] > self.free_mb:
            raise RuntimeError("start_cold would exceed node memory")
        self.free_mb -= self.mem_mb[fn_id]
        self.multi_victim_starts += len(evict) >= 2
        inst = node.Instance(next(self._ids), fn_id, node.COLD,
                             self.r(t + delay))
        self.instances[inst.inst_id] = inst
        self.by_fn[fn_id].add(inst.inst_id)
        self.cold_starts += 1
        self.cold_time = self.r(self.cold_time
                                + self.functions[fn_id].cold_start)
        self.events.push(inst.ready_at, node.EventKind.COLD_DONE, inst)
        return inst


class Memory:
    """Container-pool admission for a `node.CentralQueue` policy."""

    def _victim_key(self, inst):
        return (inst.last_used, inst.inst_id)

    def _victims(self, fn_id, first=None):
        """The instances a cold start of ``fn_id`` evicts, in order, or
        ``None`` when even every idle one would not free enough."""
        srv = self.server
        need = srv.mem_mb[fn_id] - srv.free_mb
        chosen = [] if first is None else [first]
        for inst in chosen:
            need -= srv.mem_mb[inst.fn_id]
        rest = sorted((i for i in srv.idle_instances() if i is not first),
                      key=self._victim_key)
        for inst in rest:
            if need <= 0:
                break
            chosen.append(inst)
            need -= srv.mem_mb[inst.fn_id]
        return chosen if need <= 0 else None

    def _admit(self, fn_id, t, first=None):
        victims = self._victims(fn_id, first)
        if victims is None:
            self.server.mem_waits += 1
            return
        for v in victims:
            self._note_evict(v)
        self.server.start_cold(fn_id, t, evict=victims)

    def on_arrival(self, req, t):
        srv = self.server
        idle = srv.idle_of(req.fn_id)
        if idle is not None:
            self._note_use(idle)
            srv.dispatch(idle, req, t)
            return
        self.queues[req.fn_id].append(req)
        self._admit(req.fn_id, t)

    def _serve_or_replace(self, inst, t):
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
                      if srv.instances[i].state == node.COLD)
        if warming < 1:
            self._admit(head.fn_id, t, first=inst)


class OpenWhisk(Memory, node.OpenWhisk):
    pass


class SFF(Memory, node.SFF):
    pass


class FaasCache(Memory, node.FaasCache):
    """GREEDY-DUAL with size: the cost of a miss over the footprint."""

    def _victim_key(self, inst):
        return (inst.priority, inst.inst_id)

    def _note_use(self, inst):
        r = self.r
        value = r((inst.freq + 1) * self.functions[inst.fn_id].cold_start)
        inst.priority = r(self.clock
                          + r(value / self.server.mem_mb[inst.fn_id]))


POLICIES = {p.name: p for p in (OpenWhisk, SFF, FaasCache)}


def simulate(arrays, policy, capacity, slot_mb, *, prior=0.1, r=float):
    """Run ``policy`` on a node of ``capacity * slot_mb`` MB over the
    columnar trace ``arrays`` (with its ``mem_mb`` column); returns the
    lane's statistics."""
    functions, requests = node.build(arrays, r)
    mem_mb = [int(m) for m in arrays["mem_mb"]]
    events = node.EventQueue()
    server = Server(functions, capacity, events, r, mem_mb,
                    capacity * slot_mb)
    est = node.Estimator(len(functions), prior, r)
    pol = POLICIES[policy](server, est)
    for q in requests:
        events.push(q.arrival, node.EventKind.ARRIVAL, q)
    while True:
        ev = events.pop()
        if ev is None:
            break
        t, kind, _, payload = ev
        if kind == node.EventKind.ARRIVAL:
            pol.on_arrival(payload, t)
        elif kind == node.EventKind.EXEC_DONE:
            q = payload.current
            est.observe(q.fn_id, q.exec_time)
            pol.on_exec_done(payload, q, t)
        elif kind == node.EventKind.COLD_DONE:
            pol.on_cold_done(payload, t)
    out = node.fold(requests, lambda q: q.arrival, r)
    out.update(cold_starts=server.cold_starts, evictions=server.evictions,
               cold_time=server.cold_time, evict_mb=server.evict_mb,
               multi_victim_starts=server.multi_victim_starts,
               mem_waits=server.mem_waits)
    return out


def lane_stats(cell, streams, lane, r=float):
    """The statistics of ``lane = (policy, capacity, k)`` of a
    `bench.cell.Cell`'s unit over ``streams``, on nodes of the cell's
    ``slot_mb`` option: the entry point of `bench.check.reference`."""
    policy, capacity, k = lane
    return simulate(streams[k], policy, capacity, cell.options["slot_mb"],
                    prior=cell.config["prior_s"], r=r)
