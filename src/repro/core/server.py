"""Edge server model (paper §III): at most C co-resident function instances.

An :class:`Instance` occupies one slot from the moment its (cold start or
eviction+cold-start) transition begins until it is evicted. Replacing an
idle instance of f_{j'} by f_j therefore keeps the slot count at C and
costs ``t_{j'}^v + t_j^l`` before the new instance becomes ready — exactly
the paper's cost model.

With ``slot_mb`` set the node's resource is memory instead, as in
OpenWhisk's invoker container pool: a node of C slots holds
``C * slot_mb`` MB of user memory, an instance holds its function's
``mem_mb`` from the start of its cold start until it is evicted, and a
cold start may evict several idle instances (or none) to fit. The policy
chooses them (`repro.core.baselines.CentralQueuePolicy`).
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass, field
from enum import IntEnum
from typing import Dict, List, Optional, Sequence, Set, Union

from repro.core.events import EventKind, EventQueue
from repro.core.request import FunctionProfile, Request

# The smallest footprint an instance may have, in MB: OpenWhisk's
# minimum action memory. It bounds how many instances a node of memory
# holds, so it sizes the slot array of a memory-mode run.
MIN_MEM_MB = 128
# The policies that run on a node of memory: the central-queue policies,
# whose cold start admits by memory (`repro.core.baselines`,
# `repro.core.jax_policies.CentralQueueKernel`).
MEMORY_POLICIES = ("openwhisk", "sff", "faascache")


def check_memory(policies: Sequence[str], mem_mb: Sequence = (),
                 node_mb: int = 0) -> None:
    """Raise ``ValueError`` unless every one of ``policies`` runs on a
    node of memory and every footprint in ``mem_mb`` (``None`` for a
    function without one) lies in [`MIN_MEM_MB`, ``node_mb``]. The one
    check of ``ExperimentSpec(slot_mb=)``, ``simulate(slot_mb=)`` and
    ``simulate_policy_jax(slot_mb=)``."""
    plain = [p for p in policies if p not in MEMORY_POLICIES]
    if plain:
        raise ValueError(f"policies {plain} have no memory semantics; "
                         f"slot_mb runs only {', '.join(MEMORY_POLICIES)}")
    mem = list(mem_mb)
    if None in mem:
        raise ValueError("slot_mb needs a mem_mb footprint for every "
                         "function")
    bad = sorted({int(m) for m in mem if not MIN_MEM_MB <= m <= node_mb})
    if bad:
        raise ValueError(f"mem_mb must lie in [{MIN_MEM_MB}, {node_mb}] "
                         f"MB, the smallest node's memory; got {bad[:5]}")


class InstanceState(IntEnum):
    COLD = 0   # transitioning: eviction of predecessor + cold start
    IDLE = 1   # state(k) = 1 in the paper
    BUSY = 2   # state(k) = 0 in the paper


@dataclass
class Instance:
    inst_id: int
    fn_id: int
    state: InstanceState
    ready_at: float = 0.0
    current: Optional[Request] = None
    # bookkeeping for keep-alive style policies (FaasCache)
    freq: int = 0
    priority: float = 0.0
    last_used: float = 0.0


class ExecTimeEstimator:
    """Per-function running mean of *observed* execution times (§V).

    The scheduler can only learn execution times from completed requests.
    Before the first completion of f_j we fall back to the global running
    mean, and before any completion at all to ``prior`` seconds.
    """

    def __init__(self, n_functions: int, prior: float = 0.1,
                 oracle: Optional[List[float]] = None):
        self.n = [0] * n_functions
        self.sum = [0.0] * n_functions
        self.gn = 0
        self.gsum = 0.0
        self.prior = prior
        self.oracle = oracle

    def observe(self, fn_id: int, exec_time: float) -> None:
        self.n[fn_id] += 1
        self.sum[fn_id] += exec_time
        self.gn += 1
        self.gsum += exec_time

    def mean(self, fn_id: int) -> float:
        if self.oracle is not None:
            return max(self.oracle[fn_id], 1e-9)
        if self.n[fn_id] > 0:
            return max(self.sum[fn_id] / self.n[fn_id], 1e-9)
        if self.gn > 0:
            return max(self.gsum / self.gn, 1e-9)
        return self.prior


@dataclass
class ServerStats:
    cold_starts: int = 0
    cold_time: float = 0.0
    evictions: int = 0
    evict_time: float = 0.0
    busy_time: float = 0.0
    # memory mode only: MB evicted, cold starts that evicted two or more
    # instances, and cold starts deferred for want of memory
    evict_mb: int = 0
    multi_victim_starts: int = 0
    mem_waits: int = 0


class EdgeServer:
    """Slot/instance bookkeeping shared by every scheduling policy."""

    def __init__(self, functions: List[FunctionProfile], capacity: int,
                 events: EventQueue, slot_mb: Optional[int] = None):
        self.functions = functions
        self.capacity = capacity
        self.events = events
        self.instances: Dict[int, Instance] = {}
        self.by_fn: Dict[int, Set[int]] = {f.fn_id: set() for f in functions}
        self.stats = ServerStats()
        self._ids = itertools.count()
        # free user memory in MB; None when the resource is slots
        self.free_mb: Optional[int] = (None if slot_mb is None
                                       else capacity * slot_mb)

    # ------------------------------------------------------------ queries
    def total_instances(self) -> int:
        return len(self.instances)

    def has_free_slot(self) -> bool:
        return len(self.instances) < self.capacity

    def k_count(self, fn_id: int) -> int:
        """|K^j| — instances currently assigned to f_j (any state)."""
        return len(self.by_fn[fn_id])

    def idle_of(self, fn_id: int) -> Optional[Instance]:
        # sorted => earliest-created first: deterministic across runs and
        # engines (set iteration order would leak hash-table layout)
        for iid in sorted(self.by_fn[fn_id]):
            inst = self.instances[iid]
            if inst.state == InstanceState.IDLE:
                return inst
        return None

    def idle_instances(self) -> List[Instance]:
        return [i for i in self.instances.values()
                if i.state == InstanceState.IDLE]

    def has_idle(self, fn_id: int) -> bool:
        return self.idle_of(fn_id) is not None

    # --------------------------------------------------------- primitives
    def dispatch(self, inst: Instance, req: Request, t: float) -> None:
        """Run ``req`` on an *idle* instance of its function."""
        assert inst.state == InstanceState.IDLE, inst
        assert inst.fn_id == req.fn_id
        inst.state = InstanceState.BUSY
        inst.current = req
        inst.freq += 1
        inst.last_used = t
        req.start = t
        req.completion = t + req.exec_time
        self.stats.busy_time += req.exec_time
        self.events.push(req.completion, EventKind.EXEC_DONE, inst)

    def start_cold(self, fn_id: int, t: float,
                   evict: Union[Instance, Sequence[Instance], None] = None
                   ) -> Instance:
        """Begin initialising a new instance of f_j, optionally by evicting
        *idle* instances first (cost t_v of each evicted function, added
        in the order given). ``evict`` is one instance, or in memory mode
        the list of victims, which may be empty."""
        delay = self.functions[fn_id].cold_start
        victims = ([] if evict is None else [evict]
                   if isinstance(evict, Instance) else list(evict))
        for v in victims:
            assert v.state == InstanceState.IDLE, v
            delay += self.functions[v.fn_id].evict
            self.stats.evictions += 1
            self.stats.evict_time += self.functions[v.fn_id].evict
            self._remove(v)
        if self.free_mb is None:
            if len(self.instances) >= self.capacity:
                raise RuntimeError("start_cold would exceed capacity")
        else:
            mem = self.functions[fn_id].mem_mb
            if mem > self.free_mb:
                raise RuntimeError("start_cold would exceed node memory")
            self.free_mb -= mem
            self.stats.multi_victim_starts += len(victims) >= 2
        inst = Instance(next(self._ids), fn_id, InstanceState.COLD,
                        ready_at=t + delay)
        self.instances[inst.inst_id] = inst
        self.by_fn[fn_id].add(inst.inst_id)
        self.stats.cold_starts += 1
        self.stats.cold_time += self.functions[fn_id].cold_start
        self.events.push(inst.ready_at, EventKind.COLD_DONE, inst)
        return inst

    def make_idle(self, inst: Instance) -> None:
        inst.state = InstanceState.IDLE
        inst.current = None

    def _remove(self, inst: Instance) -> None:
        del self.instances[inst.inst_id]
        self.by_fn[inst.fn_id].discard(inst.inst_id)
        if self.free_mb is not None:
            mem = self.functions[inst.fn_id].mem_mb
            self.free_mb += mem
            self.stats.evict_mb += mem
