"""Policy kernels for the vectorised event core (`repro.core.jax_engine`).

Each kernel re-expresses one Python scheduling policy as pure functions
over the engine's fixed-shape state, request-for-request equivalent to
its event-driven counterpart (tests/test_jax_engine.py):

* **esff** — FCP (Alg. 2) / FRP (Alg. 3) with running-mean estimation;
  ``beta`` = 1.0 recovers the paper-faithful scheduler and > 1 adds the
  ESFF-H hysteresis on the conversion setup cost.
* **esff_h** — ESFF plus the three ESFF-H fixes (`repro.core.esff_h`):
  beta hysteresis (default 2.0), cold-aware drain estimates (in-flight
  instances claim a waiting request) and LRU victim choice in Eq. 8.
* **sff / openwhisk** — the central-queue baselines: immediate scale-up
  on arrival (LRU eviction at capacity), warm reuse of a freed slot's
  own queue, otherwise retarget to the central-queue head (at most one
  warming replica). SFF orders the central queue by running-mean
  execution time, OpenWhisk by arrival.
* **faascache** — OpenWhisk scheduling with GREEDY-DUAL keep-alive
  [Fuerst & Sharma, ASPLOS'21]: per-slot ``slot_freq``/``slot_prio``
  state plus a global clock; eviction victim = lowest
  ``clock + freq * cold_start`` priority, clock bumped to the evicted
  priority.
* **openwhisk_v2** — per-function queues; a queue head must wait
  ``threshold`` (100 ms) before scale-up, enforced with engine timers.

Hooks follow the engine's guarded-write convention: they execute every
loop iteration, compute with possibly-garbage values when their ``on``
predicate is false, and fold the predicate into every state write (so
disabled paths cost dropped scatters instead of dense selects under
vmap). Tie-breaking faithfully mirrors the Python engine's iteration
order via the per-slot creation sequence numbers (``slot_seq``) the
engine maintains: victim scans break ties toward the earliest-created
instance, exactly like scanning ``instances`` in ``inst_id`` order with
strict inequalities.

Kernels address the trace exclusively through the `EngineCtx` read
API (``fn_at`` / ``arrival_at`` / ``exec_at`` / ``rid_at_pos``) with
*absolute* request ids and per-function positions; the engine's
cache-window machinery translates those to window-relative slab
indices underneath (and to full-operand fallbacks for ids whose queue
links span a window boundary), so a kernel is automatically correct —
and bitwise identical — at every window size. Dispatch accounting
likewise rides the engine's ``dispatch`` helper, whose per-event
metric registers keep the streamed accumulators window-invariant; a
kernel must never write result state directly.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.jax_engine import (BIG, COLD, IDLE, PolicyKernel,
                                   _bump, _gidx, _put, arm_timer,
                                   cold_counts, dispatch, est_means,
                                   fn_count, k_counts, lex_argmin,
                                   pick_idle_own, q_consume_direct,
                                   q_head, q_pop, q_push, rearm_timer,
                                   start_cold)


class ESFFKernel(PolicyKernel):
    """ESFF (Algorithms 1-3); flags select the ESFF-H variants."""

    def __init__(self, name: str, *, lru_victim: bool = False,
                 cold_aware: bool = False, default_beta: float = 1.0):
        self.name = name
        self.lru_victim = lru_victim
        self.cold_aware = cold_aware
        self.default_beta = default_beta

    # ------------------------------------------------- FCP (Algorithm 2)
    def on_arrival(self, ctx, s, rid, t, on):
        j = ctx.fn_at(rid)
        # only function j's counts: O(C) masked sums, no (F,) histogram
        means = est_means(ctx, s)
        Kj = fn_count(s, j)
        coldKj = (fn_count(s, j, cold=True).astype(jnp.float64)
                  if self.cold_aware else None)
        has_own, own_slot = pick_idle_own(ctx, s, j)
        direct = on & has_own & (s["q_len"][j] == 0)
        s = dispatch(ctx, s, own_slot, rid, t, direct)
        s = q_consume_direct(ctx, s, j, direct)
        queued = on & ~direct

        empty = (s["slot_fn"] < 0) & ctx.cap_mask
        n_e = s["q_len"][j] + 1.0 - ctx.t_cold[j] * Kj / means[j]
        if self.cold_aware:
            n_e = n_e - coldKj
        s = start_cold(ctx, s, jnp.argmax(empty), j, t, -1,
                       queued & empty.any() & (n_e > 0))

        idle = ((s["slot_state"] == IDLE) & (s["slot_fn"] >= 0)
                & (s["slot_fn"] != j) & ctx.cap_mask)
        sf = jnp.where(s["slot_fn"] >= 0, s["slot_fn"], 0)
        n_e2 = (s["q_len"][j] + 1.0
                - (ctx.t_cold[j] + ctx.t_evict[sf]) * Kj / means[j])
        if self.cold_aware:
            n_e2 = n_e2 - coldKj
        elig = idle & (n_e2 > 0)
        # Eq. 8 victim: argmax t̄_e (ESFF) or LRU (ESFF-H), ties toward
        # the earliest-created instance
        primary = s["slot_used"] if self.lru_victim else -means[sf]
        victim = lex_argmin(primary, s["slot_seq"], elig)
        s = start_cold(ctx, s, victim, j, t, s["slot_fn"][victim],
                       queued & ~empty.any() & elig.any())
        s, _ = q_push(ctx, s, j, rid, queued)
        return s

    # ----------------------------------------------------- instance ready
    def on_cold_done(self, ctx, s, slot, t, on):
        j = s["slot_fn"][slot]
        take = on & (s["q_len"][jnp.clip(j, 0, ctx.F - 1)] > 0)
        s, rid = q_pop(ctx, s, j, take)
        return dispatch(ctx, s, slot, rid, t, take)

    # ------------------------------------------------- FRP (Algorithm 3)
    def on_exec_done(self, ctx, s, slot, rid, t, on):
        j = s["slot_fn"][slot]
        jc = jnp.clip(j, 0, ctx.F - 1)
        means = est_means(ctx, s)
        K = k_counts(ctx, s).astype(jnp.float64)
        nw = s["q_len"].astype(jnp.float64)
        # Eq. (9)
        w_own = jnp.where(
            nw[jc] > 0,
            means[jc] + ctx.t_evict[jc] * K[jc]
            / jnp.maximum(nw[jc], 1),
            BIG)
        # Eq. (7) swapped + Eq. (10) with beta hysteresis
        n_e = nw + 1.0 - (ctx.t_cold + ctx.t_evict[jc]) * K / means
        if self.cold_aware:
            n_e = n_e - cold_counts(ctx, s).astype(jnp.float64)
        w = (means + ctx.beta * (ctx.t_cold + ctx.t_evict) * (K + 1.0)
             / jnp.maximum(n_e, 1e-30))
        idx = jnp.arange(ctx.F)
        valid = (nw > 0) & (n_e > 0) & (idx != jc)
        w = jnp.where(valid, w, BIG)
        best = jnp.argmin(w)

        replace = on & (w[best] < w_own) & valid.any()
        s = start_cold(ctx, s, slot, best, t, j, replace)
        take = on & ~replace & (s["q_len"][jc] > 0)
        s, rid2 = q_pop(ctx, s, j, take)
        return dispatch(ctx, s, slot, rid2, t, take)


class CentralQueueKernel(PolicyKernel):
    """OpenWhisk / SFF: central queue + immediate scale-up + LRU keep-
    alive, with warm reuse of a freed slot's own waiting requests.

    The eviction-victim key, the dispatch bookkeeping and the new-
    instance reset are overridable hooks so FaasCache can swap LRU for
    GREEDY-DUAL priorities without touching the queue discipline."""

    def __init__(self, name: str, *, order: str = "fifo"):
        assert order in ("fifo", "sff")
        self.name = name
        self.order = order

    # -- keep-alive hooks (FaasCache overrides) --------------------------
    def _dispatch(self, ctx, s, slot, rid, t, on):
        return dispatch(ctx, s, slot, rid, t, on)

    def _victim_key(self, ctx, s):
        """Primary eviction key among idle slots (ties: slot_seq)."""
        return s["slot_used"]    # LRU

    def _note_evict(self, ctx, s, victim, on):
        return s

    def _start_cold(self, ctx, s, slot, fn, t, evict_fn, on):
        return start_cold(ctx, s, slot, fn, t, evict_fn, on)

    def _head_fn(self, ctx, s):
        """Central-queue head: (exists, fn). Requests are globally
        FIFO-comparable by id (traces are arrival-sorted), so OpenWhisk
        minimises the head id and SFF (t̄_e, id) lexicographically."""
        heads = s["q_head_rid"]
        valid = s["q_len"] > 0
        if self.order == "sff":
            f = lex_argmin(est_means(ctx, s), heads, valid)
        else:
            f = lex_argmin(jnp.zeros((ctx.F,)), heads, valid)
        return valid.any(), f

    def _scale_up(self, ctx, s, j, t, on):
        """No idle instance for an arrival of ``j``: claim a free slot,
        else evict the keep-alive victim (LRU here; GREEDY-DUAL in
        FaasCache — ties: earliest-created)."""
        empty = (s["slot_fn"] < 0) & ctx.cap_mask
        s = self._start_cold(ctx, s, jnp.argmax(empty), j, t, -1,
                             on & empty.any())
        idle = (s["slot_state"] == IDLE) & (s["slot_fn"] >= 0) \
            & ctx.cap_mask
        victim = lex_argmin(self._victim_key(ctx, s), s["slot_seq"],
                            idle)
        evicting = on & ~empty.any() & idle.any()
        s = self._note_evict(ctx, s, victim, evicting)
        return self._start_cold(ctx, s, victim, j, t,
                                s["slot_fn"][victim], evicting)

    def on_arrival(self, ctx, s, rid, t, on):
        j = ctx.fn_at(rid)
        has_own, own_slot = pick_idle_own(ctx, s, j)
        # an idle own instance never coexists with a non-empty own
        # queue (every serve/replace path drains or converts first), so
        # the q_len gate is a no-op semantically — it guarantees the
        # positional-queue contract holds even for a buggy kernel state
        direct = on & has_own & (s["q_len"][jnp.clip(j, 0, ctx.F - 1)]
                                 == 0)
        s = self._dispatch(ctx, s, own_slot, rid, t, direct)
        s = q_consume_direct(ctx, s, j, direct)
        queued = on & ~direct
        s, _ = q_push(ctx, s, j, rid, queued)
        return self._scale_up(ctx, s, j, t, queued)

    def _serve_or_replace(self, ctx, s, slot, t, on):
        """Central-queue discipline for a freed idle slot: drain its own
        function's earliest request (warm reuse), else retarget to the
        queue-head function — at most one warming replica at a time."""
        j = s["slot_fn"][slot]
        own = on & (s["q_len"][jnp.clip(j, 0, ctx.F - 1)] > 0)
        s, rid = q_pop(ctx, s, j, own)
        s = self._dispatch(ctx, s, slot, rid, t, own)

        exists, f = self._head_fn(ctx, s)
        warming = ((s["slot_fn"] == f) & (s["slot_state"] == COLD)
                   & ctx.cap_mask).any()
        retarget = on & ~own & exists & ~warming
        s = self._note_evict(ctx, s, slot, retarget)
        return self._start_cold(ctx, s, slot, f, t, j, retarget)

    def on_cold_done(self, ctx, s, slot, t, on):
        return self._serve_or_replace(ctx, s, slot, t, on)

    def on_exec_done(self, ctx, s, slot, rid, t, on):
        return self._serve_or_replace(ctx, s, slot, t, on)


class FaasCacheKernel(CentralQueueKernel):
    """FaasCache [Fuerst & Sharma, ASPLOS'21]: OpenWhisk scheduling
    with GREEDY-DUAL keep-alive, request-for-request equivalent to
    `repro.core.baselines.FaasCache`.

    Per-slot state: ``slot_freq`` (use count of the resident instance)
    and ``slot_prio`` (= clock + freq * cold_start, recomputed at every
    dispatch with the pre-increment freq + 1, exactly the Python
    ``_note_use``/``dispatch`` order); ``gd_clock`` is the global clock,
    bumped to the victim's priority on every eviction. A fresh instance
    keeps priority 0.0 until its first dispatch (the Python
    ``Instance`` default), which is what ages never-used instances out
    first."""

    name = "faascache"

    def __init__(self):
        super().__init__("faascache", order="fifo")

    def extra_state(self, L, C, F):
        return dict(slot_freq=jnp.zeros((L, C), jnp.int32),
                    slot_prio=jnp.zeros((L, C), jnp.float64),
                    gd_clock=jnp.zeros((L,), jnp.float64))

    def _dispatch(self, ctx, s, slot, rid, t, on):
        sc = jnp.clip(slot, 0, ctx.C - 1)
        fn = jnp.clip(s["slot_fn"][sc], 0, ctx.F - 1)
        prio = (s["gd_clock"]
                + (s["slot_freq"][sc] + 1.0) * ctx.t_cold[fn])
        si = _gidx(on, slot, ctx.C)
        s = dict(s)
        s["slot_freq"] = _bump(s["slot_freq"], si, 1)
        s["slot_prio"] = _put(s["slot_prio"], si, prio)
        return dispatch(ctx, s, slot, rid, t, on)

    def _victim_key(self, ctx, s):
        return s["slot_prio"]    # GREEDY-DUAL

    def _note_evict(self, ctx, s, victim, on):
        prio = s["slot_prio"][jnp.clip(victim, 0, ctx.C - 1)]
        s = dict(s)
        s["gd_clock"] = jnp.maximum(
            s["gd_clock"], jnp.where(on, prio, -BIG))
        return s

    def _start_cold(self, ctx, s, slot, fn, t, evict_fn, on):
        s = start_cold(ctx, s, slot, fn, t, evict_fn, on)
        si = _gidx(on, slot, ctx.C)
        s["slot_freq"] = _put(s["slot_freq"], si, 0)
        s["slot_prio"] = _put(s["slot_prio"], si, 0.0)
        return s


class OpenWhiskV2Kernel(PolicyKernel):
    """Per-function queues + head-wait threshold before scale-up.

    Timers replicate the event engine exactly, including its quirks: a
    timer firing for a non-head request is dropped (the then-head's own
    timer is relied upon), so a request can lose its timer and then wait
    for a warm instance of its function — same as the Python policy.
    The Python policy's ``req.start >= 0`` guard is subsumed by the
    head check: a dispatched request was popped from its queue, so it
    can never still be the head.
    """

    name = "openwhisk_v2"
    has_timers = True

    def on_arrival(self, ctx, s, rid, t, on):
        j = ctx.fn_at(rid)
        has_own, own_slot = pick_idle_own(ctx, s, j)
        direct = on & has_own & (s["q_len"][j] == 0)
        s = dispatch(ctx, s, own_slot, rid, t, direct)
        s = q_consume_direct(ctx, s, j, direct)
        queued = on & ~direct
        s, pushed = q_push(ctx, s, j, rid, queued)
        return arm_timer(ctx, s, j, rid, t, pushed, on)

    def on_timer(self, ctx, s, rid, t, on):
        j = ctx.fn_at(rid)
        is_head = (s["q_len"][j] > 0) & (q_head(ctx, s, j) == rid)
        act = on & is_head
        warming = ((s["slot_fn"] == j) & (s["slot_state"] == COLD)
                   & ctx.cap_mask).any()

        empty = (s["slot_fn"] < 0) & ctx.cap_mask
        scale = act & ~warming
        s = start_cold(ctx, s, jnp.argmax(empty), j, t, -1,
                       scale & empty.any())
        idle = (s["slot_state"] == IDLE) & (s["slot_fn"] >= 0) \
            & ctx.cap_mask
        victim = lex_argmin(s["slot_used"], s["slot_seq"], idle)
        s = start_cold(ctx, s, victim, j, t, s["slot_fn"][victim],
                       scale & ~empty.any() & idle.any())
        # blocked (still warming, or nothing evictable): retry later
        rearm = (act & warming) | (scale & ~empty.any() & ~idle.any())
        return rearm_timer(ctx, s, j, rid, t + ctx.threshold, rearm)

    def _drain_own(self, ctx, s, slot, t, on):
        j = s["slot_fn"][slot]
        take = on & (s["q_len"][jnp.clip(j, 0, ctx.F - 1)] > 0)
        s, rid = q_pop(ctx, s, j, take)
        return dispatch(ctx, s, slot, rid, t, take)

    def on_cold_done(self, ctx, s, slot, t, on):
        return self._drain_own(ctx, s, slot, t, on)

    def on_exec_done(self, ctx, s, slot, rid, t, on):
        return self._drain_own(ctx, s, slot, t, on)


# Kernel singletons: stable identities keep the jit cache warm across
# calls (the kernel is a static argument of the engine).
KERNELS = {
    "esff": ESFFKernel("esff"),
    "esff_h": ESFFKernel("esff_h", lru_victim=True, cold_aware=True,
                         default_beta=2.0),
    "sff": CentralQueueKernel("sff", order="sff"),
    "openwhisk": CentralQueueKernel("openwhisk", order="fifo"),
    "faascache": FaasCacheKernel(),
    "openwhisk_v2": OpenWhiskV2Kernel(),
}
