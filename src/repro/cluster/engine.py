"""Dynamic-routing cluster engine: K nodes, one vectorised event loop.

Static routers can pre-partition the arrival stream and reuse the
single-node engine per node (`repro.cluster.static`); a *dynamic*
router (JSQ(d), cold-aware) reads live cluster state at every arrival,
so the routing decision has to live inside the event loop. This module
generalises `repro.core.jax_engine._simulate` to K co-simulated nodes
per lane:

* **slots** become a (L, K, C) node-major rail — the packed next-event
  argmin runs over the flattened (L, 2·K·C + …) candidate matrix, so
  the same-time class order (EXEC < COLD < TIMER < NODE_ARRIVAL <
  ARRIVAL) and the within-class index tie-break extend the single-node
  engine's exactly (node-major order within each class);
* **queues** become per-(node, function) FIFOs carried as a
  *segment-overlay link rail*: runtime routing breaks the single-node
  engine's positional-cursor invariant (which arrivals of f_j reach
  node k is state-dependent), so successor links live in an (L, N) i32
  rail ``nxt`` — but per event only a per-lane (pos, val) register is
  written, staged into an (L, SEG) overlay slot, and the rail itself is
  batch-scattered **once per segment**. Link *reads* (queue pops) are
  lazy: the popped head's successor is chased in-body (overlay match
  first, single-element rail gather second — each link position is
  written at most once ever, so a stale overlay entry can only repeat
  the flushed rail value) and lands in the parked head register. All
  queue-cursor writes (``q_len``/``q_head_rid``/``q_tail_rid``) park
  in per-lane (pos, val/delta) registers and are applied as
  single-element scatters at the **top of the next step**, before
  anything reads those arrays — write-first carry, which keeps the
  (L, K, F) cursor buffers copy-free under XLA's in-place analysis
  (read-early/write-late keeps a buffer live across the body and
  costs two full copies per event per array). Carried-copy cost is
  one (L, N) scatter per SEG events — O(F + C + SEG)-amortised per
  event, the single-node streaming-carry regime, instead of the
  O(N)-per-event gather+scatter of the earlier linked-list spelling;
* **timer rails** (``openwhisk_v2``) ride a second link chain ``tnx``
  over *node arrivals*: per (node, fn) the engine carries the chain
  tail, an arrival counter and a consumed counter, so the rid-chain
  reproduces the single-node positional timer rail event-for-event
  (arm at the node-local arrival, fire in arrival order, silent
  consume on direct dispatch, no-op fires gated by the queue-head
  check) without any arrival-order precomputation;
* **per-node net_delay** becomes a third chain ``dnx``: the router
  decides at the raw ARRIVAL time, the request is appended to its
  node's in-flight FIFO and surfaces as a deferred NODE_ARRIVAL
  candidate ``delay_k`` later — the node's policy, timers and response
  accounting all run on the node-local clock (response is measured
  from the delayed arrival, matching the static tier's convention);
* **estimators** are node-local ((L, K, F) running sums plus (L, K)
  node-global fallbacks): each node's scheduler learns only from its
  own completions, exactly as K independent servers would;
* **churn** (PR 7) adds a NODE_DOWN/NODE_UP event class on a per-node
  toggle-time operand ``churn_t`` with a carried cursor ``ch_ix``
  (even parity = up). NODE_DOWN drains the dying node — busy-slot
  requests sorted by rid, then the per-fn queues fn-major — onto a
  per-lane *park FIFO* (an O(1) chain splice on the ``nxt`` rail);
  one REROUTE/orphan candidate re-injects the park head through the
  router per event. Routers never see a down node (`ClusterView.up`
  mask + a lowest-up-id correction); when every node is down the park
  queue simply holds (its candidate gates on ``any_up``) until the
  next NODE_UP re-arms it. Cold state dies with the node, requests
  never do — conservation is exact and parity-tested. Because a
  drained rid re-enters some queue later, the write-once link
  invariant behind the segment overlays no longer holds, so under the
  static ``has_churn`` flag the engine switches to direct per-event
  rail writes (and commits the queue-cursor rows like any other nodal
  array); the no-churn path compiles to the exact PR-6 program. The
  metric fold also moves from dispatch time to EXEC_DONE (a drained
  request's dispatch record must not count) and responses are
  measured from the *raw* arrival — the user-perceived, SLO-honest
  convention; no-churn paths keep their node-local convention
  bit-for-bit. Time-varying per-node delay (``var_delay`` +
  `DelaySchedule` operands) rides the same deferred-arrival rail with
  the landing time sampled at send time.

Policy kernels run *unmodified*: per event the lane state is sliced
into a single-node **view** of the event's node — one view/commit pair
per event, shared by the slot, timer and arrival phases (the phases
are mutually exclusive by construction, and the router runs first,
before any enabled write) — and the kernel's hooks operate on that
view through a `ClusterNodeCtx`, which overrides the ctx-dispatched
queue/timer ops with the overlay-rail discipline and `est_means` with
the node-local fallback chain. The view slice and the row commit are
*lane-stacked*, outside the vmapped event body: a vmapped
dynamic-index over (L, K, F) carries is a batched-operand gather —
the generic XLA:CPU path, O(K·F) per event — while one
`take_along_axis` / row scatter per nodal array stays on the fast
path, so per-event cost is O(F + C), independent of K.

With ``n_nodes=1`` and zero delay the loop degenerates to the
single-node engine — same candidate order, same helper arithmetic,
same fold — and is bitwise identical to it for every kernel,
timer-rail policies included (gated in ``benchmarks/run.py --smoke``
and tests/test_cluster.py). The delay and timer machinery is gated
*statically*, so the zero-delay/no-timer arithmetic contains no
spurious ``+0.0`` or extra candidates. The static ``seg`` knob shrinks
the segment length (default `SEG`) so tests can prove the overlay rail
is bitwise invariant to where segment boundaries fall.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.jax_engine import (BIG, BUSY, CI_DONE, CI_EXH,
                                   CI_FAILED, CI_ITERS, CI_NEXT,
                                   CI_OVF, CI_RETRY, CI_SHED, CI_STALL,
                                   CI_TERM, CI_TMO, CI_TRIPS, COLD,
                                   HIST_BINS, I32_MAX, IDLE,
                                   LOOP_COMPILER_OPTIONS, NCF, NCI,
                                   SEG, EngineCtx, _fold_event, _gidx,
                                   ensure_x64, hist_quantile)
from repro.core.resilience import backoff_jax
from repro.cluster.routers import BreakerRouter, ClusterView

ensure_x64()

# state keys sliced to the event's node before kernel hooks run (the
# timer-rail keys and the kernel's extra_state keys are appended per
# call)
_NODAL = ("slot_fn", "slot_state", "slot_ready", "slot_req",
          "slot_used", "slot_seq", "q_len", "q_head_rid", "q_tail_rid",
          "q_tot", "est_sum", "est_n", "node_gn", "node_gsum")
_NODAL_TMR = ("arr_cnt", "tmr_seq", "tmr_rid", "tmr_next", "rearm_t",
              "rearm_rid", "la_rid")
_NODAL_PEND = ("pend_head", "pend_tail", "pend_len")


def _sched_delay(t, dt, dv, dp):
    """Piecewise-constant `DelaySchedule` lookup, elementwise over
    ``t``: value of the last step at or before ``t`` (mod ``dp`` when
    periodic). ``dt``/``dv`` are the BIG-padded step times / values
    with shape ``t.shape + (D,)``; ``dp`` has ``t.shape`` (0 = not
    periodic). ``dt[..., 0] == 0`` (spec-validated), so the index is
    always in range. Every call site — candidate times, router
    ``delay_now``, landing times, the response convention — funnels
    through this one function, so the same (t, node) pair can never
    produce two different floats."""
    per = jnp.where(dp > 0, dp, 1.0)
    tt = jnp.where(dp > 0, jnp.mod(t, per), t)
    ix = jnp.clip(jnp.sum(tt[..., None] >= dt, axis=-1) - 1,
                  0, dt.shape[-1] - 1)
    return jnp.take_along_axis(dv, ix[..., None], axis=-1)[..., 0]


class ClusterNodeCtx(EngineCtx):
    """Single-node view ctx over one node of a cluster lane.

    Reads go straight to the full trace operands (the cluster loop is
    single-window); the ctx-dispatched queue/timer ops implement the
    segment-overlay link-rail discipline — writes park per-event
    registers (``lw_*`` link writes, ``qw_*`` queue-cursor writes,
    ``pp_*``/``tp_*`` deferred reads) that the engine stages into the
    overlay, resolves via the in-body chase pass, and applies
    write-first at the top of the next step — and the estimator
    fallback chain uses the
    node-local globals instead of the lane counters. ``delay`` (only
    under ``has_delay``) shifts `arrival_at` to the node-local clock so
    the response fold measures from the delayed arrival.
    """

    def __init__(self, *, fn_id2, arrival2, exec2, cold2, evict2, tix,
                 cap_mask, beta, prior, threshold, k, n, f, c, q,
                 stream, tl_bins, tl_bucket, node, delay=None,
                 delay_sched=None, deadlines=None, direct_links=False,
                 seg_n=SEG):
        super().__init__(
            fn_id2=fn_id2, arrival2=arrival2, exec2=exec2, cold2=cold2,
            evict2=evict2, pos_rids2=None, pos_off2=None,
            slabs=(None,) * 7, win_base=0, win_w=n, tix=tix,
            cap_mask=cap_mask, beta=beta, prior=prior,
            threshold=threshold, k=k, n=n, f=f, c=c, q=q, stream=stream,
            tl_bins=tl_bins, tl_bucket=tl_bucket, deadlines=deadlines)
        self._node = jnp.asarray(node, jnp.int32)
        self._delay = delay
        self._dsched = delay_sched  # (dt_row, dv_row, dp) of the node
        self._direct = direct_links  # churn: rail writes, no overlays
        self.seg_n = seg_n

    def arrival_at(self, rid):
        a = super().arrival_at(rid)
        if self._delay is not None:
            return a + self._delay
        if self._dsched is not None:
            dt, dv, dp = self._dsched
            return a + _sched_delay(a, dt, dv, dp)
        return a

    # ------------------------------------------------ estimator override
    def est_means(self, s):
        counts = s["est_n"].astype(jnp.float64)
        gn = s["node_gn"]
        g = jnp.where(gn > 0,
                      s["node_gsum"]
                      / jnp.maximum(gn.astype(jnp.float64), 1),
                      self.prior)
        return jnp.where(s["est_n"] > 0,
                         s["est_sum"] / jnp.maximum(counts, 1), g)

    # ------------------------------------------ overlay-rail queue ops
    # (q_head is inherited: the head cache works the same way)
    def q_push(self, s, fn, rid, on):
        if self._direct:
            return self._q_push_direct(s, fn, rid, on)
        fc = jnp.clip(fn, 0, self.F - 1)
        was_empty = s["q_len"][fc] == 0
        full = s["q_len"][fc] >= self.Q
        do = on & ~full
        rid32 = jnp.asarray(rid, jnp.int32)
        tail = s["q_tail_rid"][fc]
        link = do & ~was_empty
        kf = self._node * self.F + fc
        s = dict(s)
        # the view-row updates keep intra-event reads consistent; the
        # carried (L, K, F) queue arrays are updated via the qw_*
        # write registers instead (scalar scatters in step() — a row
        # commit of these arrays defeats XLA's in-place rewrite and
        # costs two full copies per event)
        s["q_head_rid"] = s["q_head_rid"].at[
            _gidx(do & was_empty, fn, self.F)].set(rid32, mode="drop")
        s["qw_head_pos"] = jnp.where(do & was_empty, kf,
                                     s["qw_head_pos"])
        s["qw_head_val"] = jnp.where(do & was_empty, rid32,
                                     s["qw_head_val"])
        # nxt[tail] = rid, staged via the per-event link register
        s["lw_q_pos"] = jnp.where(link, tail, s["lw_q_pos"])
        s["lw_q_val"] = jnp.where(link, rid32, s["lw_q_val"])
        s["q_tail_rid"] = s["q_tail_rid"].at[
            _gidx(do, fn, self.F)].set(rid32, mode="drop")
        s["qw_tail_pos"] = jnp.where(do, kf, s["qw_tail_pos"])
        s["qw_tail_val"] = jnp.where(do, rid32, s["qw_tail_val"])
        s["q_len"] = s["q_len"].at[_gidx(do, fn, self.F)].add(
            1, mode="drop")
        s["qw_len_pos"] = jnp.where(do, kf, s["qw_len_pos"])
        s["qw_len_delta"] = jnp.where(do, jnp.int32(1),
                                      s["qw_len_delta"])
        s["q_tot"] = s["q_tot"] + do.astype(jnp.int32)
        s["ci"] = s["ci"].at[CI_OVF].add((on & full).astype(jnp.int32))
        return s, do

    def _q_push_direct(self, s, fn, rid, on):
        # churn mode: a drained rid re-enters a queue, so links are no
        # longer write-once — write the nxt rail per event and let the
        # cursor trio ride the nodal row commit
        fc = jnp.clip(fn, 0, self.F - 1)
        was_empty = s["q_len"][fc] == 0
        full = s["q_len"][fc] >= self.Q
        do = on & ~full
        rid32 = jnp.asarray(rid, jnp.int32)
        tail = s["q_tail_rid"][fc]
        s = dict(s)
        s["q_head_rid"] = s["q_head_rid"].at[
            _gidx(do & was_empty, fn, self.F)].set(rid32, mode="drop")
        s["nxt"] = s["nxt"].at[
            _gidx(do & ~was_empty, tail, self.N)].set(rid32,
                                                      mode="drop")
        s["q_tail_rid"] = s["q_tail_rid"].at[
            _gidx(do, fn, self.F)].set(rid32, mode="drop")
        s["q_len"] = s["q_len"].at[_gidx(do, fn, self.F)].add(
            1, mode="drop")
        s["q_tot"] = s["q_tot"] + do.astype(jnp.int32)
        s["ci"] = s["ci"].at[CI_OVF].add((on & full).astype(jnp.int32))
        return s, do

    def q_consume_direct(self, s, fn, on):
        # no positional cursor to advance: a directly dispatched
        # arrival simply never enters the link chain
        return s

    def q_pop(self, s, fn, on):
        if self._direct:
            return self._q_pop_direct(s, fn, on)
        fc = jnp.clip(fn, 0, self.F - 1)
        rid = s["q_head_rid"][fc]
        defer = on & (s["q_len"][fc] > 1)
        fi = _gidx(on, fn, self.F)
        kf = self._node * self.F + fc
        s = dict(s)
        # the successor lookup is deferred: the chase pass rewrites
        # the parked head register from the overlay/rail before the
        # registers are applied to the carried queue arrays
        s["q_head_rid"] = s["q_head_rid"].at[fi].set(-1, mode="drop")
        s["qw_head_pos"] = jnp.where(on, kf, s["qw_head_pos"])
        s["qw_head_val"] = jnp.where(on, jnp.int32(-1),
                                     s["qw_head_val"])
        s["q_len"] = s["q_len"].at[fi].add(-1, mode="drop")
        s["qw_len_pos"] = jnp.where(on, kf, s["qw_len_pos"])
        s["qw_len_delta"] = jnp.where(on, jnp.int32(-1),
                                      s["qw_len_delta"])
        s["q_tot"] = s["q_tot"] - on.astype(jnp.int32)
        s["pp_kf"] = jnp.where(defer, kf, s["pp_kf"])
        s["pp_rid"] = jnp.where(defer, rid, s["pp_rid"])
        return s, rid

    def _q_pop_direct(self, s, fn, on):
        # churn mode: the successor is read straight off the rail (it
        # was written directly at push time, so it is always current)
        fc = jnp.clip(fn, 0, self.F - 1)
        rid = s["q_head_rid"][fc]
        succ = jnp.where(s["q_len"][fc] > 1,
                         s["nxt"][jnp.clip(rid, 0, self.N - 1)],
                         jnp.int32(-1))
        fi = _gidx(on, fn, self.F)
        s = dict(s)
        s["q_head_rid"] = s["q_head_rid"].at[fi].set(succ, mode="drop")
        s["q_len"] = s["q_len"].at[fi].add(-1, mode="drop")
        s["q_tot"] = s["q_tot"] - on.astype(jnp.int32)
        return s, rid

    # -------------------------------------------- rid-chain timer rail
    def arm_timer(self, s, fn, rid, t, pushed, on):
        fc = jnp.clip(fn, 0, self.F - 1)
        rail_head = s["tmr_seq"][fc] == s["arr_cnt"][fc] - 1
        rid32 = jnp.asarray(rid, jnp.int32)
        head_arm = on & rail_head & pushed
        hi = _gidx(head_arm, fn, self.F)
        s = dict(s)
        s["tmr_rid"] = s["tmr_rid"].at[hi].set(rid32, mode="drop")
        s["tmr_next"] = s["tmr_next"].at[hi].set(
            t + self.threshold, mode="drop")
        s["tmr_seq"] = s["tmr_seq"].at[
            _gidx(on & rail_head & ~pushed, fn, self.F)].add(
            1, mode="drop")
        return s


class ClusterResilCtx(ClusterNodeCtx):
    """Cluster node ctx under the resilience layer (fail_prob /
    timeouts / retries / shedding — see `repro.core.jax_engine
    .ResilCtx`, whose outcome-operand reads and shed-mode queue push
    this mirrors on the cluster's direct-link queue layout). Retries
    re-enqueue old rids, so the engine always runs in direct-link mode
    (``direct_links=True``) when resilience is on."""

    def __init__(self, *, nfail2, tmo2, key2, resil, **kw):
        super().__init__(**kw)
        self._nf = nfail2.reshape(-1)
        self._tm = tmo2.reshape(-1)
        self._ky = key2.reshape(-1)
        self.resil = resil  # (max_attempts, shed_mode, base, cap,
        self.has_resil = True            # jitter, fail_seed) — static
        self.defer_completion = True     # completion on success only

    def nfail_at(self, rid):
        return self._nf[self._b_n + jnp.clip(rid, 0, self.N - 1)]

    def tmo_at(self, rid):
        return self._tm[self._b_n + jnp.clip(rid, 0, self.N - 1)]

    def key_at(self, rid):
        return self._ky[self._b_n + jnp.clip(rid, 0, self.N - 1)]

    def _q_push_direct(self, s, fn, rid, on):
        # direct-link append with the admission-control modes: a push
        # onto a full backlog drops-and-counts (``error``, the legacy
        # invalid-run behaviour), sheds the arriving request (``shed``
        # — terminal, never admitted) or evicts the queue head to
        # admit the newcomer (``shed_oldest``)
        fc = jnp.clip(fn, 0, self.F - 1)
        rid32 = jnp.asarray(rid, jnp.int32)
        len0 = s["q_len"][fc]
        full = len0 >= self.Q
        mode = self.resil[1]
        s = dict(s)
        if mode == 2:  # shed_oldest: head out (terminal), newcomer in
            evict = on & full
            h = s["q_head_rid"][fc]
            hsucc = s["nxt"][jnp.clip(h, 0, self.N - 1)]
            fi = _gidx(evict, fn, self.F)
            s["q_head_rid"] = s["q_head_rid"].at[fi].set(hsucc,
                                                         mode="drop")
            s["q_len"] = s["q_len"].at[fi].add(-1, mode="drop")
            ev_i = evict.astype(jnp.int32)
            s["q_tot"] = s["q_tot"] - ev_i
            s["ci"] = s["ci"].at[jnp.array([CI_SHED, CI_TERM])].add(
                jnp.stack([ev_i, ev_i]))
            do = on
            was_empty = (len0 - ev_i) == 0
        else:
            do = on & ~full
            was_empty = len0 == 0
            if mode == 1:  # shed the arriving request
                sh_i = (on & full).astype(jnp.int32)
                s["ci"] = s["ci"].at[jnp.array([CI_SHED, CI_TERM])].add(
                    jnp.stack([sh_i, sh_i]))
            else:
                s["ci"] = s["ci"].at[CI_OVF].add(
                    (on & full).astype(jnp.int32))
        tail = s["q_tail_rid"][fc]
        s["q_head_rid"] = s["q_head_rid"].at[
            _gidx(do & was_empty, fn, self.F)].set(rid32, mode="drop")
        s["nxt"] = s["nxt"].at[
            _gidx(do & ~was_empty, tail, self.N)].set(rid32,
                                                      mode="drop")
        s["q_tail_rid"] = s["q_tail_rid"].at[
            _gidx(do, fn, self.F)].set(rid32, mode="drop")
        s["q_len"] = s["q_len"].at[_gidx(do, fn, self.F)].add(
            1, mode="drop")
        s["q_tot"] = s["q_tot"] + do.astype(jnp.int32)
        return s, do


# ------------------------------------------------------------ event loop
@functools.partial(jax.jit,
                   static_argnames=("kernel", "router", "n_nodes",
                                    "n_fns", "capacity", "queue_cap",
                                    "seed", "stream", "tl_bins",
                                    "has_delay", "has_churn",
                                    "var_delay", "seg", "resil",
                                    "trace"))
def _simulate_cluster(fn_id, arrival, exec_time, t_cold, t_evict,
                      trace_ix, cap_mask, beta, prior, threshold,
                      delays, churn_t=None, dtimes=None, dvals=None,
                      dper=None, deadlines=None, rs_nfail=None,
                      rs_tmo=None, rs_key=None, *, kernel, router,
                      n_nodes, n_fns, capacity, queue_cap, seed=0,
                      stream=False, tl_bins=0, tl_bucket=60.0,
                      has_delay=False, has_churn=False,
                      var_delay=False, seg=0, resil=None,
                      trace=False):
    """K-node lane-batched cluster loop (see the module docstring).

    ``cap_mask`` is (L, K, C) — heterogeneous node capacities are
    per-node slot masks over the common C = max slots. ``delays`` is
    the (K,) per-node network delay operand, only read when the static
    ``has_delay`` flag is set (so zero-delay runs stay bitwise the
    single-node arithmetic). ``seg`` (static; 0 -> `SEG`) sets the
    overlay segment length and never changes results.

    PR 7 operands, each gated by its own static flag so every disabled
    combination keeps its previous jaxpr: ``churn_t`` (K, E) f64
    toggle times under ``has_churn`` (even index = node goes down, odd
    = up; BIG-padded with at least one all-BIG trailing column so the
    cursor can rest past the last real toggle); ``dtimes``/``dvals``
    (K, D) + ``dper`` (K,) `DelaySchedule` steps under ``var_delay``
    (implies ``has_delay``); ``deadlines`` (F,) per-function SLO
    deadlines (or None), folded into a (L, F) ``deadline_miss``
    output.

    Returns the single-node engine's output dict plus ``node_done``
    (L, K) and, in exact mode under delay without churn, ``node_of``
    (L, N), the per-request dispatching node."""
    L = trace_ix.shape[0]
    N = fn_id.shape[1]
    F, C, K, Q = n_fns, capacity, n_nodes, queue_cap
    KC = K * C
    KF = K * F
    SG = int(seg) if seg else SEG
    timers = kernel.has_timers
    has_resil = resil is not None
    has_breaker = isinstance(router, BreakerRouter)
    # retries re-enqueue old rids, which breaks the write-once link
    # invariant behind the segment overlays exactly like churn does —
    # both run the direct-link spelling (per-event rail writes)
    direct = has_churn or has_resil
    done_col = CI_TERM if has_resil else CI_DONE
    if timers and has_churn:
        raise ValueError("timer-rail kernels are not supported under "
                         "churn (rejected at the runner)")
    if timers and has_resil:
        raise ValueError("timer-rail kernels are not supported under "
                         "the resilience layer (rejected at the "
                         "runner)")
    if var_delay and not has_delay:
        raise ValueError("var_delay requires has_delay")

    fn_id = fn_id.astype(jnp.int32)
    arrival = arrival.astype(jnp.float64)
    exec_time = exec_time.astype(jnp.float64)
    t_cold = t_cold.astype(jnp.float64)
    t_evict = t_evict.astype(jnp.float64)
    trace_ix = trace_ix.astype(jnp.int32)
    prior = jnp.float64(prior)
    threshold = jnp.float64(threshold)
    tl_bucket = jnp.float64(tl_bucket)
    delays = jnp.asarray(delays, jnp.float64)
    if has_churn:
        churn_t = jnp.asarray(churn_t, jnp.float64)
        E = churn_t.shape[1]
        churn_offs = jnp.arange(K, dtype=jnp.int32) * E
    if var_delay:
        dtimes = jnp.asarray(dtimes, jnp.float64)
        dvals = jnp.asarray(dvals, jnp.float64)
        dper = jnp.asarray(dper, jnp.float64)
        dt_b = jnp.broadcast_to(dtimes[None], (L,) + dtimes.shape)
        dv_b = jnp.broadcast_to(dvals[None], (L,) + dvals.shape)
        dp_b = jnp.broadcast_to(dper[None], (L, K))
    if deadlines is not None:
        deadlines = jnp.asarray(deadlines, jnp.float64)
    if has_resil:
        max_att, shed_mode, rt_base, rt_cap, rt_jit, rt_seed = resil
        rs_nfail = jnp.asarray(rs_nfail, jnp.int32)
        rs_tmo = jnp.asarray(rs_tmo, jnp.bool_)
        rs_key = jnp.asarray(rs_key, jnp.int32)

    s = dict(
        slot_fn=jnp.full((L, K, C), -1, jnp.int32),
        slot_state=jnp.full((L, K, C), IDLE, jnp.int32),
        slot_ready=jnp.full((L, K, C), BIG, jnp.float64),
        slot_req=jnp.full((L, K, C), -1, jnp.int32),
        slot_used=jnp.zeros((L, K, C), jnp.float64),
        slot_seq=jnp.full((L, K, C), I32_MAX, jnp.int32),
        q_len=jnp.zeros((L, K, F), jnp.int32),
        q_head_rid=jnp.full((L, K, F), -1, jnp.int32),
        q_tail_rid=jnp.full((L, K, F), -1, jnp.int32),
        q_tot=jnp.zeros((L, K), jnp.int32),
        nxt=jnp.full((L, N), -1, jnp.int32),
        est_sum=jnp.zeros((L, K, F), jnp.float64),
        est_n=jnp.zeros((L, K, F), jnp.int32),
        node_gn=jnp.zeros((L, K), jnp.int32),
        node_gsum=jnp.zeros((L, K), jnp.float64),
        node_done=jnp.zeros((L, K), jnp.int32),
        ci=jnp.zeros((L, NCI), jnp.int32),
        cf=jnp.zeros((L, NCF), jnp.float64),
        hist=jnp.zeros((L, HIST_BINS), jnp.int32),
    )
    if not direct:
        # queue write registers, carried across steps: the previous
        # event's parked queue writes are applied at the *top* of the
        # next step (see step()), so within one step the queue arrays'
        # only direct user is the opening in-place scatter. In
        # direct-link mode (churn / resilience) the trio rides the
        # nodal row commit instead and links are written directly, so
        # neither register family exists.
        s["qw_len_pos"] = jnp.full((L,), -1, jnp.int32)
        s["qw_len_delta"] = jnp.zeros((L,), jnp.int32)
        s["qw_head_pos"] = jnp.full((L,), -1, jnp.int32)
        s["qw_head_val"] = jnp.zeros((L,), jnp.int32)
        s["qw_tail_pos"] = jnp.full((L,), -1, jnp.int32)
        s["qw_tail_val"] = jnp.zeros((L,), jnp.int32)
        s["ov_q_pos"] = jnp.full((L, SG), N, jnp.int32)
        s["ov_q_val"] = jnp.zeros((L, SG), jnp.int32)
    if has_churn:
        # availability cursor (even parity = up) + the park FIFO of
        # requests orphaned by node failures / all-down arrivals; the
        # chain rides the nxt rail, park_t is the head's eligibility
        # time (the whole FIFO drains at one instant whenever a node
        # is up, so one scalar per lane suffices — see NODE_DOWN)
        s["ch_ix"] = jnp.zeros((L, K), jnp.int32)
        s["park_head"] = jnp.full((L,), -1, jnp.int32)
        s["park_tail"] = jnp.full((L,), -1, jnp.int32)
        s["park_len"] = jnp.zeros((L,), jnp.int32)
        s["park_t"] = jnp.full((L,), BIG, jnp.float64)
    if direct and has_delay:
        # landing time of each in-flight request, written at send
        # time (an orphan's or retry's re-send samples the delay
        # then, so the raw-arrival closed form no longer applies)
        s["land_t"] = jnp.zeros((L, N), jnp.float64)
    if has_resil:
        # retry rail: one cluster-global FIFO per lane over the shared
        # nxt links (a rid is queued XOR running XOR in flight XOR
        # parked XOR awaiting retry XOR terminal), eligibility times
        # rt_t and the armed head fire time r_fire; att counts started
        # attempts per rid
        s["att"] = jnp.zeros((L, N), jnp.int32)
        s["rt_t"] = jnp.zeros((L, N), jnp.float64)
        s["r_head"] = jnp.full((L,), -1, jnp.int32)
        s["r_tail"] = jnp.full((L,), -1, jnp.int32)
        s["r_len"] = jnp.zeros((L,), jnp.int32)
        s["r_fire"] = jnp.full((L,), BIG, jnp.float64)
    if has_breaker:
        # per-node circuit-breaker state: tumbling-window completion /
        # failure counts and the reopen time (0 = closed, > t = open,
        # (0, t] = half-open probe pending); read by the router, and
        # updated at EXEC_DONE by the event's node — so the trio is
        # nodal state
        s["cbr_n"] = jnp.zeros((L, K), jnp.int32)
        s["cbr_f"] = jnp.zeros((L, K), jnp.int32)
        s["cbr_until"] = jnp.zeros((L, K), jnp.float64)
    if deadlines is not None:
        s["dl_miss"] = jnp.zeros((L, F), jnp.int32)
    if timers:
        s["arr_cnt"] = jnp.zeros((L, K, F), jnp.int32)
        s["tmr_seq"] = jnp.zeros((L, K, F), jnp.int32)
        s["tmr_rid"] = jnp.full((L, K, F), -1, jnp.int32)
        s["tmr_next"] = jnp.full((L, K, F), BIG, jnp.float64)
        s["rearm_t"] = jnp.full((L, K, F), BIG, jnp.float64)
        s["rearm_rid"] = jnp.full((L, K, F), -1, jnp.int32)
        s["la_rid"] = jnp.full((L, K, F), -1, jnp.int32)
        s["tnx"] = jnp.full((L, N), -1, jnp.int32)
        s["ov_t_pos"] = jnp.full((L, SG), N, jnp.int32)
        s["ov_t_val"] = jnp.zeros((L, SG), jnp.int32)
    if has_delay:
        s["pend_head"] = jnp.full((L, K), -1, jnp.int32)
        s["pend_tail"] = jnp.full((L, K), -1, jnp.int32)
        s["pend_len"] = jnp.zeros((L, K), jnp.int32)
        s["dnx"] = jnp.full((L, N), -1, jnp.int32)
        if not direct:
            s["ov_d_pos"] = jnp.full((L, SG), N, jnp.int32)
            s["ov_d_val"] = jnp.zeros((L, SG), jnp.int32)
    if not stream:
        s["start"] = jnp.full((L, N), -1.0, jnp.float64)
        s["completion"] = jnp.full((L, N), -1.0, jnp.float64)
        if not direct:
            # direct-link mode writes the per-request records directly
            # per event (ctx.direct_records) — no d_* overlays to stage
            s["d_rid"] = jnp.full((L, SG), N, jnp.int32)
            s["d_start"] = jnp.zeros((L, SG), jnp.float64)
            s["d_comp"] = jnp.zeros((L, SG), jnp.float64)
            if has_delay:
                s["d_node"] = jnp.zeros((L, SG), jnp.int32)
                s["node_of"] = jnp.zeros((L, N), jnp.int32)
    if tl_bins:
        s["tl_cnt"] = jnp.zeros((L, tl_bins), jnp.int32)
        s["tl_resp"] = jnp.zeros((L, tl_bins), jnp.float64)
        s["tl_exec"] = jnp.zeros((L, tl_bins), jnp.float64)
    if trace:
        # event-trace segment overlay: one fixed-width record per
        # processed event, flushed to the host per segment — lane
        # global (rides gather/commit untouched), O(SG) carried state
        from repro.telemetry.rail import TR_RF, TR_RI
        s["tr_i"] = jnp.full((L, SG, TR_RI), -1, jnp.int32)
        s["tr_f"] = jnp.zeros((L, SG, TR_RF), jnp.float64)
    extra = kernel.extra_state(L, C, F)
    nodal = _NODAL + (_NODAL_TMR if timers else ()) \
        + (_NODAL_PEND if has_delay else ()) \
        + (("ch_ix",) if has_churn else ()) \
        + (("cbr_n", "cbr_f", "cbr_until") if has_breaker else ()) \
        + tuple(extra)
    for kk, v in extra.items():
        # one copy of the kernel's per-server state per node
        s[kk] = jnp.repeat(v[:, None, ...], K, axis=1)
    if has_churn:
        # pristine per-node kernel rows, for the NODE_DOWN reset
        extra0 = {kk: v[0]
                  for kk, v in kernel.extra_state(1, C, F).items()}

    max_iters = 256 * N + 4096
    if has_churn:
        # every toggle can orphan up to a nodeful of requests, each
        # re-routed and re-executed — a generous stall guard, not a
        # budget
        max_iters += (4 * N + 64) * K * E
    if has_resil:
        # each rid can run (and re-enter) up to max_attempts times
        max_iters *= max_att
    n_slot = 2 * KC
    tmr_base = n_slot
    pend_base = n_slot + (2 * KF if timers else 0)
    orph_base = pend_base + (K if has_delay else 0)
    churn_base = orph_base + (1 if has_churn else 0)
    rtry_base = churn_base + (K if has_churn else 0)
    n_cand = rtry_base + (1 if has_resil else 0) + 1
    lanes = jnp.arange(L, dtype=jnp.int32)
    lane_iota = lanes[:, None]
    t_cold_l = t_cold[trace_ix]
    t_evict_l = t_evict[trace_ix]
    # flattened-view reads with per-lane bases: (T, N) two-dim gathers
    # only hit the fast XLA:CPU path at T == 1 (see EngineCtx)
    arr_flat = arrival.reshape(-1)
    fn_flat = fn_id.reshape(-1)
    base_n = trace_ix * N

    # node view/commit live OUTSIDE the vmapped body: a vmapped
    # dynamic_index over the (L, K, F) nodal arrays is a
    # batched-operand gather — the generic XLA:CPU path, measured
    # O(K*F) per event — whereas one lane-stacked take_along_axis /
    # row scatter per array rides the fast gather/scatter path
    # the queue trio's carried writes are at most one scalar position
    # per array per event, so they skip the row commit — XLA's
    # copy-insertion cannot prove the fused row arithmetic of these
    # rows in-place and charges two full (L, K, F) copies per event —
    # and ride the qw_* write registers instead (scalar drop-scatters
    # in step(); the gathered view row stays for kernel full-row reads)
    _Q_TRIO = ("q_len", "q_head_rid", "q_tail_rid")
    # under churn / resilience the write registers don't exist
    # (direct-link mode), so the trio commits like every other nodal
    # array
    nodal_commit = (nodal if direct else
                    tuple(kk for kk in nodal if kk not in _Q_TRIO))

    def gather_nodal(s, k_ev):
        v = dict(s)
        for key in nodal:
            a = s[key]
            idx = k_ev.reshape((L,) + (1,) * (a.ndim - 1))
            v[key] = jnp.take_along_axis(a, idx, axis=1)[:, 0]
        return v

    def commit_nodal(s, v, k_ev):
        out = dict(v)
        for key in nodal_commit:
            out[key] = s[key].at[lanes, k_ev].set(v[key])
        for key in nodal:
            if key not in nodal_commit:
                out[key] = s[key]
        return out

    def make_ctx(tix, cold_l, evict_l, capm_node, beta, k_step, node):
        # response convention: under churn / resilience requests are
        # measured from the *raw* arrival (user-perceived — an
        # orphaned or retried request may traverse several nodes and
        # attempts); otherwise the node-local clock (+const delay, or
        # +schedule-at-raw-arrival) is preserved
        if direct:
            dly, dsc = None, None
        elif var_delay:
            kc = jnp.clip(node, 0, K - 1)
            dly, dsc = None, (dtimes[kc], dvals[kc], dper[kc])
        elif has_delay:
            dly, dsc = delays[node], None
        else:
            dly, dsc = None, None
        kw = dict(
            fn_id2=fn_id, arrival2=arrival, exec2=exec_time,
            cold2=cold_l, evict2=evict_l, tix=tix, cap_mask=capm_node,
            beta=beta, prior=prior, threshold=threshold, k=k_step,
            n=N, f=F, c=C, q=Q, stream=stream, tl_bins=tl_bins,
            tl_bucket=tl_bucket, node=node, delay=dly, delay_sched=dsc,
            deadlines=deadlines, direct_links=direct, seg_n=SG)
        ctx = (ClusterResilCtx(nfail2=rs_nfail, tmo2=rs_tmo,
                               key2=rs_key, resil=resil, **kw)
               if has_resil else ClusterNodeCtx(**kw))
        if direct:
            # fold at EXEC_DONE (a drained / failed attempt's dispatch
            # record must not count) and write exact-mode records per
            # event
            ctx.fold_at_dispatch = False
            ctx.direct_records = True
        return ctx

    def pick_events(s):
        na = s["ci"][:, CI_NEXT]
        r = jnp.minimum(na, N - 1)
        t_arr = jnp.where(na < N, arr_flat[base_n + r], BIG)
        ready = jnp.where(cap_mask, s["slot_ready"], BIG
                          ).reshape(L, KC)
        st = s["slot_state"].reshape(L, KC)
        blocks = [jnp.where(st == BUSY, ready, BIG),
                  jnp.where(st == COLD, ready, BIG)]
        if timers:
            blocks += [s["tmr_next"].reshape(L, KF),
                       s["rearm_t"].reshape(L, KF)]
        if has_delay:
            ph = jnp.clip(s["pend_head"], 0, N - 1)
            if direct:
                land = jnp.take_along_axis(s["land_t"], ph, axis=1)
            elif var_delay:
                arr_ph = arr_flat[base_n[:, None] + ph]
                land = arr_ph + _sched_delay(arr_ph, dt_b, dv_b, dp_b)
            else:
                land = arr_flat[base_n[:, None] + ph] + delays[None, :]
            blocks.append(jnp.where(s["pend_len"] > 0, land, BIG))
        if has_churn:
            # orphan (one column): the park head re-routes as soon as
            # any node is up; churn (K columns): each node's next
            # toggle time off the BIG-padded cursor
            up = (s["ch_ix"] & 1) == 0
            blocks.append(jnp.where((s["park_len"] > 0)
                                    & up.any(axis=1),
                                    s["park_t"], BIG)[:, None])
            cix = jnp.clip(s["ch_ix"], 0, E - 1)
            blocks.append(churn_t.reshape(-1)[churn_offs[None, :]
                                              + cix])
        if has_resil:
            # armed retry-rail head (BIG while the rail is empty)
            blocks.append(s["r_fire"][:, None])
        blocks.append(t_arr[:, None])
        cand = jnp.concatenate(blocks, axis=1)
        ei = jnp.argmin(cand, axis=1).astype(jnp.int32)
        t_ev = jnp.take_along_axis(cand, ei[:, None], axis=1)[:, 0]
        return ei, t_ev, t_arr

    def pick_one(q_len, q_tot, slot_fn, slot_state, capm, est_sum,
                 est_n, node_gn, node_gsum, cold_l, up, delay_now, brk,
                 j, rid, t):
        g = ClusterView(q_len=q_len, q_tot=q_tot, slot_fn=slot_fn,
                        slot_state=slot_state, cap_mask=capm,
                        est_sum=est_sum, est_n=est_n, node_gn=node_gn,
                        node_gsum=node_gsum, t_cold=cold_l,
                        prior=prior, n_nodes=K, seed=seed,
                        up=up, delay_now=delay_now, brk_until=brk)
        return router.pick(g, j, rid, t)

    # ``up``/``delay_now``/``brk_until`` stay python-None (an empty
    # pytree — any in_axes is legal) when their feature is off, so the
    # no-churn / const-delay / no-breaker jaxprs are unchanged; a
    # const (K,) delay_now is shared across lanes (in_axes None), a
    # scheduled one is (L, K)
    pick_lanes = jax.vmap(
        pick_one, in_axes=(0,) * 10 + (0 if has_churn else None,
                                       0 if var_delay else None,
                                       0 if has_breaker else None)
        + (0, 0, 0))

    def lane_step(k_step, s, tix, cold_l, evict_l, capm, beta, ei,
                  t_ev, t_arr, node):
        # ``s`` arrives with the nodal keys already sliced to
        # ``node``'s row (gather_nodal); ``capm`` is that node's (C,)
        # slot mask
        ci = s["ci"]
        if trace:
            tr_q0 = s["q_tot"]  # event node's queue total, pre-event
        active = (ci[done_col] < N) & (ci[CI_STALL] == 0)
        na = ci[CI_NEXT]
        live = active & (t_ev < BIG)
        # per-event registers: dispatch record (consumed by
        # _fold_event), link writes (staged into the overlays) and
        # deferred link reads (resolved by the chase pass) — in
        # direct-link mode the overlay/register families don't exist
        # (links are written directly)
        s = dict(s)
        if has_churn:
            anyup = s.pop("anyup")
        s["ev_rid"] = jnp.int32(-1)
        s["ev_comp"] = jnp.float64(0.0)
        s["ev_exec"] = jnp.float64(0.0)
        if has_resil:
            # per-lane success flag of this event (popped by step() to
            # gate the node_done tally to successful completions)
            s["rs_ok"] = jnp.bool_(False)
        if not direct:
            s["lw_q_pos"] = jnp.int32(-1)
            s["lw_q_val"] = jnp.int32(0)
            s["pp_kf"] = jnp.int32(-1)
            s["pp_rid"] = jnp.int32(-1)
            # queue write registers: each event performs at most one
            # push or one pop (the kernels' hooks are push-xor-pop and
            # the event classes are mutually exclusive), so one scalar
            # write per queue array covers every case
            s["qw_len_pos"] = jnp.int32(-1)
            s["qw_len_delta"] = jnp.int32(0)
            s["qw_head_pos"] = jnp.int32(-1)
            s["qw_head_val"] = jnp.int32(0)
            s["qw_tail_pos"] = jnp.int32(-1)
            s["qw_tail_val"] = jnp.int32(0)
        if timers:
            s["lw_t_pos"] = jnp.int32(-1)
            s["lw_t_val"] = jnp.int32(0)
            s["tp_kf"] = jnp.int32(-1)
            s["tp_rid"] = jnp.int32(-1)
        if has_delay and not direct:
            s["lw_d_pos"] = jnp.int32(-1)
            s["lw_d_val"] = jnp.int32(0)
            s["dp_k"] = jnp.int32(-1)
            s["dp_rid"] = jnp.int32(-1)

        # ------------------------------------------ event class decode
        ev_slot = live & (ei < n_slot)
        is_cold = ei >= KC
        sflat = jnp.clip(jnp.where(is_cold, ei - KC, ei), 0, KC - 1)
        slot = sflat % C
        ev_arr = live & (ei == n_cand - 1)
        if has_churn:
            ev_orph = live & (ei == orph_base)
            ev_churn = live & (ei >= churn_base) & (ei < churn_base + K)
        ev_timer = jnp.bool_(False)
        if timers:
            fire_orig = live & (ei >= tmr_base) & (ei < tmr_base + KF)
            fire_re = (live & (ei >= tmr_base + KF)
                       & (ei < tmr_base + 2 * KF))
            ev_timer = fire_orig | fire_re
            kf_t = jnp.clip(jnp.where(fire_orig, ei - tmr_base,
                                      ei - tmr_base - KF), 0, KF - 1)
            f_t = kf_t % F
        if has_delay:
            ev_pend = live & (ei >= pend_base) & (ei < pend_base + K)

        rid_a = jnp.minimum(na, N - 1)
        ctx = make_ctx(tix, cold_l, evict_l, capm, beta, k_step, node)
        v = s

        # ------------------------------------------------- slot event
        cold_on = ev_slot & is_cold
        exec_on = ev_slot & ~is_cold
        rid_done = v["slot_req"][slot]
        j_done = v["slot_fn"][slot]
        e_done = ctx.exec_at(rid_done)
        si = _gidx(ev_slot, slot, C)
        ji = _gidx(exec_on, j_done, F)
        exec_i = exec_on.astype(jnp.int32)
        v = dict(v)
        v["slot_state"] = v["slot_state"].at[si].set(IDLE, mode="drop")
        v["slot_ready"] = v["slot_ready"].at[si].set(BIG, mode="drop")
        v["slot_req"] = v["slot_req"].at[si].set(-1, mode="drop")
        # the node's estimator sees the completion before its policy
        # reacts, exactly like the single-node engine
        v["est_sum"] = v["est_sum"].at[ji].add(e_done, mode="drop")
        v["est_n"] = v["est_n"].at[ji].add(1, mode="drop")
        v["node_gsum"] = v["node_gsum"] + jnp.where(exec_on, e_done,
                                                    0.0)
        v["node_gn"] = v["node_gn"] + exec_i
        if not has_resil:
            v["ci"] = v["ci"].at[CI_DONE].add(exec_i)
            fold_on = exec_on
        else:
            # outcome of this attempt: the estimator observed it above
            # (every attempt burns real slot time); success/failure is
            # the pre-planned attempt test (see core/resilience.py)
            att_d = v["att"][jnp.clip(rid_done, 0, N - 1)]
            nf_d = ctx.nfail_at(rid_done)
            ok_d = exec_on & (att_d > nf_d)
            fail_d = exec_on & ~ok_d
            exh_d = fail_d & (att_d >= max_att)
            retry_d = fail_d & ~exh_d
            tmo_d = ctx.tmo_at(rid_done)
            ok_i = ok_d.astype(jnp.int32)
            v["ci"] = v["ci"].at[jnp.array(
                [CI_DONE, CI_TERM, CI_FAILED, CI_TMO, CI_RETRY,
                 CI_EXH])].add(jnp.stack(
                [ok_i, ok_i + exh_d.astype(jnp.int32),
                 (fail_d & ~tmo_d).astype(jnp.int32),
                 (fail_d & tmo_d).astype(jnp.int32),
                 retry_d.astype(jnp.int32),
                 exh_d.astype(jnp.int32)]))
            v["rs_ok"] = ok_d
            fold_on = ok_d
        if direct:
            # fold at EXEC_DONE: a drained / failed attempt never
            # folds, so exactly the surviving run of each request
            # counts (response = completion - raw arrival via the ctx)
            v["ev_rid"] = jnp.where(fold_on,
                                    jnp.asarray(rid_done, jnp.int32),
                                    v["ev_rid"])
            v["ev_comp"] = jnp.where(fold_on, t_ev, v["ev_comp"])
            v["ev_exec"] = jnp.where(fold_on, e_done, v["ev_exec"])
        if has_resil:
            if not stream:
                # deferred exact-mode record: an exhausted / shed rid
                # must keep completion == -1
                v["completion"] = v["completion"].at[
                    _gidx(ok_d, rid_done, N)].set(t_ev, mode="drop")
            # a retrying rid re-enters after its backoff; the rail is
            # FIFO so only an empty rail arms the fire time here
            key_d = ctx.key_at(rid_done)
            elig = t_ev + backoff_jax(att_d, key_d, rt_base, rt_cap,
                                      rt_jit, rt_seed)
            rd32 = jnp.asarray(rid_done, jnp.int32)
            v["rt_t"] = v["rt_t"].at[
                _gidx(retry_d, rid_done, N)].set(elig, mode="drop")
            r_empty = v["r_len"] == 0
            v["nxt"] = v["nxt"].at[
                _gidx(retry_d & ~r_empty, v["r_tail"], N)].set(
                rd32, mode="drop")
            v["r_head"] = jnp.where(retry_d & r_empty, rd32,
                                    v["r_head"])
            v["r_tail"] = jnp.where(retry_d, rd32, v["r_tail"])
            v["r_fire"] = jnp.where(retry_d & r_empty, elig,
                                    v["r_fire"])
            v["r_len"] = v["r_len"] + retry_d.astype(jnp.int32)
        if has_breaker:
            # circuit-breaker bookkeeping at the event's node: closed
            # (until == 0) counts the attempt into the tumbling window
            # and trips when a full window's failures reach trip_at;
            # half-open (0 < until <= t) lets the first completed
            # attempt decide — success closes, failure re-trips; open
            # (until > t) completions are pre-trip stragglers, ignored
            fail_ev = fail_d if has_resil else jnp.bool_(False)
            until0 = v["cbr_until"]
            half = exec_on & (until0 > 0.0) & (until0 <= t_ev)
            closed = exec_on & (until0 == 0.0)
            n1 = v["cbr_n"] + closed.astype(jnp.int32)
            f1 = v["cbr_f"] + (closed & fail_ev).astype(jnp.int32)
            boundary = closed & (n1 >= router.volume)
            trip = (boundary & (f1 >= router.trip_at)) | (half
                                                          & fail_ev)
            v["cbr_until"] = jnp.where(
                trip, t_ev + router.cooldown,
                jnp.where(half, 0.0, until0))
            reset = boundary | half
            v["cbr_n"] = jnp.where(reset, 0, n1)
            v["cbr_f"] = jnp.where(reset, 0, f1)
            v["ci"] = v["ci"].at[CI_TRIPS].add(trip.astype(jnp.int32))
        v = kernel.on_cold_done(ctx, v, slot, t_ev, cold_on)
        v = kernel.on_exec_done(ctx, v, slot, rid_done, t_ev,
                                exec_on)

        # ------------------------------------------------- timer event
        if timers:
            rid_o = v["tmr_rid"][f_t]
            seq_o = v["tmr_seq"][f_t]
            more = seq_o + 1 < v["arr_cnt"][f_t]
            oi = _gidx(fire_orig, f_t, F)
            rid_r = v["rearm_rid"][f_t]
            v = dict(v)
            v["tmr_seq"] = v["tmr_seq"].at[oi].add(1, mode="drop")
            # placeholder; the chase pass installs the chained
            # successor and its fire time before the next pick
            v["tmr_rid"] = v["tmr_rid"].at[oi].set(-1, mode="drop")
            v["tmr_next"] = v["tmr_next"].at[oi].set(BIG, mode="drop")
            v["rearm_t"] = v["rearm_t"].at[
                _gidx(fire_re, f_t, F)].set(BIG, mode="drop")
            chase = fire_orig & more
            v["tp_kf"] = jnp.where(chase, node * F + f_t, v["tp_kf"])
            v["tp_rid"] = jnp.where(chase, rid_o, v["tp_rid"])
            rid_t = jnp.where(fire_orig, rid_o, rid_r)
            v = kernel.on_timer(ctx, v, rid_t, t_ev, ev_timer)

        # ------------------------------------------ churn toggle event
        if has_churn:
            up0 = (v["ch_ix"] & 1) == 0  # pre-toggle parity
            ev_down = ev_churn & up0
            ev_up = ev_churn & ~up0
            v = dict(v)
            v["ch_ix"] = v["ch_ix"] + ev_churn.astype(jnp.int32)
            # ---- NODE_DOWN: drain the node onto the park FIFO.
            # Busy-slot requests first, ascending rid (an engine-
            # independent order the reference mirrors), then the
            # per-fn queues fn-major — all as O(C + F) chain splices
            # on the nxt rail. The park FIFO is provably empty here:
            # the toggling node was up, so any parked head (park_t <=
            # t_ev, orphan class < CHURN) already re-routed.
            busy_m = (v["slot_state"] == BUSY) & capm
            rids_b = jnp.sort(jnp.where(busy_m & ev_down,
                                        v["slot_req"], I32_MAX))
            valid_b = rids_b < I32_MAX
            n_busy = valid_b.sum().astype(jnp.int32)
            succ_b = jnp.concatenate(
                [rids_b[1:], jnp.array([I32_MAX], jnp.int32)])
            link_b = ev_down & valid_b & (succ_b < I32_MAX)
            v["nxt"] = v["nxt"].at[_gidx(link_b, rids_b, N)].set(
                succ_b, mode="drop")
            if has_resil:
                # a drained attempt never completes, so it must not
                # consume the rid's retry budget (the reference never
                # counts it: att increments at dispatch here but at
                # EXEC_DONE there, and a drained run reaches neither)
                v["att"] = v["att"].at[
                    _gidx(ev_down & valid_b, rids_b, N)].add(
                    -1, mode="drop")
            # queue chains: prev[f] = tail of the last nonempty fn
            # before f (exclusive cummax of nonempty fn ids), else the
            # last busy rid
            nonempty = v["q_len"] > 0
            idxf = jnp.arange(F, dtype=jnp.int32)
            cmax = lax.associative_scan(
                jnp.maximum, jnp.where(nonempty, idxf, -1))
            lnb = jnp.concatenate(
                [jnp.array([-1], jnp.int32), cmax[:-1]])
            busy_last = jnp.where(
                n_busy > 0, rids_b[jnp.clip(n_busy - 1, 0, C - 1)],
                jnp.int32(-1))
            prev = jnp.where(lnb >= 0,
                             v["q_tail_rid"][jnp.clip(lnb, 0, F - 1)],
                             busy_last)
            heads = v["q_head_rid"]
            v["nxt"] = v["nxt"].at[
                _gidx(ev_down & nonempty & (prev >= 0), prev, N)].set(
                heads, mode="drop")
            has_q = nonempty.any()
            first_ne = jnp.clip(jnp.argmax(nonempty), 0, F - 1)
            d_head = jnp.where(
                n_busy > 0, rids_b[0],
                jnp.where(has_q, heads[first_ne], jnp.int32(-1)))
            d_tail = jnp.where(
                has_q, v["q_tail_rid"][jnp.clip(cmax[-1], 0, F - 1)],
                busy_last)
            n_drain = n_busy + v["q_tot"]
            parked = ev_down & (n_drain > 0)
            v["park_head"] = jnp.where(parked, d_head, v["park_head"])
            v["park_tail"] = jnp.where(parked, d_tail, v["park_tail"])
            v["park_len"] = jnp.where(parked, n_drain, v["park_len"])
            v["park_t"] = jnp.where(parked, t_ev, v["park_t"])
            # reset the node: cold state dies with it, requests never
            # do; the estimator state deliberately survives (the node
            # remembers its execution history across an outage)
            v["slot_fn"] = jnp.where(ev_down, jnp.int32(-1),
                                     v["slot_fn"])
            v["slot_state"] = jnp.where(ev_down, jnp.int32(IDLE),
                                        v["slot_state"])
            v["slot_ready"] = jnp.where(ev_down, BIG, v["slot_ready"])
            v["slot_req"] = jnp.where(ev_down, jnp.int32(-1),
                                      v["slot_req"])
            v["slot_used"] = jnp.where(ev_down, 0.0, v["slot_used"])
            v["slot_seq"] = jnp.where(ev_down, jnp.int32(I32_MAX),
                                      v["slot_seq"])
            v["q_len"] = jnp.where(ev_down, jnp.int32(0), v["q_len"])
            v["q_head_rid"] = jnp.where(ev_down, jnp.int32(-1),
                                        v["q_head_rid"])
            v["q_tail_rid"] = jnp.where(ev_down, jnp.int32(-1),
                                        v["q_tail_rid"])
            v["q_tot"] = jnp.where(ev_down, jnp.int32(0), v["q_tot"])
            for kk in extra0:
                v[kk] = jnp.where(ev_down, extra0[kk], v[kk])
            # ---- NODE_UP: re-arm the park FIFO's eligibility clock
            # (requests stranded all-down become routable now)
            v["park_t"] = jnp.where(ev_up & (v["park_len"] > 0), t_ev,
                                    v["park_t"])

            # -------------------------------------- orphan re-route
            # (one park-head pop per event; ``node`` is the router's
            # pick for it, applied below exactly like an arrival)
            rid_o = v["park_head"]
            plen_pk = v["park_len"]
            succ_o = jnp.where(plen_pk > 1,
                               v["nxt"][jnp.clip(rid_o, 0, N - 1)],
                               jnp.int32(-1))
            v["park_head"] = jnp.where(ev_orph, succ_o, v["park_head"])
            v["park_tail"] = jnp.where(ev_orph & (plen_pk <= 1),
                                       jnp.int32(-1), v["park_tail"])
            v["park_len"] = v["park_len"] - ev_orph.astype(jnp.int32)
            node_up = (v["ch_ix"] & 1) == 0  # event node, post-toggle

        # ------------------------------------------------- retry event
        ev_rtry = jnp.bool_(False)
        if has_resil:
            # pop the retry-rail head; the successor is promoted but
            # may not fire before this pop (FIFO, no overtaking)
            ev_rtry = live & (ei == rtry_base)
            rlen0 = v["r_len"]
            rid_r = v["r_head"]
            succ_r = v["nxt"][jnp.clip(rid_r, 0, N - 1)]
            rid_r32 = jnp.asarray(rid_r, jnp.int32)
            v = dict(v)
            v["r_head"] = jnp.where(ev_rtry, succ_r, v["r_head"])
            v["r_tail"] = jnp.where(ev_rtry & (rlen0 <= 1),
                                    jnp.int32(-1), v["r_tail"])
            v["r_len"] = rlen0 - ev_rtry.astype(jnp.int32)
            nfire = jnp.maximum(
                v["rt_t"][jnp.clip(succ_r, 0, N - 1)], t_ev)
            v["r_fire"] = jnp.where(
                ev_rtry, jnp.where(rlen0 > 1, nfire, BIG),
                v["r_fire"])

        # ------------------------------------- node arrival / deferral
        if has_delay:
            # deferred-arrival pop: the event time is the node-local
            # (delayed) arrival; the FIFO successor resolves lazily
            # (overlay mode) or straight off the rail (direct mode).
            # A retry (like a raw arrival) only *sends* here — it
            # reaches its node via a later NODE_ARRIVAL pop
            plen0 = v["pend_len"]
            rid_p = v["pend_head"]
            v = dict(v)
            if direct:
                succ_p = jnp.where(plen0 > 1,
                                   v["dnx"][jnp.clip(rid_p, 0, N - 1)],
                                   jnp.int32(-1))
                v["pend_head"] = jnp.where(ev_pend, succ_p,
                                           v["pend_head"])
                v["pend_len"] = (v["pend_len"]
                                 - ev_pend.astype(jnp.int32))
                # a request landing on a node that died in flight
                # parks instead of arriving
                na_on = (ev_pend & node_up) if has_churn else ev_pend
            else:
                v["pend_head"] = jnp.where(ev_pend, jnp.int32(-1),
                                           v["pend_head"])
                v["pend_len"] = (v["pend_len"]
                                 - ev_pend.astype(jnp.int32))
                defer_p = ev_pend & (plen0 > 1)
                v["dp_k"] = jnp.where(defer_p, node, v["dp_k"])
                v["dp_rid"] = jnp.where(defer_p, rid_p, v["dp_rid"])
                na_on = ev_pend
            rid_na = jnp.where(ev_pend, rid_p, rid_a)
            t_na = t_ev
        else:
            rid_na = rid_a
            t_na = t_arr
            na_on = ev_arr
            if has_churn:
                # an orphan re-enters the node exactly like an
                # arrival, at the orphan event's time; all-down fresh
                # arrivals park instead
                rid_na = jnp.where(ev_orph, rid_o, rid_na)
                t_na = jnp.where(ev_orph, t_ev, t_na)
                na_on = (ev_arr & anyup) | ev_orph
            if has_resil:
                # a retry re-enters the router-picked node exactly
                # like an arrival, at its fire time (all-down retries
                # park instead, like fresh arrivals)
                rid_na = jnp.where(ev_rtry, rid_r, rid_na)
                t_na = jnp.where(ev_rtry, t_ev, t_na)
                na_on = na_on | ((ev_rtry & anyup) if has_churn
                                 else ev_rtry)
        rid_na32 = jnp.asarray(rid_na, jnp.int32)
        if timers:
            # chain every node arrival onto the (node, fn) timer rail
            j_na = ctx.fn_at(rid_na)
            prev_tail = v["la_rid"][jnp.clip(j_na, 0, F - 1)]
            chain = na_on & (prev_tail >= 0)
            v = dict(v)
            v["lw_t_pos"] = jnp.where(chain, prev_tail, v["lw_t_pos"])
            v["lw_t_val"] = jnp.where(chain, rid_na32, v["lw_t_val"])
            ni = _gidx(na_on, j_na, F)
            v["la_rid"] = v["la_rid"].at[ni].set(rid_na32, mode="drop")
            v["arr_cnt"] = v["arr_cnt"].at[ni].add(1, mode="drop")
        progress = ev_slot | ev_timer | ev_arr | ev_rtry
        if has_delay:
            progress = progress | ev_pend
        if has_churn:
            progress = progress | ev_orph | ev_churn
        v = dict(v)
        v["ci"] = v["ci"].at[jnp.array([CI_NEXT, CI_ITERS])].add(
            jnp.stack([ev_arr.astype(jnp.int32),
                       progress.astype(jnp.int32)]))
        v = kernel.on_arrival(ctx, v, rid_na, t_na, na_on)
        if has_delay:
            # raw arrival (and, under churn / resilience, orphan
            # re-route or retry): the routing decision is made
            # (``node`` is the pick) and the request goes in flight to
            # that node
            rid_a32 = jnp.asarray(rid_a, jnp.int32)
            if direct:
                if has_churn:
                    snd_on = (ev_arr & anyup) | ev_orph
                    rid_s = jnp.where(ev_orph, rid_o, rid_a32)
                else:
                    snd_on = ev_arr
                    rid_s = rid_a32
                if has_resil:
                    snd_on = snd_on | ((ev_rtry & anyup) if has_churn
                                       else ev_rtry)
                    rid_s = jnp.where(ev_rtry, rid_r32, rid_s)
                # landing time samples the delay at send time
                kc = jnp.clip(node, 0, K - 1)
                if var_delay:
                    d_snd = _sched_delay(t_ev, dtimes[kc], dvals[kc],
                                         dper[kc])
                else:
                    d_snd = delays[kc]
                ptail = v["pend_tail"]
                pempty = v["pend_len"] == 0
                v = dict(v)
                v["land_t"] = v["land_t"].at[
                    _gidx(snd_on, rid_s, N)].set(t_ev + d_snd,
                                                 mode="drop")
                v["dnx"] = v["dnx"].at[
                    _gidx(snd_on & ~pempty, ptail, N)].set(
                    rid_s, mode="drop")
                v["pend_head"] = jnp.where(snd_on & pempty, rid_s,
                                           v["pend_head"])
                v["pend_tail"] = jnp.where(snd_on, rid_s,
                                           v["pend_tail"])
                v["pend_len"] = (v["pend_len"]
                                 + snd_on.astype(jnp.int32))
            else:
                ptail = v["pend_tail"]
                pempty = v["pend_len"] == 0
                v = dict(v)
                v["pend_head"] = jnp.where(ev_arr & pempty, rid_a32,
                                           v["pend_head"])
                v["lw_d_pos"] = jnp.where(ev_arr & ~pempty, ptail,
                                          v["lw_d_pos"])
                v["lw_d_val"] = jnp.where(ev_arr & ~pempty, rid_a32,
                                          v["lw_d_val"])
                v["pend_tail"] = jnp.where(ev_arr, rid_a32,
                                           v["pend_tail"])
                v["pend_len"] = (v["pend_len"]
                                 + ev_arr.astype(jnp.int32))
        if has_churn:
            # park append — the one code path that grows the FIFO:
            # all-down fresh arrivals / retries, and (under delay)
            # requests landing on a node that died while in flight
            if has_delay:
                park_in = (ev_arr & ~anyup) | (ev_pend & ~node_up)
                rid_pk = jnp.where(ev_pend, rid_p,
                                   jnp.asarray(rid_a, jnp.int32))
            else:
                park_in = ev_arr & ~anyup
                rid_pk = jnp.asarray(rid_a, jnp.int32)
            if has_resil:
                park_in = park_in | (ev_rtry & ~anyup)
                rid_pk = jnp.where(ev_rtry, rid_r32, rid_pk)
            pk_empty = v["park_len"] == 0
            pk_tail = v["park_tail"]
            v = dict(v)
            v["nxt"] = v["nxt"].at[
                _gidx(park_in & ~pk_empty, pk_tail, N)].set(
                rid_pk, mode="drop")
            v["park_head"] = jnp.where(park_in & pk_empty, rid_pk,
                                       v["park_head"])
            v["park_tail"] = jnp.where(park_in, rid_pk,
                                       v["park_tail"])
            v["park_len"] = v["park_len"] + park_in.astype(jnp.int32)
            v["park_t"] = jnp.where(park_in & pk_empty, t_ev,
                                    v["park_t"])
        s = v
        if has_delay and not stream and not direct:
            ki = jnp.where(s["ev_rid"] >= 0, k_step, SG)
            s["d_node"] = s["d_node"].at[ki].set(
                jnp.asarray(node, jnp.int32), mode="drop")

        s = _fold_event(ctx, s)
        s = dict(s)
        if trace:
            # stage this event's trace record (shared by both link
            # modes); non-progress steps park on the SG guard row
            from repro.core.jax_engine import CI_COLD
            from repro.telemetry.rail import (AUX_COLD,
                AUX_FAIL_EXHAUSTED, AUX_FAIL_RETRY, AUX_OVERFLOW,
                AUX_QUEUED, AUX_SHED, AUX_TIMEOUT, TraceKind)
            ci1 = s["ci"]
            dlt = ci1 - ci
            kind = jnp.where(exec_on, TraceKind.EXEC, jnp.where(
                cold_on, TraceKind.COLD, jnp.int32(-1)))
            if timers:
                kind = jnp.where(ev_timer, TraceKind.TIMER, kind)
            if has_churn:
                kind = jnp.where(
                    ev_churn, TraceKind.CHURN,
                    jnp.where(ev_orph, TraceKind.REROUTE, kind))
            if has_resil:
                kind = jnp.where(ev_rtry, TraceKind.RETRY, kind)
            if has_delay:
                kind = jnp.where(ev_pend, TraceKind.NODE_ARRIVAL,
                                 kind)
            kind = jnp.where(ev_arr, TraceKind.ARRIVAL, kind)
            rid_tr = jnp.where(ev_slot,
                               jnp.asarray(rid_done, jnp.int32),
                               jnp.int32(-1))
            if timers:
                rid_tr = jnp.where(ev_timer, rid_t, rid_tr)
            if has_churn:
                rid_tr = jnp.where(
                    ev_orph, jnp.asarray(rid_o, jnp.int32), rid_tr)
            if has_resil:
                rid_tr = jnp.where(ev_rtry, rid_r32, rid_tr)
            if has_delay:
                rid_tr = jnp.where(
                    ev_pend, jnp.asarray(rid_p, jnp.int32), rid_tr)
            rid_tr = jnp.where(ev_arr, jnp.asarray(rid_a, jnp.int32),
                               rid_tr)
            fn_tr = jnp.where(
                ev_slot, j_done,
                jnp.where(rid_tr >= 0,
                          ctx.fn_at(jnp.clip(rid_tr, 0, N - 1)),
                          jnp.int32(-1)))
            fail_i = dlt[CI_FAILED] + dlt[CI_TMO]
            aux_ex = (jnp.where(dlt[CI_EXH] > 0, AUX_FAIL_EXHAUSTED,
                                jnp.where(fail_i > 0, AUX_FAIL_RETRY,
                                          0))
                      + jnp.where(dlt[CI_TMO] > 0, AUX_TIMEOUT, 0))
            aux = (jnp.where(dlt[CI_COLD] > 0, AUX_COLD, 0)
                   + jnp.where(s["q_tot"] > tr_q0, AUX_QUEUED, 0)
                   + jnp.where(dlt[CI_SHED] > 0, AUX_SHED, 0)
                   + jnp.where(dlt[CI_OVF] > 0, AUX_OVERFLOW, 0))
            aux = jnp.where(exec_on, aux_ex, aux)
            if has_churn:
                aux = jnp.where(ev_churn, node_up.astype(jnp.int32),
                                aux)
            busy = ((s["slot_state"] == BUSY) & capm).sum()
            warm = ((s["slot_state"] == IDLE) & (s["slot_fn"] >= 0)
                    & capm).sum()
            rec_i = jnp.stack(
                [kind, rid_tr, fn_tr, jnp.asarray(node, jnp.int32),
                 aux, s["q_tot"], busy, warm,
                 ci1[CI_ITERS]]).astype(jnp.int32)
            rec_f = jnp.stack([t_ev, jnp.where(exec_on, e_done, 0.0)])
            ki_tr = jnp.where(progress, k_step, SG)
            s["tr_i"] = s["tr_i"].at[ki_tr].set(rec_i, mode="drop")
            s["tr_f"] = s["tr_f"].at[ki_tr].set(rec_f, mode="drop")
        if direct:
            # direct-link mode: no overlays to stage, no reads to
            # chase — every link write already hit its rail
            stall = jnp.where(
                active & ~live, 1,
                jnp.where(active & (s["ci"][CI_ITERS] >= max_iters),
                          2, s["ci"][CI_STALL]))
            s["ci"] = s["ci"].at[CI_STALL].set(stall)
            return s
        # stage this event's link writes into the overlay slot (every
        # step overwrites its own slot, so no per-segment reset — a
        # stale entry can only repeat the already-flushed rail value)
        lwp, lwv = s.pop("lw_q_pos"), s.pop("lw_q_val")
        s["ov_q_pos"] = s["ov_q_pos"].at[k_step].set(
            jnp.where(lwp >= 0, lwp, jnp.int32(N)))
        s["ov_q_val"] = s["ov_q_val"].at[k_step].set(lwv)
        if timers:
            ltp, ltv = s.pop("lw_t_pos"), s.pop("lw_t_val")
            s["ov_t_pos"] = s["ov_t_pos"].at[k_step].set(
                jnp.where(ltp >= 0, ltp, jnp.int32(N)))
            s["ov_t_val"] = s["ov_t_val"].at[k_step].set(ltv)
        if has_delay:
            ldp, ldv = s.pop("lw_d_pos"), s.pop("lw_d_val")
            s["ov_d_pos"] = s["ov_d_pos"].at[k_step].set(
                jnp.where(ldp >= 0, ldp, jnp.int32(N)))
            s["ov_d_val"] = s["ov_d_val"].at[k_step].set(ldv)

        # deferred link reads: a push and a pop of the same chain
        # never share an event, so the parked successor lookups can
        # run here — each rail read is a *single-element* gather
        # (cheap even on the vmap batched-operand path; it's full-row
        # batched gathers the design keeps out of the body) and every
        # park register targets the event's own node, so the
        # successor lands in the node's view row and rides the one
        # row commit
        def chase(rail, ov_pos, ov_val, rid):
            m = ov_pos == rid
            ov = ov_val[jnp.argmax(m)]
            return jnp.where(m.any(), ov,
                             rail[jnp.clip(rid, 0, N - 1)])

        pp_kf, pp_rid = s.pop("pp_kf"), s.pop("pp_rid")
        succ = chase(s["nxt"], s["ov_q_pos"], s["ov_q_val"], pp_rid)
        # a deferred pop's successor overrides the parked head write
        # (the pop already set qw_head_pos to the same (node, fn) slot)
        s["qw_head_val"] = jnp.where(pp_kf >= 0, succ,
                                     s["qw_head_val"])
        if timers:
            tp_kf, tp_rid = s.pop("tp_kf"), s.pop("tp_rid")
            tsucc = chase(s["tnx"], s["ov_t_pos"], s["ov_t_val"],
                          tp_rid)
            # ctx.arrival_at is the node-local clock (+delay under
            # has_delay) — the same float association as arming at
            # the head of the rail
            t_fire = ctx.arrival_at(tsucc) + threshold
            ti = _gidx(tp_kf >= 0, tp_kf % F, F)
            s["tmr_rid"] = s["tmr_rid"].at[ti].set(tsucc, mode="drop")
            s["tmr_next"] = s["tmr_next"].at[ti].set(t_fire,
                                                     mode="drop")
        if has_delay:
            dp_k, dp_rid = s.pop("dp_k"), s.pop("dp_rid")
            dsucc = chase(s["dnx"], s["ov_d_pos"], s["ov_d_val"],
                          dp_rid)
            s["pend_head"] = jnp.where(dp_k >= 0, dsucc,
                                       s["pend_head"])
        stall = jnp.where(
            active & ~live, 1,
            jnp.where(active & (s["ci"][CI_ITERS] >= max_iters), 2,
                      s["ci"][CI_STALL]))
        s["ci"] = s["ci"].at[CI_STALL].set(stall)
        return s

    step_lanes = jax.vmap(
        lane_step, in_axes=(None, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0))

    def cond(s):
        ci = s["ci"]
        return jnp.any((ci[:, done_col] < N) & (ci[:, CI_STALL] == 0))

    def segment(s):
        if not stream and not direct:
            s = dict(s)
            s["d_rid"] = jnp.full((L, SG), N, jnp.int32)
        if trace:
            # clear the trace overlay: non-progress steps leave their
            # slot untouched, so stale rows must read as unused (-1)
            from repro.telemetry.rail import TR_RF, TR_RI
            s = dict(s)
            s["tr_i"] = jnp.full((L, SG, TR_RI), -1, jnp.int32)
            s["tr_f"] = jnp.zeros((L, SG, TR_RF), jnp.float64)

        def step(k_step, s):
            # apply the previous event's parked queue writes before
            # anything reads the queue arrays: with the in-place
            # scatter as each buffer's sole direct user and every
            # later read consuming its output, copy-insertion carries
            # the (L, K, F) queue arrays copy-free (writing them at
            # the end of the step instead costs two full copies per
            # event per array). The final event's registers are never
            # applied — nothing reads the queues after the loop.
            def qw_idx(pos):
                return (jnp.where(pos >= 0, pos // F, K),
                        jnp.where(pos >= 0, pos % F, F))

            s = dict(s)
            if not direct:
                kw, fw = qw_idx(s["qw_len_pos"])
                s["q_len"] = s["q_len"].at[lanes, kw, fw].add(
                    s["qw_len_delta"], mode="drop")
                kw, fw = qw_idx(s["qw_head_pos"])
                s["q_head_rid"] = s["q_head_rid"].at[
                    lanes, kw, fw].set(s["qw_head_val"], mode="drop")
                kw, fw = qw_idx(s["qw_tail_pos"])
                s["q_tail_rid"] = s["q_tail_rid"].at[
                    lanes, kw, fw].set(s["qw_tail_val"], mode="drop")
            ei, t_ev, t_arr = pick_events(s)
            ci = s["ci"]
            live = ((ci[:, done_col] < N) & (ci[:, CI_STALL] == 0)
                    & (t_ev < BIG))
            # the router runs first, read-only: in an arrival event no
            # enabled write precedes the arrival phase, so the state
            # it reads equals the post-slot-phase state of the old
            # two-view spelling bit-for-bit
            rid_a = jnp.minimum(ci[:, CI_NEXT], N - 1)
            if has_churn:
                up = (s["ch_ix"] & 1) == 0
                # the routed request may be the park head (orphan
                # re-route), decided at the orphan event's time
                ev_orph_g = live & (ei == orph_base)
                rid_rt = jnp.where(
                    ev_orph_g, jnp.clip(s["park_head"], 0, N - 1),
                    rid_a)
                t_rt = jnp.where(ev_orph_g, t_ev, t_arr)
            else:
                up = None
                rid_rt, t_rt = rid_a, t_arr
            if has_resil:
                # ... or the retry-rail head, decided at its fire time
                ev_rtry_g = live & (ei == rtry_base)
                rid_rt = jnp.where(
                    ev_rtry_g, jnp.clip(s["r_head"], 0, N - 1),
                    rid_rt)
                t_rt = jnp.where(ev_rtry_g, t_ev, t_rt)
            j_rt = fn_flat[base_n + rid_rt]
            if var_delay:
                delay_now = _sched_delay(
                    jnp.broadcast_to(t_rt[:, None], (L, K)),
                    dt_b, dv_b, dp_b)
            elif has_delay:
                delay_now = delays
            else:
                delay_now = None
            k_route = jnp.clip(
                pick_lanes(s["q_len"], s["q_tot"], s["slot_fn"],
                           s["slot_state"], cap_mask, s["est_sum"],
                           s["est_n"], s["node_gn"], s["node_gsum"],
                           t_cold_l, up, delay_now,
                           s["cbr_until"] if has_breaker else None,
                           j_rt, rid_rt, t_rt), 0, K - 1)
            if has_churn:
                # a router may still name a down node (e.g. every
                # sampled JSQ candidate is down); re-aim at the
                # lowest-id up node — mirrored in the reference
                k_route = jnp.where(
                    jnp.take_along_axis(up, k_route[:, None],
                                        axis=1)[:, 0],
                    k_route, jnp.argmax(up, axis=1).astype(jnp.int32))
            # the event's node: the phases are mutually exclusive, so
            # one view/commit pair serves slot, timer,
            # deferred-arrival and arrival events alike
            ev_slot = live & (ei < n_slot)
            node_s = jnp.clip(jnp.where(ei >= KC, ei - KC, ei),
                              0, KC - 1) // C
            k_ev = jnp.where(ev_slot, node_s, k_route)
            if timers:
                ev_timer = live & (ei >= tmr_base) & (ei < pend_base)
                kf_t = jnp.clip(jnp.where(ei < tmr_base + KF,
                                          ei - tmr_base,
                                          ei - tmr_base - KF),
                                0, KF - 1)
                k_ev = jnp.where(ev_timer, kf_t // F, k_ev)
            if has_delay:
                ev_pend = (live & (ei >= pend_base)
                           & (ei < pend_base + K))
                k_ev = jnp.where(
                    ev_pend, jnp.clip(ei - pend_base, 0, K - 1), k_ev)
            if has_churn:
                ev_churn_g = (live & (ei >= churn_base)
                              & (ei < churn_base + K))
                k_ev = jnp.where(
                    ev_churn_g, jnp.clip(ei - churn_base, 0, K - 1),
                    k_ev)
            v = gather_nodal(s, k_ev)
            if has_churn:
                v["anyup"] = up.any(axis=1)
            capm_node = jnp.take_along_axis(
                cap_mask, k_ev[:, None, None], axis=1)[:, 0]
            v = step_lanes(k_step, v, trace_ix, t_cold_l, t_evict_l,
                           capm_node, beta, ei, t_ev, t_arr, k_ev)
            s = commit_nodal(s, v, k_ev)
            exec_on = ev_slot & (ei < KC)
            if has_resil:
                # only successful completions count toward the
                # per-node tally (the lane body classified them)
                nd_on = s.pop("rs_ok")
            else:
                nd_on = exec_on
            s["node_done"] = s["node_done"].at[
                lanes, jnp.where(nd_on, k_ev, K)].add(
                1, mode="drop")
            return s

        s = lax.fori_loop(0, SG, step, s)
        if trace:
            from repro.telemetry.rail import emit_flush
            emit_flush(s["tr_i"], s["tr_f"])
        if direct:
            # direct-link mode writes every rail in-body; nothing to
            # flush
            return s
        # batch-flush the staged links — the only (L, N) rail writes,
        # paid once per SG events
        s = dict(s)
        s["nxt"] = s["nxt"].at[lane_iota, s["ov_q_pos"]].set(
            s["ov_q_val"], mode="drop")
        if timers:
            s["tnx"] = s["tnx"].at[lane_iota, s["ov_t_pos"]].set(
                s["ov_t_val"], mode="drop")
        if has_delay:
            s["dnx"] = s["dnx"].at[lane_iota, s["ov_d_pos"]].set(
                s["ov_d_val"], mode="drop")
        if not stream:
            s["start"] = s["start"].at[lane_iota, s["d_rid"]].set(
                s["d_start"], mode="drop")
            s["completion"] = s["completion"].at[
                lane_iota, s["d_rid"]].set(s["d_comp"], mode="drop")
            if has_delay:
                s["node_of"] = s["node_of"].at[
                    lane_iota, s["d_rid"]].set(s["d_node"],
                                               mode="drop")
        return s

    final = lax.while_loop(cond, segment, s)
    ci, cf = final["ci"], final["cf"]
    from repro.core.jax_engine import (CF_COLDT, CF_EVICTT, CF_RMAX,
                                       CF_RSUM, CF_SSUM, CI_COLD,
                                       CI_EVICT)
    out = dict(cold_starts=ci[:, CI_COLD], cold_time=cf[:, CF_COLDT],
               evictions=ci[:, CI_EVICT], evict_time=cf[:, CF_EVICTT],
               overflow=ci[:, CI_OVF],
               stalled=ci[:, CI_STALL], n_events=ci[:, CI_ITERS],
               done=ci[:, CI_DONE], node_done=final["node_done"],
               resp_sum=cf[:, CF_RSUM], slow_sum=cf[:, CF_SSUM],
               max_response=cf[:, CF_RMAX], resp_hist=final["hist"])
    if tl_bins:
        out["tl_count"] = final["tl_cnt"]
        out["tl_resp_sum"] = final["tl_resp"]
        out["tl_exec_sum"] = final["tl_exec"]
    if not stream:
        out["start"] = final["start"]
        out["completion"] = final["completion"]
        if has_delay and not direct:
            out["node_of"] = final["node_of"]
    if deadlines is not None:
        out["deadline_miss"] = final["dl_miss"]
    if has_resil:
        out["failed"] = ci[:, CI_FAILED]
        out["timed_out"] = ci[:, CI_TMO]
        out["retried"] = ci[:, CI_RETRY]
        out["shed"] = ci[:, CI_SHED]
        out["failed_exhausted"] = ci[:, CI_EXH]
    if has_breaker:
        out["breaker_trips"] = ci[:, CI_TRIPS]
    return out


@functools.partial(jax.jit,
                   static_argnames=("kernel", "router", "n_nodes",
                                    "n_fns", "capacity", "queue_cap",
                                    "seed", "stream", "tl_bins",
                                    "has_delay", "has_churn",
                                    "var_delay", "seg",
                                    "keep_responses", "resil",
                                    "trace"),
                   compiler_options=LOOP_COMPILER_OPTIONS)
def _cluster_metrics(fn, arr, ex, cold, ev, tix, masks, betas, prior,
                     threshold, delays=None, churn_t=None, dtimes=None,
                     dvals=None, dper=None, deadlines=None,
                     rs_nfail=None, rs_tmo=None, rs_key=None, *,
                     kernel, router, n_nodes, n_fns, capacity,
                     queue_cap, seed=0, stream=True, tl_bins=0,
                     tl_bucket=60.0, has_delay=False, has_churn=False,
                     var_delay=False, seg=0, keep_responses=False,
                     resil=None, trace=False):
    """Cluster counterpart of `jax_engine._sweep_metrics`: lane-batched
    dynamic-router run + on-device metric reduction (same metric
    names, plus ``node_done``). ``delays``/``has_delay`` switch on the
    deferred-arrival rail; exact-mode responses are then measured from
    each request's node-local (delayed) arrival. ``churn_t`` +
    ``has_churn`` switch on the failure rail (responses then measure
    from the *raw* arrival — the user-perceived convention);
    ``dtimes``/``dvals``/``dper`` + ``var_delay`` make the per-node
    delay time-varying; ``deadlines`` (F,) adds the per-function
    ``deadline_miss`` fold (attainment is derived outside jit by
    `repro.core.jax_engine.slo_attainment`, shared by every tier).
    ``rs_nfail``/``rs_tmo``/``rs_key`` + the static ``resil`` tuple
    switch on the resilience layer (failure injection / timeouts /
    retries / shedding — means and quantiles then reduce over the
    successful completions, and responses use the raw-arrival
    convention like churn)."""
    if keep_responses and stream:
        raise ValueError("keep_responses requires stream=False")
    if delays is None:
        delays = jnp.zeros((n_nodes,), jnp.float64)
    out = _simulate_cluster(fn, arr, ex, cold, ev, tix, masks, betas,
                            prior, threshold, delays, churn_t, dtimes,
                            dvals, dper, deadlines, rs_nfail, rs_tmo,
                            rs_key, kernel=kernel,
                            router=router, n_nodes=n_nodes,
                            n_fns=n_fns, capacity=capacity,
                            queue_cap=queue_cap, seed=seed,
                            stream=stream, tl_bins=tl_bins,
                            tl_bucket=tl_bucket, has_delay=has_delay,
                            has_churn=has_churn, var_delay=var_delay,
                            seg=seg, resil=resil, trace=trace)
    N = fn.shape[1]
    if resil is not None:
        # under faults only successes fold into the response sums and
        # per-request records; means/quantiles reduce over those
        denom = jnp.maximum(out["done"], 1).astype(jnp.float64)
    else:
        denom = N
    if stream:
        nq = out["done"][:, None] if resil is not None else N
        p99 = hist_quantile(out["resp_hist"], 0.99, nq,
                            out["max_response"])
    else:
        arr_l = arr[tix]
        if has_churn or resil is not None:
            pass  # raw-arrival convention: completion - arrival
        elif var_delay:
            nof = out["node_of"]
            arr_l = arr_l + _sched_delay(arr_l, dtimes[nof],
                                         dvals[nof], dper[nof])
        elif has_delay:
            arr_l = arr_l + delays[out["node_of"]]
        resp = out["completion"] - arr_l
        if resil is not None:
            # shed / retry-exhausted rids keep completion == -1
            resp = jnp.where(out["completion"] >= 0, resp, jnp.nan)
            p99 = jnp.nanpercentile(resp, 99.0, axis=1)
        else:
            p99 = jnp.percentile(resp, 99.0, axis=1)
    res = dict(mean_response=out["resp_sum"] / denom,
               mean_slowdown=out["slow_sum"] / denom,
               resp_sum=out["resp_sum"],
               slow_sum=out["slow_sum"],
               done=out["done"],
               node_done=out["node_done"],
               p99_response=p99,
               max_response=out["max_response"],
               resp_hist=out["resp_hist"],
               cold_starts=out["cold_starts"],
               cold_time=out["cold_time"],
               evictions=out["evictions"],
               overflow=out["overflow"],
               stalled=out["stalled"],
               n_events=out["n_events"])
    if tl_bins:
        res["tl_count"] = out["tl_count"]
        res["tl_resp_sum"] = out["tl_resp_sum"]
        res["tl_exec_sum"] = out["tl_exec_sum"]
    if deadlines is not None:
        res["deadline_miss"] = out["deadline_miss"]
    if resil is not None:
        for key in ("failed", "timed_out", "retried", "shed",
                    "failed_exhausted"):
            res[key] = out[key]
    if "breaker_trips" in out:
        res["breaker_trips"] = out["breaker_trips"]
    if keep_responses:
        res["response"] = resp
    return res


# ---------------------------------------------------------- audit hooks
# Pure metadata for `repro.analysis`; the loops never read it. Each
# entry names a carried array that legitimately scales with the trace
# length N and the reason the cost is accepted (PR 5 documented the
# rid-chain rails as the dynamic tier's one O(N) concession; PR 6 kept
# them while moving everything else onto the segment overlay).
CARRY_RAILS = {
    "nxt": "per-function FIFO successor rid -- runtime routing means "
           "queue membership is only known at dispatch time, so the "
           "queue rail is a linked chain with one i32 link per "
           "request (the segment overlay batches the *writes*; the "
           "links themselves must persist).",
    "tnx": "openwhisk_v2 timer-rail successor rid (same linked-chain "
           "argument as `nxt`, for the per-function re-arm timers).",
    "dnx": "deferred NODE_ARRIVAL rail under net_delay: in-flight "
           "requests ride a time-ordered chain, one i32 link per "
           "request.",
    "land_t": "churn re-route landing time per in-flight rid (f64); "
              "paired with `dnx` when the failure rail is active.",
    "att": "resilience attempt counter per original rid (i32).",
    "rt_t": "resilience retry-eligibility time per rid (f64).",
    "node_of": "exact mode under net_delay records each request's "
               "dispatching node -- an output record, not loop "
               "bookkeeping.",
    "start": "exact-mode per-request dispatch-time record (output).",
    "completion": "exact-mode per-request completion-time record "
                  "(output).",
    "tr_i": "event-trace overlay (trace=True only): one int32 record "
            "per event in an O(SG) segment buffer, flushed to the "
            "host per segment via an ordered io_callback -- never "
            "N-scaling.",
    "tr_f": "event-trace overlay float half (see `tr_i`): per-event "
            "simulation time and execution time, O(SG) carried "
            "state.",
}


def audit_jits():
    """Jitted cluster entry points by name, for `repro.analysis`."""
    return {"simulate_cluster": _simulate_cluster,
            "cluster_metrics": _cluster_metrics}
