"""Policy-agnostic fixed-shape event core for vectorised scheduling.

The Python event engine (`repro.core.simulator`) replays ~10^4 req/s;
policy x capacity x trace sweeps need orders of magnitude more. This
module owns everything that is *policy independent* about simulating a
C-slot edge server in JAX — the state layout, the queue ops, the slot
primitives (`dispatch` / `start_cold`), the running-mean estimator and
the ``lax.while_loop`` event loop — while the *decisions* live in
pure-function policy kernels (`repro.core.jax_policies`). A kernel is
selected by a static argument, so ``jax.jit`` specialises the loop body
per policy, and the engine carries a leading *lane* dimension so a whole
policy x capacity x beta x trace grid runs as one device call (`sweep`).

State layout (static F functions, C slots, N requests, L lanes; all
arrays carry the leading L):

  slots:  slot_fn    (C,) i32  function resident in the slot (-1 empty)
          slot_state (C,) i32  {0 COLD (warming), 1 IDLE, 2 BUSY}
          slot_ready (C,) f64  next slot event time (cold-done for COLD,
                               exec-done for BUSY; BIG when IDLE/empty)
          slot_req   (C,) i32  request id being executed (BUSY only)
          slot_used  (C,) f64  last dispatch time (LRU bookkeeping;
                               0.0 for a never-used instance)
          slot_seq   (C,) i32  creation sequence number of the resident
                               instance — mirrors the Python engine's
                               monotonically increasing ``inst_id`` so
                               iteration-order tie-breaks (LRU, victim
                               scans) reproduce exactly
  queues: per-function FIFOs as *position cursors* into the trace's
          per-function arrival order. The requests of f_j, sorted by
          id, are a loop-invariant shared operand (``pos_rids`` +
          ``pos_off`` built from a stable argsort of fn_id), and
          because every arrival of f_j consumes exactly one position —
          q_push for a queued arrival, q_consume_direct for a directly
          dispatched one — and pops are FIFO, the queue of f_j is
          always the contiguous position range
          [q_head_pos, q_head_pos + q_len). Head/successor lookups are
          gathers into the shared operand; the carried queue state is
          just q_head_pos/q_len (F,) i32 plus a q_head_rid (F,) i32
          cache (refreshed with the successor at pop time, so head
          reads — including the central-queue head scan — touch no
          large operand) — O(F) no matter how long a backlog gets (SFF
          starvation can hold a request queued for the whole trace).
          ``queue_cap`` bounds the per-function
          backlog: a push onto a function with queue_cap waiting
          requests is dropped and counted in ``overflow`` (must stay 0
          for a valid run; a dropped request breaks the position
          invariant, which is fine — the run is already invalid).
  est:    est_sum (F,) f64 / est_n (F,) i32 — running means of observed
          execution times with global-mean, then `prior`, fallback (the
          global accumulators live in the packed counters)
  timers: original timers fire at arrival + threshold in arrival
          order, so the rail rides the same per-function positions:
          tmr_pos (F,) i32 is the next position whose timer fires,
          arr_cnt (F,) i32 counts arrived positions, tmr_next (F,) f64
          is the head fire time. Every arrival arms its position;
          arrivals that dispatch directly while the rail is idle are
          consumed silently, and one that slips into a busy rail fires
          later as a no-op (the is-head gate drops it, exactly like the
          Python policy drops timers of already-served requests).
          Re-arms (only ever the current queue head) keep the one-slot
          cache rearm_t (F,) f64 / rearm_rid (F,) i32. Allocated only
          when the kernel sets ``has_timers``.
  memory: only when the node's resource is memory (``mem_mb`` given,
          `repro.api.ExperimentSpec(slot_mb=...)`): mc (NMC,) i32 — free
          MB, MB evicted, cold starts with two or more victims, and cold
          starts deferred for want of memory. The slot array then holds
          the most instances the node's memory can (``node_mb //
          repro.core.server.MIN_MEM_MB``), and a cold start evicts the
          shortest prefix of the idle slots in the policy's victim
          order that makes the footprint fit (`pick_victims`,
          `start_cold_mem`).
  ctrs:   ci (NCI,) i32 / cf (NCF,) f64 — every per-lane scalar counter
          (arrival cursor, done/event counts, stall flag, instance
          sequence, estimator globals, cold/eviction/overflow tallies
          and the streaming response accumulators) packed into two
          arrays so the while_loop carries 2 small buffers instead of
          a dozen scalars.
  out:    always: streaming metric accumulators — response sum,
          slowdown sum, response max (in cf) and ``hist`` (HIST_BINS,)
          i32, a fixed log-spaced response-time histogram (8 bins per
          decade over 1e-4..1e4 s) that serves p99 and CDFs to within
          one bin width; optionally (``tl_bins > 0``) a minute-binned
          timeline (request count / response sum / exec sum per
          arrival-time bucket, the Fig. 8 fold). In *exact* mode
          (``stream=False``) additionally start/completion (N,) f64
          per-request records.

Event arbitration mirrors `repro.core.events`: at equal times
EXEC_DONE < COLD_DONE < TIMER < ARRIVAL, so capacity freed at time t is
visible to an arrival at the same t. ``cap_mask`` masks slots so
capacity is sweepable across lanes without retracing; ``stalled`` flags
lanes that ran out of events or iteration budget before every request
completed (overflowed requests can never finish).

Engine internals — window/slab layout
-------------------------------------

The event loop runs over the trace in *time-ordered windows* of ``W =
window`` requests (``DEFAULT_WINDOW`` unless overridden; traces are
arrival-sorted, so a contiguous id range *is* a time window). The loop
nest is::

    fori_loop over windows            # shared slab refresh per window
      while_loop over segments        # until every lane leaves the window
        fori_loop over SEG events     # lane-stacked pick + vmapped body
          segment flush               # exact mode: overlay scatter

Per window, the four gather-heavy shared operands — ``arrival`` /
``exec_time`` / ``fn_id`` (rid-indexed) and the positional queue layout
(position-indexed) — are ``dynamic_slice``'d into (T, W) *slabs* sized
to stay L2-resident (24 bytes/request: f64 times + two i32 ids), so
the random gathers of the inner loop stop thrashing the cache once N
outgrows it. Slabs are f64/i32 *copies*, so results are bitwise
independent of the window size; every read goes through a dual-source
bounds check (`EngineCtx._dual`): in-window indices hit the slab,
out-of-window indices (a queue entry or running request whose links
span a window boundary — the positional-cursor design makes this a
bounds check, not a re-link) fall back to the full operand, and the
disabled side of each pair reads a fixed cached location.

Windows are *global*: all lanes share one slab set (a per-lane window
would batch the slab operand and knock every gather off vmap's
unbatched-operand fast path). A lane whose next event is an arrival
beyond the current window **parks** — its arrival candidate keeps its
exact time (read from the full operand at the boundary element) so the
packed argmin still resolves event order exactly, but the consume is
gated off and the lane no-ops until the slowest lane finishes the
window. Parking preserves each lane's event order exactly: a lane only
parks when its true earliest pending event is the out-of-window
arrival. The per-lane window cursor is implicit in the arrival cursor
(``ci[CI_NEXT] // W``); ``n_events`` counts *processed events*, so it
is window-size invariant. ``loop_steps`` (one scalar per launch)
counts what the device ran instead: SEG lane-stacked iterations per
segment, parked spins and finished lanes included, so the sum of a
launch's ``n_events`` over ``loop_steps`` x L is its lane occupancy.
The queue-successor gathers use a second, window-major positional
layout (stable argsort of (rid // W, fn)) with
per-window per-function offsets (``off_w`` / ``cum_cnt``) so in-window
position reads are slab-local.

f32 slab copies for the time reads were evaluated and rejected for the
default path: every consumer feeds either the event-time arbitration
or the f64 metric accumulators, and a float32 round (~1e-7 relative)
breaks the engine's bitwise gates (stream-vs-exact equality and
request-for-request parity with the Python engine). The indices
(``fn_id`` + positional layout, half the slab bytes) are i32 already.

Performance shape — the six rules the layout follows, measured on the
XLA CPU backend:

1. *No control flow inside the body.* Every handler runs every
   iteration gated by an ``on`` predicate, and all writes are guarded
   by an out-of-bounds sentinel index when disabled (`_gidx`): one-hot
   selects on the small carried arrays (`_put` / `_bump`), scatters
   with ``mode="drop"`` on the per-request rails. A ``lax.cond`` under
   vmap lowers to a `select` over every carried array, i.e. a dense
   copy of the whole state per event.
2. *Lanes live inside the loop.* One ``while_loop`` carries (L, ...)
   state and the branchless body is vmapped per lane; finished lanes
   no-op through their guards. Vmapping the ``while_loop`` itself would
   mask finished lanes with per-event dense selects over all state.
3. *No large carried array is both gathered and scattered in one loop
   body.* XLA's copy-insertion materialises a full copy of such a
   buffer every iteration — the dominant cost of a naive spelling.
   Queues therefore never carry their contents at all: successor
   lookups are gathers into loop-invariant shared operands (which XLA
   neither copies nor scatters), and the only per-event writes touch
   O(F)/O(C) cursor arrays. Exact-mode per-request records go through
   the small per-segment overlay (d_rid/d_start/d_comp),
   batch-scattered into the (L, N) arrays once per SEG-event segment.
4. *Carried state is independent of trace length, and metrics fold per
   event.* Each dispatch leaves its (rid, completion, exec) triple in
   three per-event registers (``ev_*`` — plain selects, no scatters)
   and `_fold_event` folds them into the O(1) streaming accumulators
   (sums, max, histogram, optional timeline bins) at the end of every
   event — in event order, which is what makes the streamed sums
   bitwise *window-size invariant* (any deferred batch fold regroups
   its reduction tree wherever a window boundary cuts a segment; PR 2's
   per-segment flush fold was also, measurably, the large-N
   bottleneck: its (L, SEG) gathers/scatters scaled with N and cost
   ~3x at N = 3e5). The (L, N) per-request records exist only in exact
   mode (``stream=False``). A streaming lane carries
   O(F + C + HIST_BINS) state no matter how long the trace, which is
   what lets one machine sweep 10^6-request traces at a flat
   ~190k req/s per lane (benchmarks/engine_scale.py). Both modes run
   the identical fold, so streamed means are bit-identical to
   exact-mode means.
5. *One packed reduction picks the next event.* The candidate times of
   every event source — BUSY slots, COLD slots, original timers,
   re-arms, the arrival cursor — are concatenated in priority order
   into one lane-stacked (L, 2C+2F+1) matrix and a single segmented
   first-index ``argmin`` over the candidate axis resolves, for every
   lane at once, both the time and the tie-break (position encodes
   EXEC < COLD < TIMER < ARRIVAL and the within-class index order).
   The pick lives *outside* the per-lane vmap so wide lane batches on
   GPU/TPU lower to one reduction kernel instead of L small ones;
   small scalar counters ride the two packed ci/cf arrays so XLA:CPU
   dispatches fewer ops per event.
6. *The hot loop reads cache-sized slabs.* Shared trace operands are
   re-sliced per window (see above) so gather working sets stay
   L2-resident at any N; lane batching is backend-adaptive
   (`LANE_CHUNKS` / ``REPRO_LANE_CHUNK`` / `resolve_lane_chunk`)
   because the XLA:CPU sweet spot (~16 lanes) underfills an
   accelerator by orders of magnitude.
"""
from __future__ import annotations

import functools
import os
import time
from typing import Dict, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.request import Trace
from repro.core.resilience import backoff_jax

BIG = 1e30
COLD, IDLE, BUSY = 0, 1, 2
I32_MAX = np.iinfo(np.int32).max
SEG = 32          # events per segment (deferred result-write window)

# Compiler options of every jitted event loop (here and in
# `repro.cluster.engine`). The TPU compiler's loop-range analysis
# (`tpu-indexed-instruction-analyzer`) extracts each while-loop body,
# simplifies the copy and kills the process on the event loops:
#   algebraic_simplifier.cc:580] Check failed: computation->Accept(this)
#   is OK (FAILED_PRECONDITION: A cycle is detected while visiting
#   instruction ... control-predecessors={...}
# The control predecessors are the ordering edges that copy insertion
# puts around an in-place scatter on a carried array that the same body
# also reads: the resilience link rails (`nxt`, `att`, `rt_t`) and the
# cluster's queue and link rails. The small per-lane arrays avoid them
# by spelling (`_put` / `_bump`); the per-request rails cannot, and
# which of them trips the check moves with unrelated edits to the body.
# Other backends have no pass of that name, so their programs do not
# change.
LOOP_COMPILER_OPTIONS = {
    "xla_disable_hlo_passes": "tpu-indexed-instruction-analyzer"}

# Requests per trace window: the four slabs cost 24 bytes/request
# (2 x f64 + 2 x i32), so 524288 bounds the gather working set to
# ~12 MB — last-level-cache scale — however long the trace grows,
# while traces at or below it run the single-window fast path (no
# dual-source reads at all). ``window=`` overrides per call; results
# are bitwise identical at every setting, only locality changes.
DEFAULT_WINDOW = 524288

# Lanes per device call, by backend. XLA:CPU's per-lane efficiency is
# flat over ~8-48 lanes since the lane-stacked event pick landed, so
# the CPU entry is sized for *scheduling*: smaller chunks pack evenly
# onto the sweep's overlapping host threads (a 48-lane grid in chunks
# of 16 leaves one thread a straggler chunk; chunks of 8 balance).
# Accelerators amortise kernel launches over wide batches and the
# O(F+C) streaming carry fits thousands of lanes in HBM — table
# entries there are educated defaults pending real-hardware runs
# (ROADMAP). ``REPRO_LANE_CHUNK`` overrides with an integer or
# ``auto`` (two-point probe, see `resolve_lane_chunk`).
LANE_CHUNKS = {"cpu": 8, "gpu": 256, "tpu": 512}
_AUTO_CHUNK: Dict[str, int] = {}

# Packed per-lane counters: ci (NCI,) i32 and cf (NCF,) f64.
# CI_TERM..CI_TRIPS are the resilience tallies (requests terminal for
# any reason, injected failures, timeouts, retries, sheds, retry-budget
# exhaustions, circuit-breaker trips) — appended so the pre-resilience
# indices, and therefore every existing jaxpr, are unchanged; they stay
# zero unless the run declares a failure source.
(CI_NEXT, CI_DONE, CI_ITERS, CI_STALL, CI_SEQ, CI_GN, CI_COLD,
 CI_EVICT, CI_OVF, CI_TERM, CI_FAILED, CI_TMO, CI_RETRY, CI_SHED,
 CI_EXH, CI_TRIPS) = range(16)
NCI = 16
CF_GSUM, CF_COLDT, CF_EVICTT, CF_RSUM, CF_SSUM, CF_RMAX = range(6)
NCF = 6
# Packed memory registers mc (NMC,) i32 of a memory-mode run: free MB, MB
# evicted, cold starts that evicted two or more instances, cold starts
# deferred because memory could not be freed. A run without memory
# carries none of them.
MC_FREE, MC_EVICT_MB, MC_MULTI, MC_WAIT = range(4)
NMC = 4

# Streaming response histogram: log-spaced, 8 bins/decade over
# [1e-4, 1e4) seconds. Quantile reads are exact to one bin width
# (a factor of 10^(1/8) ~ 1.33x).
HIST_BINS = 64
HIST_LO = -4.0
HIST_PER_DECADE = 8


def ensure_x64() -> None:
    """Enable f64 before anything is traced.

    Event times need f64 for exact agreement with the Python engine over
    multi-hour traces. Flipping the flag mid-run (the old
    ``simulate_jax_from_trace`` behaviour) invalidates already-traced
    f32 jits elsewhere; importing this module instead performs the
    switch once, at import time, before the engine traces anything.
    """
    if not jax.config.jax_enable_x64:
        jax.config.update("jax_enable_x64", True)


ensure_x64()


# ---------------------------------------------------------- lane batching
def default_lane_chunk(backend: Optional[str] = None) -> int:
    """Table entry for the active (or given) JAX backend. A backend
    the table does not name raises: its entry has to be measured, not
    borrowed from the CPU's."""
    backend = backend or jax.default_backend()
    if backend not in LANE_CHUNKS:
        raise ValueError(
            f"no LANE_CHUNKS entry for JAX backend {backend!r} "
            f"(known: {sorted(LANE_CHUNKS)})")
    return LANE_CHUNKS[backend]


def resolve_lane_chunk(setting: Union[int, str, None] = None) -> int:
    """Resolve the lanes-per-device-call batch size.

    ``setting`` (or the ``REPRO_LANE_CHUNK`` environment variable when
    ``setting`` is None) may be an integer, ``"table"``/empty (use the
    per-backend `LANE_CHUNKS` entry) or ``"auto"`` — time a two-point
    probe (the table entry vs 4x it) on a small synthetic workload at
    the first sweep and keep whichever sustains more req/s. The probe
    result is cached per backend for the process lifetime.
    """
    if setting is None:
        setting = os.environ.get("REPRO_LANE_CHUNK", "")
    if isinstance(setting, str):
        setting = setting.strip().lower()
    if setting in ("", "table", None):
        return default_lane_chunk()
    if setting == "auto":
        return _probe_lane_chunk()
    return max(1, int(setting))


def _probe_lane_chunk(n_requests: int = 2048, n_functions: int = 24,
                      capacity: int = 8) -> int:
    """Two-point lane-batch probe: per-backend table entry vs 4x it.

    Runs the streaming engine (``sff`` — the cheapest kernel) over a
    small synthetic trace once per candidate (after a warm-up call per
    jit specialisation) and returns the candidate with the higher
    aggregate req/s. Cached per backend in ``_AUTO_CHUNK``.
    """
    backend = jax.default_backend()
    if backend in _AUTO_CHUNK:
        return _AUTO_CHUNK[backend]
    from repro.core.jax_policies import KERNELS
    from repro.traces.generator import synth_azure_arrays
    base = default_lane_chunk(backend)
    cands = (base, max(1, base * 4))
    a = synth_azure_arrays(n_functions=n_functions,
                           n_requests=n_requests, seed=0,
                           utilization=0.3)
    shared = tuple(jnp.asarray(a[k])[None]
                   for k in ("fn_id", "arrival", "exec_time",
                             "cold_start", "evict"))
    best, best_rate = base, -1.0
    for c in cands:
        args = shared + (jnp.zeros((c,), jnp.int32),
                         jnp.ones((c, capacity), bool),
                         jnp.ones((c,), jnp.float64),
                         jnp.float64(0.1), jnp.float64(0.1))
        kw = dict(kernel=KERNELS["sff"], n_fns=n_functions,
                  capacity=capacity, queue_cap=n_requests, stream=True)
        jax.block_until_ready(_sweep_metrics(*args, **kw))
        t0 = time.perf_counter()
        jax.block_until_ready(_sweep_metrics(*args, **kw))
        rate = c * n_requests / (time.perf_counter() - t0)
        if rate > best_rate:
            best, best_rate = c, rate
    _AUTO_CHUNK[backend] = best
    return best


class EngineCtx:
    """Per-lane view of the run handed to policy kernels.

    Bundles the (traced) trace arrays and window slabs, the (static)
    shape constants, the scalar knobs and the current segment step
    ``k``. Built inside the jitted entry point — it never crosses a jit
    boundary itself.

    Trace arrays are *shared* (T, ...) operands indexed by the lane's
    ``tix``: under vmap a gather whose operand is unbatched lowers to a
    single efficient gather, whereas a batched operand takes a generic
    path that is orders of magnitude slower on the CPU backend. The
    row-indexed (T, X) operands are additionally read through
    *flattened* views with a precomputed per-lane base offset
    (``tix * X + i``): a two-index-dim gather only hits XLA:CPU's fast
    path when the leading dim is size 1 (the simplifier drops the
    always-clamped index) — at T > 1 (multi-trace grids, the cluster
    static path's (T·K) sub-stream rows) it falls to the generic
    gather, measured ~25x slower per event. The per-request reads
    (`fn_at` / `arrival_at` / `exec_at`, and the positional queue
    reads `rid_at_pos`) are *dual-source*: indices inside the current
    window read the (T, W) L2-resident slab, the rest (queue links
    spanning a window boundary, long-running requests) fall back to
    the full operand — a bounds check plus two guarded gathers whose
    disabled side reads a fixed cached location, never a branch. Slabs
    hold exact f64/i32 copies, so which source serves a read can never
    change a result bit.
    """

    def __init__(self, *, fn_id2, arrival2, exec2, cold2, evict2,
                 pos_rids2, pos_off2, slabs, win_base, win_w, tix,
                 cap_mask, beta, prior, threshold, k, n, f, c, q,
                 stream=False, tl_bins=0, tl_bucket=60.0,
                 deadlines=None, mem_mb=None):
        flat = lambda a: (None if a is None          # noqa: E731
                          else a.reshape(-1))
        self._fn = flat(fn_id2)     # (T*N,) shared, flattened view
        self._arr = flat(arrival2)
        self._ex = flat(exec2)
        self._pos = flat(pos_rids2)  # rids by (fn, id)
        self._off = flat(pos_off2)   # per-fn offsets ((T*(F+1),))
        # current-window slabs: rid-indexed (T, W) copies + the
        # window-major positional slab and its per-fn (T, F) rows —
        # all flattened the same way
        (fn_s, arr_s, ex_s, pos_s, offw, cc_lo, cc_hi) = slabs
        self._fn_s, self._arr_s, self._ex_s = \
            flat(fn_s), flat(arr_s), flat(ex_s)
        self._pos_s = flat(pos_s)
        self._offw, self._cc_lo, self._cc_hi = \
            flat(offw), flat(cc_lo), flat(cc_hi)
        self.win_base = win_base   # first request id of the window
        self.W = win_w             # static window length
        self.single_win = win_w >= n   # static: slab == whole trace
        self.tix = tix             # this lane's trace index
        # per-lane flat base offsets into each operand family
        self._b_n = tix * n            # (T, N) rows
        self._b_w = tix * win_w        # (T, W) slabs
        self._b_f = tix * f            # (T, F) rows
        self._b_f1 = tix * (f + 1)     # (T, F+1) offsets
        self.t_cold = cold2        # (F,) — this lane's row, pre-gathered
        self.t_evict = evict2      # once outside the loops
        # (F,) i32 footprints in MB when the node's resource is memory,
        # else None (static: the slot-count path traces no memory op)
        self.mem_mb = mem_mb
        self.cap_mask = cap_mask
        self.beta = beta
        self.prior = prior
        self.threshold = threshold
        self.k = k                  # segment step (overlay slot)
        self.seg_n = SEG            # overlay length (drop sentinel)
        self.N, self.F, self.C, self.Q = n, f, c, q
        self.stream = stream        # static: drop per-request records
        self.tl_bins = tl_bins      # static: timeline fold bins (0=off)
        self.tl_bucket = tl_bucket
        self.deadlines = deadlines  # (F,) per-fn SLO deadlines or None
        # fold-site gates: the cluster's churn loop folds metrics at
        # EXEC_DONE (a drained request may be re-dispatched, so the
        # dispatch-time record would double-count) and writes exact-
        # mode per-request records directly per event (the d_* overlay
        # assumes one record per rid per segment)
        self.fold_at_dispatch = True
        self.direct_records = False
        # resilience gates: attempt counting at dispatch and deferring
        # the exact-mode completion record to the (successful)
        # EXEC_DONE — an exhausted request must keep completion == -1,
        # not its last attempt's dispatch-time completion
        self.has_resil = False
        self.defer_completion = False

    def _dual(self, full, slab, rid):
        """Windowed read of ``full[tix, rid]``: slab when ``rid`` is in
        the current window, full-operand fallback otherwise. The
        disabled source reads a fixed, hot location (slab 0 / the
        window base) so it costs no extra cache traffic. Single-window
        runs (W >= N — every trace at or under `DEFAULT_WINDOW`) skip
        the bounds check statically: the one window covers every id."""
        r = jnp.clip(jnp.asarray(rid, jnp.int32), 0, self.N - 1)
        if self.single_win:
            return full[self._b_n + r]
        off = r - self.win_base
        inw = (off >= 0) & (off < self.W)
        sv = slab[self._b_w + jnp.where(inw, off, 0)]
        fv = full[self._b_n + jnp.where(inw, self.win_base, r)]
        return jnp.where(inw, sv, fv)

    def fn_at(self, rid):
        return self._dual(self._fn, self._fn_s, rid)

    def arrival_at(self, rid):
        return self._dual(self._arr, self._arr_s, rid)

    def exec_at(self, rid):
        return self._dual(self._ex, self._ex_s, rid)

    def rid_at_pos(self, fn, pos):
        """Request id at arrival position ``pos`` of function ``fn``
        (garbage on out-of-range positions — callers gate).

        Positions are absolute (per-function arrival order over the
        whole trace); the bounds check against the window's per-fn
        position range [cc_lo, cc_hi) routes in-window positions to
        the window-major slab and cross-window links to the full
        (fn, id)-sorted layout. Single-window runs read the full
        layout directly (it is the slab)."""
        fc = jnp.clip(fn, 0, self.F - 1)
        if self.single_win:
            gi = self._off[self._b_f1 + fc] + pos
            return self._pos[self._b_n + jnp.clip(gi, 0, self.N - 1)]
        lo = self._cc_lo[self._b_f + fc]
        inw = (pos >= lo) & (pos < self._cc_hi[self._b_f + fc])
        si = self._offw[self._b_f + fc] + (pos - lo)
        sv = self._pos_s[self._b_w
                         + jnp.where(inw, jnp.clip(si, 0, self.W - 1),
                                     0)]
        gi = self._off[self._b_f1 + fc] + pos
        fv = self._pos[self._b_n
                       + jnp.where(inw, 0, jnp.clip(gi, 0, self.N - 1))]
        return jnp.where(inw, sv, fv)

    # ------------------------------------------------- overridable ops
    # The queue discipline and the estimator's fallback chain are ctx
    # *methods* so an alternative engine (the multi-node cluster loop,
    # `repro.cluster.engine`) can substitute its own carried layout —
    # linked-list per-(node, function) queues, per-node estimator
    # globals — while policy kernels keep calling the same module-level
    # helpers (`q_push`/`q_pop`/`q_head`/`q_consume_direct`/
    # `est_means`), which delegate here.

    def est_means(self, s):
        """Per-function running means with global-mean / prior
        fallback."""
        counts = s["est_n"].astype(jnp.float64)
        g_n = s["ci"][CI_GN]
        gcount = g_n.astype(jnp.float64)
        g = jnp.where(g_n > 0,
                      s["cf"][CF_GSUM] / jnp.maximum(gcount, 1),
                      self.prior)
        return jnp.where(s["est_n"] > 0,
                         s["est_sum"] / jnp.maximum(counts, 1), g)

    def q_head(self, s, fn):
        """Request id at the head of ``fn``'s queue (garbage when
        empty — callers gate on ``q_len``). Served from the carried
        q_head_rid cache so head reads — including the central-queue
        (F,) head scan — cost no gathers into the big positional
        operand."""
        return s["q_head_rid"][jnp.clip(fn, 0, self.F - 1)]

    def q_push(self, s, fn, rid, on):
        """Append ``rid``; returns (state, pushed). The pushed request
        is by construction the next arrival position of ``fn``, so only
        the length moves (plus the head cache when the queue was
        empty). A push onto a full backlog (q_len == queue_cap) is
        dropped and counted in overflow."""
        fc = jnp.clip(fn, 0, self.F - 1)
        was_empty = s["q_len"][fc] == 0
        full = s["q_len"][fc] >= self.Q
        do = on & ~full
        s = dict(s)
        s["q_head_rid"] = _put(s["q_head_rid"],
                               _gidx(do & was_empty, fn, self.F), rid)
        s["q_len"] = _bump(s["q_len"], _gidx(do, fn, self.F), 1)
        s["ci"] = _bump(s["ci"], CI_OVF, on & full)
        return s, do

    def q_consume_direct(self, s, fn, on):
        """Account a directly dispatched arrival: its (empty-queue)
        head position is consumed without ever being enqueued. The head
        cache stays stale-but-gated (q_len == 0) until the next push
        rewrites it."""
        s = dict(s)
        s["q_head_pos"] = _bump(s["q_head_pos"], _gidx(on, fn, self.F),
                                1)
        return s

    def q_pop(self, s, fn, on):
        """Consume the head of ``fn``'s queue; returns (state, rid).
        The one positional gather refreshes the head cache with the
        successor (garbage when the queue empties — reads gate on
        q_len)."""
        fc = jnp.clip(fn, 0, self.F - 1)
        rid = s["q_head_rid"][fc]
        succ = self.rid_at_pos(fc, s["q_head_pos"][fc] + 1)
        fi = _gidx(on, fn, self.F)
        s = dict(s)
        s["q_head_rid"] = _put(s["q_head_rid"], fi, succ)
        s["q_head_pos"] = _bump(s["q_head_pos"], fi, 1)
        s["q_len"] = _bump(s["q_len"], fi, -1)
        return s, rid

    def arm_timer(self, s, fn, rid, t, pushed, on):
        """Account the original timer of an arrival (position
        ``arr_cnt - 1`` of the positional timer rail; ``rid`` is
        redundant here — the position identifies the request — but the
        cluster's rid-chain rail needs it). See the module-level
        `arm_timer` for the semantics."""
        fc = jnp.clip(fn, 0, self.F - 1)
        rail_head = s["tmr_pos"][fc] == s["arr_cnt"][fc] - 1
        s = dict(s)
        s["tmr_next"] = _put(s["tmr_next"],
                             _gidx(on & rail_head & pushed, fn, self.F),
                             t + self.threshold)
        s["tmr_pos"] = _bump(s["tmr_pos"],
                             _gidx(on & rail_head & ~pushed, fn, self.F),
                             1)
        return s


class ResilCtx(EngineCtx):
    """Engine ctx under the resilience layer (fail_prob / timeouts /
    retries / shedding).

    Retries re-enqueue an old rid, which breaks the positional-cursor
    queue invariant (each arrival consumes exactly one position, once),
    so the per-function queues switch to the direct rid-link layout the
    cluster's churn loop uses: a shared ``nxt`` (N,) successor array
    (a rid is queued XOR running XOR awaiting retry XOR terminal, so
    one link array serves both the function queues and the retry rail)
    plus carried ``q_tail_rid``. Resilience runs are forced
    single-window for the same reason (a retried rid can be arbitrarily
    far behind the arrival cursor), so the dual-source reads are the
    flat fast path anyway.

    The pre-planned outcome operands (`repro.core.resilience
    .plan_outcomes`) ride three (T, N) rows: ``nfail_at`` (leading
    failed attempts), ``tmo_at`` (the failure is a timeout) and
    ``key_at`` (the request's *original* trace id — the jitter hash
    key, so sliced/renumbered sub-streams draw identically)."""

    def __init__(self, *, nfail2, tmo2, key2, resil, **kw):
        super().__init__(**kw)
        self._nf = nfail2.reshape(-1)
        self._tm = tmo2.reshape(-1)
        self._ky = key2.reshape(-1)
        self.resil = resil  # (max_attempts, shed_mode, base, cap,
        self.has_resil = True            # jitter, fail_seed) — static
        self.fold_at_dispatch = False    # fold successes at EXEC_DONE
        self.direct_records = True       # re-dispatches break the d_*
        self.defer_completion = True     # overlay; completion on success

    def nfail_at(self, rid):
        return self._nf[self._b_n + jnp.clip(rid, 0, self.N - 1)]

    def tmo_at(self, rid):
        return self._tm[self._b_n + jnp.clip(rid, 0, self.N - 1)]

    def key_at(self, rid):
        return self._ky[self._b_n + jnp.clip(rid, 0, self.N - 1)]

    def q_push(self, s, fn, rid, on):
        """Direct-link append with the admission-control modes: a push
        onto a full backlog drops-and-counts (``error``, the legacy
        invalid-run behaviour), sheds the arriving request
        (``shed`` — it becomes terminal, never admitted) or evicts the
        queue head to admit the newcomer (``shed_oldest``)."""
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
            s["q_head_rid"] = _put(s["q_head_rid"], fi, hsucc)
            s["q_len"] = _bump(s["q_len"], fi, -1)
            ev_i = evict.astype(jnp.int32)
            s["ci"] = _bumps(s["ci"], {CI_SHED: ev_i, CI_TERM: ev_i})
            do = on
            was_empty = (len0 - ev_i) == 0
        else:
            do = on & ~full
            was_empty = len0 == 0
            if mode == 1:  # shed the arriving request
                sh_i = (on & full).astype(jnp.int32)
                s["ci"] = _bumps(s["ci"], {CI_SHED: sh_i, CI_TERM: sh_i})
            else:
                s["ci"] = _bump(s["ci"], CI_OVF, on & full)
        tail = s["q_tail_rid"][fc]
        s["q_head_rid"] = _put(s["q_head_rid"],
                               _gidx(do & was_empty, fn, self.F), rid32)
        s["nxt"] = s["nxt"].at[
            _gidx(do & ~was_empty, tail, self.N)].set(rid32,
                                                      mode="drop")
        s["q_tail_rid"] = _put(s["q_tail_rid"], _gidx(do, fn, self.F),
                               rid32)
        s["q_len"] = _bump(s["q_len"], _gidx(do, fn, self.F), 1)
        return s, do

    def q_consume_direct(self, s, fn, on):
        """Direct links carry no positional cursor — nothing to
        account for a straight-to-slot arrival."""
        return s

    def q_pop(self, s, fn, on):
        fc = jnp.clip(fn, 0, self.F - 1)
        rid = s["q_head_rid"][fc]
        succ = s["nxt"][jnp.clip(rid, 0, self.N - 1)]
        fi = _gidx(on, fn, self.F)
        s = dict(s)
        s["q_head_rid"] = _put(s["q_head_rid"], fi, succ)
        s["q_len"] = _bump(s["q_len"], fi, -1)
        return s, rid


class PolicyKernel:
    """Interface a vectorised policy implements over the engine state.

    Each hook is a pure function ``state -> state`` gated by an ``on``
    predicate (guarded-write style — hooks run every iteration, their
    writes are masked); the engine has already done the
    policy-independent bookkeeping — cursor advance for arrivals,
    estimator update + slot release for exec-done, slot release for
    cold-done, timer consumption for timers — exactly mirroring
    `repro.core.simulator.simulate`.

    Queue contract: every enabled ``on_arrival`` must consume exactly
    one queue position of the request's function — `q_push` when it
    queues, `q_consume_direct` when it dispatches the arrival straight
    to a slot — so the positional queues stay contiguous.
    """

    name = "base"
    has_timers = False
    default_beta = 1.0

    def extra_state(self, L, C, F) -> Dict[str, jnp.ndarray]:
        """Kernel-private carried arrays (leading L), e.g. FaasCache's
        per-slot GREEDY-DUAL bookkeeping. Keys must not collide with
        the engine's."""
        return {}

    def on_arrival(self, ctx, s, rid, t, on):
        raise NotImplementedError

    def on_cold_done(self, ctx, s, slot, t, on):
        raise NotImplementedError

    def on_exec_done(self, ctx, s, slot, rid, t, on):
        raise NotImplementedError

    def on_timer(self, ctx, s, rid, t, on):  # pragma: no cover
        return s


# --------------------------------------------------------------- helpers
def _gidx(on, idx, size):
    """Guarded scatter index: ``idx`` when enabled and valid, else an
    out-of-bounds sentinel that ``mode="drop"`` discards."""
    return jnp.where(on & (idx >= 0), idx, size)


def _hit(x, i):
    """One-hot mask of row ``i`` over the leading axis of ``x``,
    broadcast over its trailing axes; all-false when ``i`` is out of
    range (the `_gidx` sentinel)."""
    h = jnp.arange(x.shape[0], dtype=jnp.int32) == i
    return h.reshape(h.shape + (1,) * (x.ndim - 1))


def _put(x, i, v):
    """``x.at[i].set(v, mode="drop")`` as a one-hot select.

    Every per-event write to a small carried array (O(F), O(C), the
    packed counters, the per-segment overlays) goes through `_put` /
    `_bump` instead of a scatter. A select updates no buffer in place,
    so copy insertion puts no ordering edges around it: those edges
    are what the TPU compiler's loop analysis aborts on (see
    `LOOP_COMPILER_OPTIONS`), and with these writes as selects the
    no-fault single-node loop compiles even with that analysis on. It
    writes the same bits as the scatter on every backend."""
    return jnp.where(_hit(x, i), jnp.asarray(v, x.dtype), x)


def _bump(x, i, v):
    """``x.at[i].add(v, mode="drop")`` as a one-hot select (see
    `_put`)."""
    return jnp.where(_hit(x, i), x + jnp.asarray(v, x.dtype), x)


def _bumps(x, deltas):
    """`_bump` at several static indices: ``{index: value}``."""
    for i, v in deltas.items():
        x = _bump(x, i, v)
    return x


def lex_argmin(primary, secondary, valid):
    """First index minimising ``(primary, secondary)`` among ``valid``.

    Reproduces the Python engine's deterministic scans: iterate in
    ``secondary`` (creation / fn-id) order, keep on strict improvement.
    """
    p = jnp.where(valid, primary, BIG)
    tie = valid & (p <= jnp.min(p))
    return jnp.argmin(jnp.where(tie, secondary, I32_MAX))


def argmin_i32(vals, valid):
    """First valid index minimising an i32 key (sentinel-masked)."""
    return jnp.argmin(jnp.where(valid, vals, I32_MAX))


def est_means(ctx, s):
    """Per-function running means with global-mean / prior fallback
    (delegates to the ctx so cluster node views can rebind the
    globals)."""
    return ctx.est_means(s)


def _fn_histogram(ctx, s, mask):
    """Per-function count of the slots in ``mask``, as an (F,) int32.

    A dense one-hot compare over the slot axis, summed, and not a
    scatter-add: XLA:TPU serializes a scatter over its L x C updates
    under the lane vmap, and the ESFF kernels count on every event
    step. Integer counts, so a scatter-add gives the same bits. The
    (C, F) compare grows with F; a caller that needs one function's
    count takes `fn_count` instead, which stays O(C)."""
    fns = jnp.arange(ctx.F, dtype=jnp.int32)
    hit = (s["slot_fn"][:, None] == fns[None, :]) & mask[:, None]
    return hit.sum(0, dtype=jnp.int32)


def k_counts(ctx, s):
    """|K^j| — slots assigned to each function, any state."""
    return _fn_histogram(ctx, s, s["slot_fn"] >= 0)


def cold_counts(ctx, s):
    """Slots currently warming up (state COLD) per function."""
    return _fn_histogram(ctx, s, (s["slot_fn"] >= 0)
                         & (s["slot_state"] == COLD))


def fn_count(s, fn, cold=False):
    """Entry ``fn`` of `k_counts` (``cold``: of `cold_counts`) for one
    function ``fn`` in [0, F): a masked sum over the (C,) slot rail."""
    hit = s["slot_fn"] == fn
    if cold:
        hit = hit & (s["slot_state"] == COLD)
    return hit.sum(dtype=jnp.int32)


def idle_own(ctx, s, fn):
    """Mask of usable idle slots already resident with ``fn``."""
    return ((s["slot_fn"] == fn) & (s["slot_state"] == IDLE)
            & ctx.cap_mask)


def pick_idle_own(ctx, s, fn):
    """(mask.any(), earliest-created idle own slot) — Python's
    ``idle_of`` picks the lowest ``inst_id``."""
    mask = idle_own(ctx, s, fn)
    return mask.any(), argmin_i32(s["slot_seq"], mask)


def q_head(ctx, s, fn):
    """Head request id of ``fn``'s queue (ctx-dispatched)."""
    return ctx.q_head(s, fn)


def q_push(ctx, s, fn, rid, on):
    """Append ``rid``; returns (state, pushed) (ctx-dispatched)."""
    return ctx.q_push(s, fn, rid, on)


def q_consume_direct(ctx, s, fn, on):
    """Account a directly dispatched arrival (ctx-dispatched)."""
    return ctx.q_consume_direct(s, fn, on)


def q_pop(ctx, s, fn, on):
    """Consume the head of ``fn``'s queue; returns (state, rid)
    (ctx-dispatched)."""
    return ctx.q_pop(s, fn, on)


def arm_timer(ctx, s, fn, rid, t, pushed, on):
    """Account the original timer of the arrival ``rid`` (the newest
    entry of ``fn``'s timer rail; ctx-dispatched).

    The rail covers every arrival in order. If the rail is idle (this
    arrival is its head) a *pushed* arrival arms the head fire time,
    while a directly dispatched one is consumed silently; a direct
    dispatch behind a busy rail stays armed and later fires as a no-op
    (its is-head gate fails), mirroring how the Python policy drops
    timers of already-served requests."""
    return ctx.arm_timer(s, fn, rid, t, pushed, on)


def rearm_timer(ctx, s, fn, rid, t_fire, on):
    """Re-arm the (unique) blocked queue head of ``fn`` at ``t_fire``."""
    fi = _gidx(on, fn, ctx.F)
    s = dict(s)
    s["rearm_t"] = _put(s["rearm_t"], fi, t_fire)
    s["rearm_rid"] = _put(s["rearm_rid"], fi, rid)
    return s


def dispatch(ctx, s, slot, rid, t, on):
    """Run ``rid`` on an idle ``slot`` of its function.

    The streaming metrics (response/slowdown sums, max, histogram and
    the optional timeline bins) are folded *per event*: each dispatch
    site only records the (rid, completion, exec) triple in the
    per-event ``ev_*`` registers — three cheap selects, no scatters —
    and the engine applies the fold once at the end of the event
    (`_fold_event`). The accumulation order is then exactly the event
    order, which makes the streamed sums bitwise invariant to the
    window size (a deferred batch fold would regroup the reduction
    tree wherever a window boundary cuts a segment), and both modes
    share the fold so streamed means stay bit-identical to exact-mode
    means. At most one dispatch happens per event (call sites are
    mutually exclusive), so the registers cannot clobber a live
    record.

    In exact mode the per-request start/completion record additionally
    goes into the segment overlay (d_*), batch-scattered into the
    (L, N) result arrays once per SEG-event segment; the overlay slot
    is indexed by the segment step and disabled sites drop instead of
    clobbering it."""
    s = dict(s)
    e = ctx.exec_at(rid)
    comp = t + e
    si = _gidx(on, slot, ctx.C)
    s["slot_state"] = _put(s["slot_state"], si, BUSY)
    s["slot_ready"] = _put(s["slot_ready"], si, comp)
    s["slot_req"] = _put(s["slot_req"], si, rid)
    s["slot_used"] = _put(s["slot_used"], si, t)
    if ctx.has_resil:
        # attempt counter: incremented when the request starts running,
        # read back at its EXEC_DONE to classify the outcome
        s["att"] = s["att"].at[_gidx(on, rid, ctx.N)].add(1,
                                                          mode="drop")
    if ctx.fold_at_dispatch:
        s["ev_rid"] = jnp.where(on, jnp.asarray(rid, jnp.int32),
                                s["ev_rid"])
        s["ev_comp"] = jnp.where(on, comp, s["ev_comp"])
        s["ev_exec"] = jnp.where(on, e, s["ev_exec"])
    if not ctx.stream:
        if ctx.direct_records:
            # churn can re-dispatch a drained rid within one segment;
            # the overlay's one-slot-per-rid assumption breaks, so pay
            # a per-event scatter (last write wins, matching the
            # reference's completion rewrite)
            ri = _gidx(on, rid, ctx.N)
            s["start"] = s["start"].at[ri].set(t, mode="drop")
            if not ctx.defer_completion:
                s["completion"] = s["completion"].at[ri].set(
                    comp, mode="drop")
        else:
            ki = jnp.where(on, ctx.k, ctx.seg_n)
            s["d_rid"] = _put(s["d_rid"], ki, rid)
            s["d_start"] = _put(s["d_start"], ki, t)
            s["d_comp"] = _put(s["d_comp"], ki, comp)
    return s


def _fold_event(ctx, s):
    """End-of-event metric fold of the ``ev_*`` dispatch registers
    (see `dispatch`): one arrival gather + one histogram bin per
    event, applied in event order so the streamed accumulators are
    bitwise window-size invariant. Consumes (pops) the registers."""
    s = dict(s)
    rid = s.pop("ev_rid")
    comp = s.pop("ev_comp")
    e = s.pop("ev_exec")
    on = rid >= 0
    arr = ctx.arrival_at(rid)
    resp = comp - arr
    slow = resp / jnp.maximum(e, 1e-9)
    cf = _bumps(s["cf"], {CF_RSUM: jnp.where(on, resp, 0.0),
                          CF_SSUM: jnp.where(on, slow, 0.0)})
    s["cf"] = jnp.where(_hit(cf, CF_RMAX),
                        jnp.maximum(cf, jnp.where(on, resp, 0.0)), cf)
    s["hist"] = _bump(s["hist"], jnp.where(on, hist_bin(resp),
                                           jnp.int32(HIST_BINS)), 1)
    if ctx.deadlines is not None:
        fnr = ctx.fn_at(rid)
        dl = ctx.deadlines[jnp.clip(fnr, 0, ctx.F - 1)]
        s["dl_miss"] = _bump(s["dl_miss"],
                             _gidx(on & (resp > dl), fnr, ctx.F), 1)
    if ctx.tl_bins:
        tb = jnp.clip((arr / ctx.tl_bucket).astype(jnp.int32),
                      0, ctx.tl_bins - 1)
        ti = jnp.where(on, tb, jnp.int32(ctx.tl_bins))
        s["tl_cnt"] = _bump(s["tl_cnt"], ti, 1)
        s["tl_resp"] = _bump(s["tl_resp"], ti, resp)
        s["tl_exec"] = _bump(s["tl_exec"], ti, e)
    return s


def start_cold(ctx, s, slot, fn, t, evict_fn, on):
    """Claim/convert ``slot`` for ``fn`` (``evict_fn`` = -1 -> empty slot,
    otherwise the resident function paying its eviction cost first)."""
    s = dict(s)
    fn = jnp.asarray(fn, jnp.int32)  # argmin/argmax indices are i64
    evict_fn = jnp.asarray(evict_fn, jnp.int32)
    fc = jnp.clip(fn, 0, ctx.F - 1)
    evicting = on & (evict_fn >= 0)
    ev_cost = jnp.where(evicting,
                        ctx.t_evict[jnp.clip(evict_fn, 0, ctx.F - 1)],
                        0.0)
    si = _gidx(on, slot, ctx.C)
    s["slot_fn"] = _put(s["slot_fn"], si, fn)
    s["slot_state"] = _put(s["slot_state"], si, COLD)
    s["slot_ready"] = _put(s["slot_ready"], si,
                           t + ctx.t_cold[fc] + ev_cost)
    s["slot_req"] = _put(s["slot_req"], si, -1)
    s["slot_used"] = _put(s["slot_used"], si, 0.0)
    s["slot_seq"] = _put(s["slot_seq"], si, s["ci"][CI_SEQ])
    s["ci"] = _bumps(s["ci"], {CI_SEQ: on, CI_COLD: on,
                               CI_EVICT: evicting})
    s["cf"] = _bumps(s["cf"], {CF_COLDT: jnp.where(on, ctx.t_cold[fc],
                                                   0.0),
                               CF_EVICTT: ev_cost})
    return s


def pick_victims(ctx, s, key, fn, first=None):
    """Memory mode: the idle slots a cold start of ``fn`` evicts, as
    OpenWhisk's container pool admits one. Returns ``(victims, fits,
    slot)``: the (C,) victim mask, whether the footprint fits, and the
    slot the new instance takes.

    Victims are the shortest prefix of the idle slots, ordered by
    ``(key, slot_seq)``, whose footprints cover the shortfall ``mem_mb[fn]
    - free``: none when free memory suffices, and none when even every
    idle slot would not free enough (``fits`` False). ``first`` (a slot:
    `_serve_or_replace`'s freed instance) is evicted before any other.
    The prefix is a dense rank, not a sort: a slot is in it when the
    idle slots ranked before it fall short of the need. The new instance
    takes ``first``, else the first victim's slot, else the first empty
    one."""
    fc = jnp.clip(fn, 0, ctx.F - 1)
    foot = ctx.mem_mb[jnp.clip(s["slot_fn"], 0, ctx.F - 1)]
    idle = (s["slot_state"] == IDLE) & (s["slot_fn"] >= 0) & ctx.cap_mask
    need = ctx.mem_mb[fc] - s["mc"][MC_FREE]
    if first is not None:
        lead = jnp.arange(ctx.C, dtype=jnp.int32) == first
        need = need - foot[jnp.clip(first, 0, ctx.C - 1)]
        idle = idle & ~lead
    seq = s["slot_seq"]
    # before[i, j]: idle slot j ranks ahead of slot i
    before = idle[None, :] & (
        (key[None, :] < key[:, None])
        | ((key[None, :] == key[:, None]) & (seq[None, :] < seq[:, None])))
    ahead = jnp.where(before, foot[None, :], 0).sum(1)
    fits = jnp.where(idle, foot, 0).sum() >= need
    victims = idle & (ahead < need) & fits
    if first is not None:
        return victims | (lead & fits), fits, first
    empty = (s["slot_fn"] < 0) & ctx.cap_mask
    slot = jnp.where(victims.any(), lex_argmin(key, seq, victims),
                     jnp.argmax(empty))
    return victims, fits, slot


def start_cold_mem(ctx, s, slot, fn, t, victims, on):
    """Memory mode's `start_cold`: evict every slot of the ``victims``
    mask and start ``fn`` in ``slot`` (see `pick_victims`). The evict
    delay and `evictions` are summed over the victims; free memory gains
    their footprints and loses ``fn``'s."""
    s = dict(s)
    fn = jnp.asarray(fn, jnp.int32)
    fc = jnp.clip(fn, 0, ctx.F - 1)
    vic = victims & on
    resident = jnp.clip(s["slot_fn"], 0, ctx.F - 1)
    ev_cost = jnp.where(vic, ctx.t_evict[resident], 0.0).sum()
    ev_mb = jnp.where(vic, ctx.mem_mb[resident], 0).sum()
    n_vic = vic.sum(dtype=jnp.int32)
    # victims are idle: their slots go back to the empty state
    s["slot_fn"] = jnp.where(vic, -1, s["slot_fn"])
    s["slot_used"] = jnp.where(vic, 0.0, s["slot_used"])
    s["slot_seq"] = jnp.where(vic, I32_MAX, s["slot_seq"])
    si = _gidx(on, slot, ctx.C)
    s["slot_fn"] = _put(s["slot_fn"], si, fn)
    s["slot_state"] = _put(s["slot_state"], si, COLD)
    s["slot_ready"] = _put(s["slot_ready"], si,
                           t + ctx.t_cold[fc] + ev_cost)
    s["slot_req"] = _put(s["slot_req"], si, -1)
    s["slot_seq"] = _put(s["slot_seq"], si, s["ci"][CI_SEQ])
    s["ci"] = _bumps(s["ci"], {CI_SEQ: on, CI_COLD: on, CI_EVICT: n_vic})
    s["cf"] = _bumps(s["cf"], {CF_COLDT: jnp.where(on, ctx.t_cold[fc],
                                                   0.0),
                               CF_EVICTT: ev_cost})
    s["mc"] = _bumps(s["mc"], {
        MC_FREE: jnp.where(on, ev_mb - ctx.mem_mb[fc], 0),
        MC_EVICT_MB: ev_mb, MC_MULTI: n_vic >= 2})
    return s


# ----------------------------------------------------- streaming metrics
def hist_edges() -> np.ndarray:
    """Bin edges (HIST_BINS + 1,) of the streaming response histogram."""
    return 10.0 ** (HIST_LO
                    + np.arange(HIST_BINS + 1) / HIST_PER_DECADE)


def hist_bin(resp):
    """Log-spaced bin index of a (batch of) response time(s)."""
    b = jnp.floor((jnp.log10(jnp.maximum(resp, 1e-30)) - HIST_LO)
                  * HIST_PER_DECADE)
    return jnp.clip(b, 0, HIST_BINS - 1).astype(jnp.int32)


def hist_quantile(hist, q, n, resp_max=None):
    """Upper edge of the bin containing the q-quantile of ``n`` folded
    responses — exact to one bin width (~1.33x).

    The edge bins also hold everything clipped past the histogram
    range, so their edges would silently misstate out-of-range tails;
    with ``resp_max`` (the exact carried maximum) the result is never
    range-capped: a quantile in the top bin reports the maximum itself,
    and any bin's edge is clamped to it (which makes all-fast traces —
    every response under the 1e-4 s floor — report the true tail
    instead of the floor edge). The reported value always upper-bounds
    the true quantile; only a distribution almost entirely below the
    floor with large outliers can push it past one bin of the truth."""
    cum = jnp.cumsum(hist, axis=-1)
    need = jnp.ceil(q * n).astype(cum.dtype)
    b = jnp.argmax(cum >= need, axis=-1)
    edge = jnp.asarray(hist_edges())[b + 1]
    if resp_max is None:
        return edge
    return jnp.where(b >= HIST_BINS - 1, resp_max,
                     jnp.minimum(edge, resp_max))


def hist_cdf(hist):
    """(edges, cdf) arrays for plotting a CDF from the streamed
    histogram (exact to one bin width)."""
    h = np.asarray(hist, np.float64)
    cum = h.cumsum(axis=-1)
    total = np.maximum(cum[..., -1:], 1.0)
    return hist_edges()[1:], cum / total


# ------------------------------------------------------------ event loop
@functools.partial(jax.jit,
                   static_argnames=("kernel", "n_fns", "capacity",
                                    "queue_cap", "stream", "window",
                                    "tl_bins", "resil", "trace"))
def _simulate(fn_id, arrival, exec_time, t_cold, t_evict, trace_ix,
              cap_mask, beta, prior, threshold, n_live=None,
              deadlines=None, rs_nfail=None, rs_tmo=None, rs_key=None,
              mem_mb=None, node_mb=None, *, kernel, n_fns, capacity, queue_cap,
              stream=False, window=0, tl_bins=0, tl_bucket=60.0,
              resil=None, trace=False):
    """Lane-batched engine. Trace arrays are shared (T, ...) operands;
    ``trace_ix``, ``cap_mask`` and ``beta`` carry the leading lane
    dimension L (one lane per sweep point). The loop nest is windows ->
    segments -> events (see the module docstring): per window the
    shared operands are re-sliced into L2-resident slabs, and within a
    window one ``while_loop`` runs all lanes in segments of SEG events
    with the branchless per-event body vmapped per lane (finished and
    parked lanes no-op via their guards).

    ``stream=True`` drops the (L, N) per-request result arrays: each
    event folds its dispatch record into the per-lane metric
    accumulators (`_fold_event`), so carried state is independent of N.
    ``window`` (static; 0 -> `DEFAULT_WINDOW`) sets the slab size and
    never changes results, only locality. ``tl_bins > 0`` adds the
    minute-binned timeline fold (bucket width ``tl_bucket`` seconds).

    ``n_live`` ((L,) i32, optional) caps how many leading requests of
    each lane's trace row are real: a lane completes once its first
    ``n_live`` requests have finished and never consumes the padding
    tail. This is what lets ragged request streams — the per-node
    sub-streams of `repro.cluster`'s static routing path — share one
    padded (T, N) operand without recompilation per length. ``None``
    (every existing caller) means all N requests are live.

    ``resil`` (static: ``(max_attempts, shed_mode, base, cap, jitter,
    fail_seed)``, or None) enables the request-resilience layer; the
    pre-planned outcome operands ``rs_nfail`` / ``rs_tmo`` / ``rs_key``
    ((T, N), see `repro.core.resilience.plan_outcomes` and `ResilCtx`)
    then ride along. With ``resil=None`` — every no-fault spec — none
    of the resilience code is traced and the loop lowers bitwise
    unchanged. A lane is finished when every live request is
    *terminal* (done, shed, or retry-exhausted), counted in CI_TERM.

    ``trace`` (static) enables the telemetry event-trace rail
    (`repro.telemetry.rail`): every processed event stages a
    fixed-width record into an (L, SEG, ·) overlay, flushed to the
    host sink once per segment through an ordered ``io_callback``.
    ``trace=False`` traces none of it — the loop lowers bitwise onto
    the unchanged program, exactly like the other optional rails.

    ``mem_mb`` ((T, F) i32 footprints) and ``node_mb`` ((L,) i32 user
    memory per lane) make the node's resource memory (`pick_victims`,
    `start_cold_mem`; only `MEMORY_POLICIES`' kernels). ``capacity``
    is then the slot array's length, which the runner sizes to hold the
    largest lane's instances, and ``cap_mask`` each lane's share of it.
    Every launch then also reports ``evict_mb``,
    ``multi_victim_starts`` and ``mem_waits`` per lane. With ``mem_mb``
    None none of it is traced.
    """
    L = trace_ix.shape[0]
    T_ = fn_id.shape[0]
    N = fn_id.shape[1]
    F, C, Q = n_fns, capacity, queue_cap
    nl = (jnp.full((L,), N, jnp.int32) if n_live is None
          else jnp.asarray(n_live, jnp.int32))

    has_mem = mem_mb is not None
    has_resil = resil is not None
    if has_resil:
        if kernel.has_timers:
            raise NotImplementedError(
                "resilience (fail_prob/timeouts/retries) does not "
                "support timer-rail kernels (openwhisk_v2) — the "
                "positional timer rail assumes each arrival position "
                "is consumed exactly once, which retries break")
        max_att, shed_mode, rt_base, rt_cap, rt_jit, rt_seed = resil
        rs_nfail = rs_nfail.astype(jnp.int32)
        rs_tmo = rs_tmo.astype(bool)
        rs_key = rs_key.astype(jnp.int32)

    W = int(window) if window else DEFAULT_WINDOW
    W = max(1, min(W, N))
    if has_resil:
        # a retried rid can trail the arrival cursor by any distance,
        # so the 2-source window-slab invariant doesn't hold; run the
        # whole trace as one window (results are window-invariant)
        W = N
    n_win = -(-N // W)
    NP = n_win * W

    fn_id = fn_id.astype(jnp.int32)
    arrival = arrival.astype(jnp.float64)
    exec_time = exec_time.astype(jnp.float64)
    t_cold = t_cold.astype(jnp.float64)
    t_evict = t_evict.astype(jnp.float64)
    trace_ix = trace_ix.astype(jnp.int32)
    prior = jnp.float64(prior)
    threshold = jnp.float64(threshold)
    tl_bucket = jnp.float64(tl_bucket)

    # positional queue layout (loop-invariant): request ids sorted by
    # (fn, id) + per-function offsets — fn j's k-th arrival is
    # pos_rids[pos_off[j] + k]
    pos_rids = jnp.argsort(fn_id, axis=1, stable=True).astype(jnp.int32)
    counts = jax.vmap(
        lambda row: jnp.zeros((F,), jnp.int32).at[
            jnp.clip(row, 0, F - 1)].add(1))(fn_id)
    pos_off = jnp.concatenate(
        [jnp.zeros((counts.shape[0], 1), jnp.int32),
         jnp.cumsum(counts, axis=1)], axis=1)

    # window-major operands: the trace padded to n_win * W (so slab
    # slices never clamp) plus a second positional layout sorted by
    # (window, fn, id) — window w's block is rows [w*W, (w+1)*W), with
    # per-window per-fn offsets off_w and exclusive prefix counts
    # cum_cnt (fn j's positions in window w are [cum_cnt[w], cum_cnt[w+1])).
    # Single-window runs (W >= N) skip all of it statically — the full
    # operands are the slab and every windowed read takes its fast path.
    single_win = n_win == 1
    if not single_win:
        pad = NP - N
        fn_pad = jnp.pad(fn_id, ((0, 0), (0, pad)))
        arr_pad = jnp.pad(arrival, ((0, 0), (0, pad)),
                          constant_values=BIG)
        ex_pad = jnp.pad(exec_time, ((0, 0), (0, pad)))
        win_key = ((jnp.arange(N, dtype=jnp.int32) // W)[None] * F
                   + fn_id)
        pos_w = jnp.pad(
            jnp.argsort(win_key, axis=1, stable=True).astype(jnp.int32),
            ((0, 0), (0, pad)))
        wcnt = jax.vmap(
            lambda kr: jnp.zeros((n_win * F,), jnp.int32).at[kr].add(1)
        )(win_key).reshape(T_, n_win, F)
        off_w = jnp.concatenate(
            [jnp.zeros((T_, n_win, 1), jnp.int32),
             jnp.cumsum(wcnt, axis=2)[:, :, :-1]], axis=2)
        cum_cnt = jnp.concatenate(
            [jnp.zeros((T_, 1, F), jnp.int32),
             jnp.cumsum(wcnt, axis=1)], axis=1)

    s = dict(
        slot_fn=jnp.full((L, C), -1, jnp.int32),
        slot_state=jnp.full((L, C), IDLE, jnp.int32),
        slot_ready=jnp.full((L, C), BIG, jnp.float64),
        slot_req=jnp.full((L, C), -1, jnp.int32),
        slot_used=jnp.zeros((L, C), jnp.float64),
        slot_seq=jnp.full((L, C), I32_MAX, jnp.int32),
        q_head_pos=jnp.zeros((L, F), jnp.int32),
        q_head_rid=jnp.full((L, F), -1, jnp.int32),
        q_len=jnp.zeros((L, F), jnp.int32),
        est_sum=jnp.zeros((L, F), jnp.float64),
        est_n=jnp.zeros((L, F), jnp.int32),
        ci=jnp.zeros((L, NCI), jnp.int32),
        cf=jnp.zeros((L, NCF), jnp.float64),
        hist=jnp.zeros((L, HIST_BINS), jnp.int32),
    )
    if not stream:
        s["d_rid"] = jnp.full((L, SEG), N, jnp.int32)
        s["d_start"] = jnp.zeros((L, SEG), jnp.float64)
        s["d_comp"] = jnp.zeros((L, SEG), jnp.float64)
        s["start"] = jnp.full((L, N), -1.0, jnp.float64)
        s["completion"] = jnp.full((L, N), -1.0, jnp.float64)
    if deadlines is not None:
        deadlines = jnp.asarray(deadlines, jnp.float64)
        s["dl_miss"] = jnp.zeros((L, F), jnp.int32)
    if tl_bins:
        s["tl_cnt"] = jnp.zeros((L, tl_bins), jnp.int32)
        s["tl_resp"] = jnp.zeros((L, tl_bins), jnp.float64)
        s["tl_exec"] = jnp.zeros((L, tl_bins), jnp.float64)
    if kernel.has_timers:
        s["arr_cnt"] = jnp.zeros((L, F), jnp.int32)
        s["tmr_pos"] = jnp.zeros((L, F), jnp.int32)
        s["tmr_next"] = jnp.full((L, F), BIG, jnp.float64)
        s["rearm_t"] = jnp.full((L, F), BIG, jnp.float64)
        s["rearm_rid"] = jnp.full((L, F), -1, jnp.int32)
    if has_resil:
        # direct-link queues (ResilCtx) + the retry FIFO rail: one
        # shared successor array serves both chains (a rid is in at
        # most one), the rail carries head/tail/len and the head fire
        # time (BIG when empty). rt_t holds each waiter's eligible
        # time; a head promoted behind a later-firing predecessor is
        # clamped to the pop time (no overtaking within the rail).
        s["q_tail_rid"] = jnp.full((L, F), -1, jnp.int32)
        s["nxt"] = jnp.full((L, N), -1, jnp.int32)
        s["att"] = jnp.zeros((L, N), jnp.int32)
        s["rt_t"] = jnp.zeros((L, N), jnp.float64)
        s["r_head"] = jnp.full((L,), -1, jnp.int32)
        s["r_tail"] = jnp.full((L,), -1, jnp.int32)
        s["r_len"] = jnp.zeros((L,), jnp.int32)
        s["r_fire"] = jnp.full((L,), BIG, jnp.float64)
    if trace:
        from repro.telemetry.rail import TR_RF, TR_RI
        s["tr_i"] = jnp.full((L, SEG, TR_RI), -1, jnp.int32)
        s["tr_f"] = jnp.zeros((L, SEG, TR_RF), jnp.float64)
    if has_mem:
        s["mc"] = jnp.zeros((L, NMC), jnp.int32).at[:, MC_FREE].set(
            jnp.asarray(node_mb, jnp.int32))
    s.update(kernel.extra_state(L, C, F))
    # lane-stacked loop iterations the device runs: SEG per segment,
    # counting parked spins and finished lanes (`n_events` counts only
    # processed events)
    s["loop_steps"] = jnp.int32(0)

    max_iters = (256 * N + 4096) * (max_att if has_resil else 1)
    n_slot = 2 * C   # candidate positions: busy slots then cold slots
    # candidate order: busy | cold | (timers) | retry | arrival
    n_cand = (n_slot + (2 * F if kernel.has_timers else 0)
              + (1 if has_resil else 0) + 1)
    lanes = jnp.arange(L, dtype=jnp.int32)
    lane_iota = lanes[:, None]
    # per-lane (F,) cold/evict rows, gathered once (the (T, F) row
    # gather would otherwise sit inside the per-event body)
    t_cold_l = t_cold[trace_ix]
    t_evict_l = t_evict[trace_ix]
    mem_l = (jnp.asarray(mem_mb, jnp.int32)[trace_ix] if has_mem
             else None)
    # lane-stacked arrival reads go through the flattened operand with
    # a per-lane base — a (T, N) two-dim gather only hits the fast
    # XLA:CPU path at T == 1 (see EngineCtx)
    arr_flat = arrival.reshape(-1)
    base_n = trace_ix * N

    def window_body(w, s):
        base = w * W
        if single_win:
            slabs = (None,) * 7
            win_end = N
            is_last = True
        else:
            # shared (T, W) slabs for this window — contiguous copies,
            # so the inner loop's gathers stay inside ~24*W bytes per
            # trace
            fn_s = lax.dynamic_slice_in_dim(fn_pad, base, W, 1)
            arr_s = lax.dynamic_slice_in_dim(arr_pad, base, W, 1)
            ex_s = lax.dynamic_slice_in_dim(ex_pad, base, W, 1)
            pos_s = lax.dynamic_slice_in_dim(pos_w, base, W, 1)
            offw = lax.dynamic_slice_in_dim(off_w, w, 1, 1)[:, 0]
            cc_lo = lax.dynamic_slice_in_dim(cum_cnt, w, 1, 1)[:, 0]
            cc_hi = lax.dynamic_slice_in_dim(cum_cnt, w + 1, 1, 1)[:, 0]
            slabs = (fn_s, arr_s, ex_s, pos_s, offw, cc_lo, cc_hi)
            win_end = jnp.minimum(base + W, N)
            is_last = w >= n_win - 1

        def pick_events(s):
            """Lane-stacked next-event pick: one segmented first-index
            argmin over the (L, 2C[+2F]+1) candidate matrix resolves
            time and tie-break for every lane at once — position
            encodes the same-time class order EXEC < COLD <
            TIMER(orig < rearm) < ARRIVAL and the within-class index
            tie-break (Python engine heap order)."""
            na = s["ci"][:, CI_NEXT]
            r = jnp.minimum(na, N - 1)
            if single_win:
                t_arr = jnp.where(na < nl, arr_flat[base_n + r], BIG)
            else:
                off = r - base
                inw = (off >= 0) & (off < W)
                sv = arr_s.reshape(-1)[trace_ix * W
                                       + jnp.where(inw, off, 0)]
                fv = arr_flat[base_n + jnp.where(inw, base, r)]
                t_arr = jnp.where(na < nl, jnp.where(inw, sv, fv), BIG)
            ready = jnp.where(cap_mask, s["slot_ready"], BIG)
            st = s["slot_state"]
            blocks = [jnp.where(st == BUSY, ready, BIG),
                      jnp.where(st == COLD, ready, BIG)]
            if kernel.has_timers:
                blocks += [s["tmr_next"], s["rearm_t"]]
            if has_resil:
                blocks.append(s["r_fire"][:, None])
            blocks.append(t_arr[:, None])
            cand = jnp.concatenate(blocks, axis=1)
            ei = jnp.argmin(cand, axis=1).astype(jnp.int32)
            t_ev = jnp.take_along_axis(cand, ei[:, None], axis=1)[:, 0]
            return ei, t_ev, t_arr

        def lane_step(k, s, tix, cold_l, evict_l, cap_mask, beta,
                      nl_l, ei, t_ev, t_arr, mem_row):
            kw = dict(fn_id2=fn_id, arrival2=arrival,
                      exec2=exec_time, cold2=cold_l,
                      evict2=evict_l, pos_rids2=pos_rids,
                      pos_off2=pos_off, slabs=slabs,
                      win_base=base, win_w=W, tix=tix,
                      cap_mask=cap_mask, beta=beta, prior=prior,
                      threshold=threshold, k=k, n=N, f=F, c=C,
                      q=Q, stream=stream, tl_bins=tl_bins,
                      tl_bucket=tl_bucket, deadlines=deadlines,
                      mem_mb=mem_row)
            ctx = (ResilCtx(nfail2=rs_nfail, tmo2=rs_tmo, key2=rs_key,
                            resil=resil, **kw)
                   if has_resil else EngineCtx(**kw))
            ci = s["ci"]
            done_ci = CI_TERM if has_resil else CI_DONE
            active = (ci[done_ci] < nl_l) & (ci[CI_STALL] == 0)
            if trace:
                tr_q0 = s["q_len"].sum()
            na = ci[CI_NEXT]
            live = active & (t_ev < BIG)
            # per-event dispatch registers (consumed by _fold_event)
            s = dict(s)
            s["ev_rid"] = jnp.int32(-1)
            s["ev_comp"] = jnp.float64(0.0)
            s["ev_exec"] = jnp.float64(0.0)
            ev_slot = live & (ei < n_slot)
            is_cold = ei >= C
            slot = jnp.clip(jnp.where(is_cold, ei - C, ei), 0, C - 1)
            # an arrival beyond the current window parks the lane (its
            # time still won the pick, so every earlier event has been
            # processed); the consume waits for the next window
            ev_arr = live & (ei == n_cand - 1) & (na < win_end)

            # ------------------------------------------------- slot event
            cold_on = ev_slot & is_cold
            exec_on = ev_slot & ~is_cold
            rid_done = s["slot_req"][slot]
            j_done = s["slot_fn"][slot]
            e_done = ctx.exec_at(rid_done)
            si = _gidx(ev_slot, slot, C)
            ji = _gidx(exec_on, j_done, F)
            exec_i = exec_on.astype(jnp.int32)
            s = dict(s)
            s["slot_state"] = _put(s["slot_state"], si, IDLE)
            s["slot_ready"] = _put(s["slot_ready"], si, BIG)
            s["slot_req"] = _put(s["slot_req"], si, -1)
            # estimator sees the completion before the policy reacts
            s["est_sum"] = _bump(s["est_sum"], ji, e_done)
            s["est_n"] = _bump(s["est_n"], ji, 1)
            s["cf"] = _bump(s["cf"], CF_GSUM,
                            jnp.where(exec_on, e_done, 0.0))
            if not has_resil:
                s["ci"] = _bumps(s["ci"], {CI_GN: exec_i,
                                           CI_DONE: exec_i})
            else:
                # outcome of this attempt: the estimator observed the
                # attempt above (every attempt burns real slot time);
                # success/failure is the pre-planned attempt test
                att_d = s["att"][jnp.clip(rid_done, 0, N - 1)]
                nf_d = ctx.nfail_at(rid_done)
                ok_d = exec_on & (att_d > nf_d)
                fail_d = exec_on & ~ok_d
                exh_d = fail_d & (att_d >= max_att)
                retry_d = fail_d & ~exh_d
                tmo_d = ctx.tmo_at(rid_done)
                ok_i = ok_d.astype(jnp.int32)
                s["ci"] = _bumps(s["ci"], {
                    CI_GN: exec_i, CI_DONE: ok_i,
                    CI_TERM: ok_i + exh_d.astype(jnp.int32),
                    CI_FAILED: fail_d & ~tmo_d, CI_TMO: fail_d & tmo_d,
                    CI_RETRY: retry_d, CI_EXH: exh_d})
                # fold (and exact-record) successful completions only
                rd32 = jnp.asarray(rid_done, jnp.int32)
                s["ev_rid"] = jnp.where(ok_d, rd32, s["ev_rid"])
                s["ev_comp"] = jnp.where(ok_d, t_ev, s["ev_comp"])
                s["ev_exec"] = jnp.where(ok_d, e_done, s["ev_exec"])
                if not stream:
                    s["completion"] = s["completion"].at[
                        _gidx(ok_d, rid_done, N)].set(t_ev,
                                                      mode="drop")
                # a retrying rid re-enters after its backoff; the rail
                # is FIFO so only an empty rail arms the fire time here
                key_d = ctx.key_at(rid_done)
                elig = t_ev + backoff_jax(att_d, key_d, rt_base,
                                          rt_cap, rt_jit, rt_seed)
                s["rt_t"] = s["rt_t"].at[
                    _gidx(retry_d, rid_done, N)].set(elig, mode="drop")
                r_empty = s["r_len"] == 0
                s["nxt"] = s["nxt"].at[
                    _gidx(retry_d & ~r_empty, s["r_tail"], N)].set(
                    rd32, mode="drop")
                s["r_head"] = jnp.where(retry_d & r_empty, rd32,
                                        s["r_head"])
                s["r_tail"] = jnp.where(retry_d, rd32, s["r_tail"])
                s["r_fire"] = jnp.where(retry_d & r_empty, elig,
                                        s["r_fire"])
                s["r_len"] = s["r_len"] + retry_d.astype(jnp.int32)
            with jax.named_scope("repro.policy"):
                s = kernel.on_cold_done(ctx, s, slot, t_ev, cold_on)
                s = kernel.on_exec_done(ctx, s, slot, rid_done, t_ev,
                                        exec_on)

            # ------------------------------------------------ timer event
            ev_timer = jnp.bool_(False)
            if kernel.has_timers:
                # originals (arrival + threshold, arrival order) vs the
                # unique re-armed head; originals win exact ties (FIFO
                # seq)
                fire_orig = live & (ei >= n_slot) & (ei < n_slot + F)
                fire_re = (live & (ei >= n_slot + F)
                           & (ei < n_slot + 2 * F))
                ev_timer = fire_orig | fire_re
                f_o = jnp.clip(ei - n_slot, 0, F - 1)
                f_r = jnp.clip(ei - n_slot - F, 0, F - 1)
                p_o = s["tmr_pos"][f_o]
                rid_o = ctx.rid_at_pos(f_o, p_o)
                succ = ctx.rid_at_pos(f_o, p_o + 1)
                more = p_o + 1 < s["arr_cnt"][f_o]
                oi = _gidx(fire_orig, f_o, F)
                rid_r = s["rearm_rid"][f_r]
                s = dict(s)
                s["tmr_pos"] = _bump(s["tmr_pos"], oi, 1)
                s["tmr_next"] = _put(
                    s["tmr_next"], oi,
                    jnp.where(more, ctx.arrival_at(succ) + threshold,
                              BIG))
                s["rearm_t"] = _put(s["rearm_t"], _gidx(fire_re, f_r, F),
                                    BIG)
                rid_t = jnp.where(fire_orig, rid_o, rid_r)
                with jax.named_scope("repro.policy"):
                    s = kernel.on_timer(ctx, s, rid_t, t_ev, ev_timer)

            # ------------------------------------------------ retry event
            ev_rtry = jnp.bool_(False)
            rid_a = jnp.minimum(na, N - 1)
            rid_na, t_na = rid_a, t_arr
            if has_resil:
                ev_rtry = live & (ei == n_slot)
                rlen0 = s["r_len"]
                rid_r = s["r_head"]
                succ_r = s["nxt"][jnp.clip(rid_r, 0, N - 1)]
                s = dict(s)
                s["r_head"] = jnp.where(ev_rtry, succ_r, s["r_head"])
                s["r_tail"] = jnp.where(ev_rtry & (rlen0 <= 1),
                                        jnp.int32(-1), s["r_tail"])
                s["r_len"] = rlen0 - ev_rtry.astype(jnp.int32)
                # promote the successor; it may not fire before this
                # pop (FIFO, no overtaking within the rail)
                nfire = jnp.maximum(
                    s["rt_t"][jnp.clip(succ_r, 0, N - 1)], t_ev)
                s["r_fire"] = jnp.where(
                    ev_rtry, jnp.where(rlen0 > 1, nfire, BIG),
                    s["r_fire"])
                # a retry re-enters through the same arrival hook, at
                # its fire time
                rid_na = jnp.where(ev_rtry, rid_r, rid_a)
                t_na = jnp.where(ev_rtry, t_ev, t_arr)

            # ---------------------------------------------------- arrival
            s = dict(s)
            if kernel.has_timers:
                s["arr_cnt"] = _bump(s["arr_cnt"],
                                     _gidx(ev_arr, ctx.fn_at(rid_a), F),
                                     1)
            # n_events counts processed events (parked no-op spins are
            # excluded, so the count is window-size invariant)
            progress = ev_slot | ev_timer | ev_arr | ev_rtry
            s["ci"] = _bumps(s["ci"], {CI_NEXT: ev_arr,
                                       CI_ITERS: progress})
            with jax.named_scope("repro.policy"):
                s = kernel.on_arrival(ctx, s, rid_na, t_na,
                                      ev_arr | ev_rtry)
            with jax.named_scope("repro.fold"):
                s = _fold_event(ctx, s)
            s = dict(s)
            if trace:
                # telemetry record: one fixed-width row per processed
                # event, staged at the segment-step slot (parked spins
                # drop). Outcome detail comes from the counter deltas
                # of this event, so every rail reports through one
                # code path.
                from repro.telemetry.rail import (
                    AUX_COLD, AUX_FAIL_EXHAUSTED, AUX_FAIL_RETRY,
                    AUX_OVERFLOW, AUX_QUEUED, AUX_SHED, AUX_TIMEOUT,
                    TraceKind)
                ci1 = s["ci"]
                dlt = ci1 - ci
                kind = jnp.where(exec_on, TraceKind.EXEC, jnp.where(
                    cold_on, TraceKind.COLD, jnp.where(
                        ev_timer, TraceKind.TIMER, jnp.where(
                            ev_rtry, TraceKind.RETRY, jnp.where(
                                ev_arr, TraceKind.ARRIVAL, -1)))))
                rid_tr = jnp.where(
                    ev_slot, rid_done,
                    jnp.where(ev_arr | ev_rtry, rid_na, -1))
                if kernel.has_timers:
                    rid_tr = jnp.where(ev_timer, rid_t, rid_tr)
                fn_tr = jnp.where(ev_slot, j_done, jnp.where(
                    rid_tr >= 0, ctx.fn_at(rid_tr), -1))
                fail_i = dlt[CI_FAILED] + dlt[CI_TMO]
                aux_ex = (jnp.where(
                    dlt[CI_EXH] > 0, AUX_FAIL_EXHAUSTED,
                    jnp.where(fail_i > 0, AUX_FAIL_RETRY, 0))
                    + jnp.where(dlt[CI_TMO] > 0, AUX_TIMEOUT, 0))
                aux_arr = (
                    jnp.where(dlt[CI_COLD] > 0, AUX_COLD, 0)
                    + jnp.where(s["q_len"].sum() > tr_q0,
                                AUX_QUEUED, 0)
                    + jnp.where(dlt[CI_SHED] > 0, AUX_SHED, 0)
                    + jnp.where(dlt[CI_OVF] > 0, AUX_OVERFLOW, 0))
                busy = ((s["slot_state"] == BUSY)
                        & cap_mask).sum()
                warm = ((s["slot_state"] == IDLE) & (s["slot_fn"] >= 0)
                        & cap_mask).sum()
                rec_i = jnp.stack([
                    kind, rid_tr, fn_tr, jnp.int32(-1),
                    jnp.where(exec_on, aux_ex, aux_arr),
                    s["q_len"].sum(), busy, warm,
                    ci1[CI_ITERS]]).astype(jnp.int32)
                rec_f = jnp.stack([
                    t_ev, jnp.where(exec_on, e_done, 0.0)])
                ki = jnp.where(progress, k, SEG)
                s["tr_i"] = _put(s["tr_i"], ki, rec_i)
                s["tr_f"] = _put(s["tr_f"], ki, rec_f)
            stall = jnp.where(
                active & ~live, 1,
                jnp.where(active & (s["ci"][CI_ITERS] >= max_iters), 2,
                          s["ci"][CI_STALL]))
            s["ci"] = _put(s["ci"], CI_STALL, stall)
            return s

        step_lanes = jax.vmap(
            lane_step, in_axes=(None, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0))

        def cond(s):
            ci = s["ci"]
            done_col = CI_TERM if has_resil else CI_DONE
            act = (ci[:, done_col] < nl) & (ci[:, CI_STALL] == 0)
            return jnp.any(act & (is_last | (ci[:, CI_NEXT] < win_end)))

        def segment(s):
            # streaming metrics fold per event (`dispatch` registers +
            # `_fold_event`) — a segment is pure event-stepping
            # plus, in exact mode, the batched overlay scatter into the
            # (L, N) per-request arrays (the only large-array write,
            # paid once per SEG events, not per event)
            s = dict(s)
            steps = s.pop("loop_steps")
            if not stream:
                s["d_rid"] = jnp.full((L, SEG), N, jnp.int32)
            if trace:
                from repro.telemetry.rail import TR_RF, TR_RI
                s["tr_i"] = jnp.full((L, SEG, TR_RI), -1, jnp.int32)
                s["tr_f"] = jnp.zeros((L, SEG, TR_RF), jnp.float64)

            def step(k, s):
                with jax.named_scope("repro.pick"):
                    ei, t_ev, t_arr = pick_events(s)
                return step_lanes(k, s, trace_ix, t_cold_l, t_evict_l,
                                  cap_mask, beta, nl, ei, t_ev, t_arr,
                                  mem_l)

            s = lax.fori_loop(0, SEG, step, s)
            if not stream:
                s = dict(s)
                s["start"] = s["start"].at[lane_iota, s["d_rid"]].set(
                    s["d_start"], mode="drop")
                s["completion"] = s["completion"].at[
                    lane_iota, s["d_rid"]].set(s["d_comp"], mode="drop")
            if trace:
                from repro.telemetry.rail import emit_flush
                with jax.named_scope("repro.flush"):
                    emit_flush(s["tr_i"], s["tr_f"])
            s = dict(s)
            s["loop_steps"] = steps + SEG
            return s

        return lax.while_loop(cond, segment, s)

    final = (window_body(0, s) if single_win
             else lax.fori_loop(0, n_win, window_body, s))
    ci, cf = final["ci"], final["cf"]
    out = dict(cold_starts=ci[:, CI_COLD], cold_time=cf[:, CF_COLDT],
               evictions=ci[:, CI_EVICT], evict_time=cf[:, CF_EVICTT],
               overflow=ci[:, CI_OVF],
               stalled=ci[:, CI_STALL], n_events=ci[:, CI_ITERS],
               done=ci[:, CI_DONE],
               resp_sum=cf[:, CF_RSUM], slow_sum=cf[:, CF_SSUM],
               max_response=cf[:, CF_RMAX], resp_hist=final["hist"],
               loop_steps=final["loop_steps"])
    if tl_bins:
        out["tl_count"] = final["tl_cnt"]
        out["tl_resp_sum"] = final["tl_resp"]
        out["tl_exec_sum"] = final["tl_exec"]
    if deadlines is not None:
        out["deadline_miss"] = final["dl_miss"]
    if has_mem:
        mc = final["mc"]
        out["evict_mb"] = mc[:, MC_EVICT_MB]
        out["multi_victim_starts"] = mc[:, MC_MULTI]
        out["mem_waits"] = mc[:, MC_WAIT]
    if has_resil:
        out["failed"] = ci[:, CI_FAILED]
        out["timed_out"] = ci[:, CI_TMO]
        out["retried"] = ci[:, CI_RETRY]
        out["shed"] = ci[:, CI_SHED]
        out["failed_exhausted"] = ci[:, CI_EXH]
    if not stream:
        out["start"] = final["start"]
        out["completion"] = final["completion"]
    return out


# ------------------------------------------------------------ public API
def simulate_policy_jax(fn_id, arrival, exec_time, t_cold, t_evict, *,
                        policy: str = "esff", n_fns: int, capacity: int,
                        queue_cap: int = 512, beta=None,
                        prior: float = 0.1, threshold: float = 0.1,
                        cap_mask=None, stream: bool = False,
                        window: int = 0, tl_bins: int = 0,
                        tl_bucket: float = 60.0, mem_mb=None,
                        slot_mb: Optional[int] = None
                        ) -> Dict[str, jnp.ndarray]:
    """Run ``policy`` over a (sorted-by-arrival) request stream.

    ``policy`` selects a kernel from `repro.core.jax_policies.KERNELS`
    statically, so each policy gets its own jit specialisation. ``beta``
    defaults to the kernel's own default (2.0 for ESFF-H, else 1.0).
    ``window`` sets the cache-window slab size (0 -> `DEFAULT_WINDOW`;
    results are bitwise independent of it). ``tl_bins > 0`` adds the
    minute-binned timeline accumulators (``tl_count`` / ``tl_resp_sum``
    / ``tl_exec_sum``). Returns the counter block (cold starts,
    evictions, overflow, stalled) plus the streaming metric
    accumulators (resp_sum / slow_sum / max_response / resp_hist);
    with the default ``stream=False`` also per-request
    start/completion. ``slot_mb`` with the (F,) footprints ``mem_mb``
    makes the node ``capacity * slot_mb`` MB of memory (see `_simulate`).
    """
    from repro.core.jax_policies import KERNELS  # deferred: cycle-free
    from repro.core.server import MIN_MEM_MB, check_memory
    kernel = KERNELS[policy]
    if beta is None:
        beta = kernel.default_beta
    share = lambda x: jnp.expand_dims(jnp.asarray(x), 0)  # noqa: E731
    mem_op = node_op = None
    if slot_mb is not None:
        check_memory([policy], [None] if mem_mb is None
                     else np.unique(mem_mb), capacity * slot_mb)
        node_op = jnp.full((1,), capacity * slot_mb, jnp.int32)
        mem_op = share(jnp.asarray(mem_mb, jnp.int32))
        capacity = capacity * slot_mb // MIN_MEM_MB
    if cap_mask is None:
        cap_mask = jnp.ones((capacity,), bool)
    out = _simulate(share(fn_id), share(arrival), share(exec_time),
                    share(t_cold), share(t_evict),
                    jnp.zeros((1,), jnp.int32),
                    jnp.expand_dims(jnp.asarray(cap_mask), 0),
                    jnp.asarray(beta, jnp.float64).reshape((1,)),
                    jnp.float64(prior), jnp.float64(threshold),
                    mem_mb=mem_op, node_mb=node_op, kernel=kernel,
                    n_fns=n_fns, capacity=capacity,
                    queue_cap=queue_cap, stream=stream, window=window,
                    tl_bins=tl_bins, tl_bucket=tl_bucket)
    out.pop("loop_steps")
    return {k: jnp.squeeze(v, axis=0) for k, v in out.items()}


def simulate_policy_from_trace(trace: Trace, policy: str, capacity: int,
                               *, beta=None, queue_cap: int = 1024,
                               prior: float = 0.1,
                               threshold: float = 0.1,
                               window: int = 0,
                               slot_mb: Optional[int] = None
                               ) -> Dict[str, np.ndarray]:
    """Trace-object convenience wrapper mirroring ``simulate()``
    (exact per-request mode)."""
    a = trace.to_arrays()
    out = simulate_policy_jax(
        jnp.asarray(a["fn_id"]), jnp.asarray(a["arrival"]),
        jnp.asarray(a["exec_time"]), jnp.asarray(a["cold_start"]),
        jnp.asarray(a["evict"]), policy=policy,
        n_fns=trace.n_functions, capacity=capacity, queue_cap=queue_cap,
        beta=beta, prior=prior, threshold=threshold, window=window,
        mem_mb=a.get("mem_mb"), slot_mb=slot_mb)
    out = {k: np.asarray(v) for k, v in out.items()}
    out["response"] = out["completion"] - a["arrival"]
    out["mean_response"] = float(out["response"].mean())
    return out


@functools.partial(jax.jit,
                   static_argnames=("kernel", "n_fns", "capacity",
                                    "queue_cap", "stream", "window",
                                    "tl_bins", "keep_responses",
                                    "resil", "trace"),
                   compiler_options=LOOP_COMPILER_OPTIONS)
def _sweep_metrics(fn, arr, ex, cold, ev, tix, masks, betas, prior,
                   threshold, n_live=None, deadlines=None,
                   rs_nfail=None, rs_tmo=None, rs_key=None, mem_mb=None,
                   node_mb=None, *, kernel,
                   n_fns, capacity, queue_cap, stream=True, window=0,
                   tl_bins=0, tl_bucket=60.0, keep_responses=False,
                   resil=None, trace=False):
    """Lane-batched run + on-device metric reduction. Means and
    slowdowns come from the streaming accumulators in *both* modes (so
    streamed and exact sweeps agree bitwise); p99 is exact in exact
    mode and one-bin-accurate from the histogram in streaming mode.
    ``keep_responses`` (exact mode only) additionally returns the
    (L, N) per-request response vector — the CDF/percentile surface
    `repro.api.ExperimentSpec(keep_per_request=True)` exposes.
    ``n_live`` ((L,) i32) marks lanes as ragged prefixes of their
    padded trace rows (see `_simulate`); means/quantiles then reduce
    over each lane's live prefix only. ``mem_mb`` / ``node_mb`` make
    the node's resource memory (see `_simulate`), adding ``evict_mb``,
    ``multi_victim_starts`` and ``mem_waits`` per lane."""
    if keep_responses and stream:
        raise ValueError("keep_responses requires stream=False")
    out = _simulate(fn, arr, ex, cold, ev, tix, masks, betas, prior,
                    threshold, n_live, deadlines, rs_nfail, rs_tmo,
                    rs_key, mem_mb, node_mb, kernel=kernel,
                    n_fns=n_fns, capacity=capacity, queue_cap=queue_cap,
                    stream=stream, window=window, tl_bins=tl_bins,
                    tl_bucket=tl_bucket, resil=resil, trace=trace)
    N = fn.shape[1]
    if resil is not None:
        # under faults only successes fold into the response sums and
        # per-request records; means/quantiles reduce over those
        denom = jnp.maximum(out["done"], 1).astype(jnp.float64)
    elif n_live is None:
        denom = N
    else:
        n_live = jnp.asarray(n_live, jnp.int32)
        denom = jnp.maximum(n_live, 1).astype(jnp.float64)
    if stream:
        if resil is not None:
            nq = out["done"][:, None]
        else:
            nq = N if n_live is None else n_live[:, None]
        p99 = hist_quantile(out["resp_hist"], 0.99, nq,
                            out["max_response"])
    else:
        resp = out["completion"] - arr[tix]
        if resil is not None:
            # shed / retry-exhausted rids keep completion == -1
            resp = jnp.where(out["completion"] >= 0, resp, jnp.nan)
            p99 = jnp.nanpercentile(resp, 99.0, axis=1)
        elif n_live is None:
            p99 = jnp.percentile(resp, 99.0, axis=1)
        else:
            live = jnp.arange(N) < n_live[:, None]
            p99 = jnp.nanpercentile(
                jnp.where(live, resp, jnp.nan), 99.0, axis=1)
    res = dict(mean_response=out["resp_sum"] / denom,
               mean_slowdown=out["slow_sum"] / denom,
               resp_sum=out["resp_sum"],
               slow_sum=out["slow_sum"],
               done=out["done"],
               p99_response=p99,
               max_response=out["max_response"],
               resp_hist=out["resp_hist"],
               cold_starts=out["cold_starts"],
               cold_time=out["cold_time"],
               evictions=out["evictions"],
               overflow=out["overflow"],
               stalled=out["stalled"],
               n_events=out["n_events"],
               loop_steps=out["loop_steps"])
    if tl_bins:
        res["tl_count"] = out["tl_count"]
        res["tl_resp_sum"] = out["tl_resp_sum"]
        res["tl_exec_sum"] = out["tl_exec_sum"]
    if deadlines is not None:
        res["deadline_miss"] = out["deadline_miss"]
    if mem_mb is not None:
        for key in ("evict_mb", "multi_victim_starts", "mem_waits"):
            res[key] = out[key]
    if resil is not None:
        for key in ("failed", "timed_out", "retried", "shed",
                    "failed_exhausted"):
            res[key] = out[key]
    if keep_responses:
        res["response"] = resp
    return res


def goodput(done, n):
    """Fraction of offered requests that eventually completed
    successfully: ``done / n``. Computed in numpy *outside* jit and
    shared by every tier (like `slo_attainment`) so the derived metric
    is bitwise identical no matter which tier produced the counters."""
    return (np.asarray(done, np.float64)
            / np.maximum(np.asarray(n, np.float64), 1.0))


def slo_attainment(deadline_miss, done):
    """Fraction of completed requests that met their per-fn deadline:
    ``1 - deadline_miss.sum(-1) / done``. Computed in numpy *outside*
    jit and shared by every tier (single-node runner, dynamic cluster,
    static merge) so the derived metric is bitwise identical no matter
    which tier produced the counters."""
    miss = np.asarray(deadline_miss)
    d = np.maximum(np.asarray(done, dtype=np.float64), 1.0)
    return 1.0 - miss.sum(axis=-1) / d


def sweep(traces: Union[Trace, Sequence[Trace], dict, Sequence[dict]],
          policies: Sequence[str] = ("esff", "esff_h", "sff",
                                     "openwhisk", "faascache",
                                     "openwhisk_v2"),
          capacities: Sequence[int] = (8, 16, 32),
          betas=None, *, queue_cap: int = 2048, prior: float = 0.1,
          threshold: float = 0.1, stream: bool = True,
          window: int = 0, tl_bins: int = 0, tl_bucket: float = 60.0,
          lane_chunk: Union[int, str, None] = None
          ) -> Dict[str, np.ndarray]:
    """Deprecated batched-sweep entry point (use `repro.api`).

    This is now a thin shim over the declarative experiment API: the
    arguments are packed into a `repro.api.ExperimentSpec`, executed by
    `repro.api.run_experiment` (the same `_sweep_metrics` lanes, same
    chunk order, so outputs are bitwise identical — gated by
    ``benchmarks/run.py --smoke`` and ``tests/test_api.py``), and the
    `ResultSet` is flattened back into the legacy dict of
    (P, T, K, B)-shaped metric arrays plus the ``"axes"`` dict.

    Prefer::

        from repro.api import ExperimentSpec, run
        rs = run(ExperimentSpec(traces=[...], policies=...,
                                capacities=...))

    which adds labeled selection, CSV/npz round-trips, multi-device
    and multi-host sharding, and registry-backed custom policies.
    """
    import warnings
    warnings.warn(
        "repro.core.jax_engine.sweep() is deprecated; build a "
        "repro.api.ExperimentSpec and call repro.api.run() instead "
        "(see docs/api.md)", DeprecationWarning, stacklevel=2)
    from repro.api import ExperimentSpec
    from repro.api.runner import legacy_sweep_dict, run_experiment
    if isinstance(traces, (Trace, dict)):
        traces = [traces]
    traces = list(traces)
    spec = ExperimentSpec(
        traces=traces, policies=policies, capacities=capacities,
        betas=betas, queue_cap=queue_cap, prior=prior,
        threshold=threshold, stream=stream, window=window,
        tl_bins=tl_bins, tl_bucket=tl_bucket, lane_chunk=lane_chunk,
        devices=1)
    return legacy_sweep_dict(run_experiment(spec), len(traces))


# ---------------------------------------------------------- audit hooks
# Pure metadata for `repro.analysis` (the jaxpr/HLO invariant auditor):
# nothing in the hot loops reads any of this. Every carried array that
# is *allowed* to scale with the trace length N carries a rationale
# here; the carry-budget analyzer fails on any N-scaling carry whose
# (shape-class, dtype) signature is not claimed by one of these rails.
CARRY_RAILS = {
    "start": "exact mode records every request's dispatch time -- the "
             "(L, N) record *is* the requested output, not loop "
             "bookkeeping (streaming mode folds it away).",
    "completion": "exact mode's per-request completion-time record; "
                  "same contract as `start`.",
    "nxt": "resilience rid-chain: per-function FIFO successor links, "
           "one i32 per request. Retries re-enqueue old rids, which "
           "breaks the positional-cursor invariant, so the linked "
           "spelling is the documented O(N) cost of the layer.",
    "att": "resilience attempt counter per original rid; i32, "
           "written once per retry.",
    "rt_t": "resilience retry-eligibility time per rid (backoff "
            "target); f64, written once per retry.",
    "tr_i": "telemetry trace rail (trace=True only): (L, SEG, TR_RI) "
            "i32 record overlay, reset per segment and flushed to "
            "the host through an ordered io_callback -- O(SEG) "
            "carried state, never N-scaling.",
    "tr_f": "telemetry trace rail float half ((L, SEG, TR_RF) f64); "
            "same contract as `tr_i`.",
}


def audit_jits():
    """Jitted engine entry points by name, for `repro.analysis` and
    the recompilation auditor (cache introspection via
    ``_cache_size``/``clear_cache``)."""
    return {"simulate": _simulate, "sweep_metrics": _sweep_metrics}
