"""A node whose resource is memory (`ExperimentSpec(slot_mb=...)`):
OpenWhisk's container-pool admission and FaasCache's size-aware
GREEDY-DUAL, in the JAX engine and in the Python engine it mirrors.

* the two engines agree request for request;
* footprints all equal to ``slot_mb`` reproduce the slot-count run bit
  for bit (dividing the GREEDY-DUAL priority by 256 = 2^8 is exact);
* the engine's victim prefix is the one a sort of the idle set gives;
* what has no memory semantics, or no valid footprint, is refused."""
from types import SimpleNamespace

import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import ArrayTrace, ClusterSpec, ExperimentSpec, run_experiment
from repro.core import simulate
from repro.core.jax_engine import (COLD, I32_MAX, IDLE, MC_FREE, NMC,
                                   pick_victims, simulate_policy_from_trace)
from repro.core.request import Trace
from repro.traces import synth_azure_arrays

MEMORY_POLICIES = ("openwhisk", "sff", "faascache")
SLOT_MB = 256
F, N = 20, 500


def arrays(seed, mem=None):
    """A small Azure-like trace with log-normal footprints (median
    170 MB, sigma 0.668, clipped to [128, 512]) or ``mem`` for all."""
    a = synth_azure_arrays(n_functions=F, n_requests=N, utilization=0.2,
                           seed=seed)
    rng = np.random.default_rng([seed, 7])
    fp = np.exp(rng.normal(np.log(170.0), 0.668, F))
    a["mem_mb"] = (np.full(F, mem, np.int32) if mem is not None else
                   np.clip(np.rint(fp), 128, 512).astype(np.int32))
    return a


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("policy", MEMORY_POLICIES)
def test_jax_engine_matches_python_engine(policy, seed):
    for capacity in (2, 4):
        tr = Trace.from_arrays(arrays(seed))
        py = simulate(tr, policy, capacity, slot_mb=SLOT_MB)
        jx = simulate_policy_from_trace(tr, policy, capacity,
                                        queue_cap=N, slot_mb=SLOT_MB)
        assert int(jx["overflow"]) == 0 and int(jx["stalled"]) == 0
        np.testing.assert_allclose(
            jx["start"], [q.start for q in tr.requests], rtol=1e-9,
            atol=1e-9)
        np.testing.assert_allclose(
            jx["completion"], [q.completion for q in tr.requests],
            rtol=1e-9, atol=1e-9)
        st = py.server
        got = {k: int(jx[k]) for k in ("cold_starts", "evictions",
                                       "evict_mb", "multi_victim_starts",
                                       "mem_waits")}
        assert got == {"cold_starts": st.cold_starts,
                       "evictions": st.evictions, "evict_mb": st.evict_mb,
                       "multi_victim_starts": st.multi_victim_starts,
                       "mem_waits": st.mem_waits}
        assert st.evict_mb > 0 and st.mem_waits > 0


@pytest.mark.parametrize("policy", MEMORY_POLICIES)
def test_uniform_footprints_reproduce_slots_bitwise(policy):
    traces = [ArrayTrace.make(arrays(s, mem=SLOT_MB), f"t{s}")
              for s in (4, 5)]
    kw = dict(traces=traces, policies=(policy,), capacities=(2, 3, 5),
              queue_cap=N)
    slots = run_experiment(ExperimentSpec(**kw)).check()
    mem = run_experiment(ExperimentSpec(**kw, slot_mb=SLOT_MB)).check()
    assert set(mem.data) - set(slots.data) == {"evict_mb"}
    for k, v in slots.data.items():
        np.testing.assert_array_equal(mem.data[k], v, err_msg=k)
    np.testing.assert_array_equal(mem["evict_mb"],
                                  slots["evictions"] * SLOT_MB)
    assert mem.meta["slot_mb"] == SLOT_MB
    assert len(mem.meta["mem_waits"]) == len(mem.meta["loop_steps"])


def brute_force(key, seq, foot, idle, need, first=None):
    """The victims of a cold start by a sort of the idle slots."""
    chosen = [] if first is None else [first]
    need -= sum(foot[i] for i in chosen)
    order = sorted((i for i in np.flatnonzero(idle) if i != first),
                   key=lambda i: (key[i], seq[i]))
    for i in order:
        if need <= 0:
            break
        chosen.append(int(i))
        need -= foot[i]
    return None if need > 0 else sorted(chosen)


@pytest.mark.parametrize("first", [False, True])
@pytest.mark.parametrize("case", ["none", "one", "several", "cannot_fit"])
def test_victim_prefix_matches_a_sort(case, first):
    rng = np.random.default_rng([len(case), first])
    C, Fn = 16, 6
    for trial in range(20):
        mem_mb = rng.integers(128, 513, Fn).astype(np.int32)
        slot_fn = rng.integers(-1, Fn, C).astype(np.int32)
        # free memory for a start without victims leaves a slot empty
        slot_fn[int(rng.integers(C))] = -1
        slot_state = np.where(rng.random(C) < 0.6, IDLE,
                              rng.choice([COLD, 2], C)).astype(np.int32)
        slot_state[slot_fn < 0] = IDLE
        # ties in the key, broken by the creation sequence
        key = rng.integers(0, 4, C).astype(np.float64)
        seq = rng.permutation(C).astype(np.int32)
        seq[slot_fn < 0] = I32_MAX
        idle = (slot_state == IDLE) & (slot_fn >= 0)
        if idle.sum() < 3:
            continue
        lead = int(rng.choice(np.flatnonzero(idle))) if first else None
        foot = np.where(slot_fn >= 0, mem_mb[np.clip(slot_fn, 0, Fn - 1)],
                        0)
        rest = idle.copy()
        if lead is not None:
            rest[lead] = False
        ranked = sorted(np.flatnonzero(rest), key=lambda i: (key[i], seq[i]))
        base = foot[lead] if lead is not None else 0
        total = base + foot[rest].sum()
        need = {"none": base - int(rng.integers(0, 100)),
                "one": base + int(foot[ranked[0]]),
                "several": base + int(foot[ranked[0]] + foot[ranked[1]]),
                "cannot_fit": total + 1}[case]
        fn = int(rng.integers(0, Fn))
        free = int(mem_mb[fn] - need)
        ctx = SimpleNamespace(F=Fn, C=C, mem_mb=jnp.asarray(mem_mb),
                              cap_mask=jnp.ones((C,), bool))
        s = dict(slot_fn=jnp.asarray(slot_fn),
                 slot_state=jnp.asarray(slot_state),
                 slot_seq=jnp.asarray(seq),
                 mc=jnp.zeros((NMC,), jnp.int32).at[MC_FREE].set(free))
        vic, fits, slot = pick_victims(ctx, s, jnp.asarray(key), fn,
                                       None if lead is None
                                       else jnp.int32(lead))
        want = brute_force(key, seq, foot, idle, need, lead)
        assert bool(fits) == (want is not None)
        got = sorted(int(i) for i in np.flatnonzero(np.asarray(vic)))
        assert got == (want or [])
        if want is not None:
            count = {"none": 0, "one": 1, "several": 2}[case]
            assert len(got) == count + (lead is not None)
        if lead is not None:
            assert int(slot) == lead
        elif got:
            assert int(slot) == min(got, key=lambda i: (key[i], seq[i]))
        else:
            assert slot_fn[int(slot)] < 0


def spec_with(**kw):
    base = dict(traces=[ArrayTrace.make(arrays(1), "t")],
                policies=("faascache",), capacities=(2, 4),
                slot_mb=SLOT_MB)
    return ExperimentSpec(**dict(base, **kw))


@pytest.mark.parametrize("kw", [
    dict(policies=("esff",)), dict(policies=("esff_h",)),
    dict(policies=("openwhisk_v2",)),
    dict(cluster=ClusterSpec(n_nodes=2, router="random"),
         capacities=(4,))],
    ids=["esff", "esff_h", "openwhisk_v2", "cluster"])
def test_no_memory_semantics_is_refused(kw):
    with pytest.raises(ValueError, match="slot_mb"):
        spec_with(**kw).validate()


def footprints(mem):
    a = arrays(1)
    if mem is None:
        del a["mem_mb"]
    else:
        a["mem_mb"] = mem
    return [ArrayTrace.make(a, "t")]


@pytest.mark.parametrize("traces", [
    footprints(None), footprints(np.full(F, 127)),
    footprints(np.full(F, 2 * SLOT_MB + 1)),
    footprints(np.full(F, 200.5))],
    ids=["missing", "below_128", "above_smallest_lane", "fraction"])
def test_bad_footprints_are_refused(traces):
    with pytest.raises(ValueError, match="mem_mb"):
        run_experiment(spec_with(traces=traces))


@pytest.mark.parametrize("policy", ["esff", "openwhisk_hol",
                                    "faascache_hol"])
def test_python_engine_refuses_what_has_no_memory(policy):
    """The strict head-of-line ablations subclass the central-queue
    policies but are not `MEMORY_POLICIES`: refused like esff."""
    tr = Trace.from_arrays(arrays(1))
    with pytest.raises(ValueError, match="memory"):
        simulate(tr, policy, 2, slot_mb=SLOT_MB)
    with pytest.raises(ValueError, match="mem_mb"):
        simulate(tr, "sff", 1, slot_mb=128)


@pytest.mark.parametrize("policy,capacity,match", [
    ("esff", 2, "memory"), ("sff", 1, "mem_mb")])
def test_jax_engine_refuses_what_has_no_memory(policy, capacity, match):
    tr = Trace.from_arrays(arrays(1))
    with pytest.raises(ValueError, match=match):
        simulate_policy_from_trace(tr, policy, capacity, queue_cap=N,
                                   slot_mb=SLOT_MB // capacity)


@pytest.mark.parametrize("memory", [False, True])
def test_admission_carries_its_named_scope(memory):
    """A node of memory lowers its admission under ``repro.admit``
    (inside ``repro.policy``), so a default-mode device trace splits it
    out of a step; the slot-count program has no such scope."""
    import jax
    from repro.core.jax_engine import _sweep_metrics
    from repro.core.jax_policies import KERNELS
    S = jax.ShapeDtypeStruct
    L, C = 2, 8
    extra = (dict(mem_mb=S((1, F), jnp.int32), node_mb=S((L,), jnp.int32))
             if memory else {})
    text = _sweep_metrics.trace(
        S((1, N), jnp.int32), S((1, N), jnp.float64),
        S((1, N), jnp.float64), S((1, F), jnp.float64),
        S((1, F), jnp.float64), S((L,), jnp.int32), S((L, C), jnp.bool_),
        S((L,), jnp.float64), S((), jnp.float64), S((), jnp.float64),
        **extra, kernel=KERNELS["faascache"], n_fns=F, capacity=C,
        queue_cap=N, stream=True).lower().as_text(debug_info=True)
    assert ("repro.admit" in text) is memory
    assert "repro.policy" in text
