"""The event loops compile for a TPU v5e at the paper's widths.

Nothing here runs on a chip: the TPU compiler compiles for a described
``v5e:2x2`` topology (one of its devices) from abstract shapes. These
are the compiles that used to kill the process inside the compiler's
loop analysis (see `repro.core.jax_engine.LOOP_COMPILER_OPTIONS`), so
each entry point the runners call is compiled once at F=200 functions
and the paper trace's N=60,000 requests.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""
import re

import pytest

jax = pytest.importorskip("jax")

from repro.core.jax_engine import ensure_x64  # noqa: E402

ensure_x64()

import jax.numpy as jnp  # noqa: E402

F, N = 200, 60_000
RESIL_SHED_OLDEST = (3, 2, 0.05, 1.0, 0.3, 7)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    """``shape(dims, dtype)``: an abstract operand on one v5e chip. The
    persistent compilation cache stays off meanwhile: an executable
    compiled for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    one_chip = SingleDeviceSharding(topo.devices[0])
    yield lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype,
                                                   sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", was)


def _trace_args(shape, L, node_caps):
    return (shape((1, N), jnp.int32), shape((1, N), jnp.float64),
            shape((1, N), jnp.float64), shape((1, F), jnp.float64),
            shape((1, F), jnp.float64), shape((L,), jnp.int32),
            shape((L,) + node_caps, jnp.bool_), shape((L,), jnp.float64),
            shape((), jnp.float64), shape((), jnp.float64))


def _resil_ops(shape):
    return dict(rs_nfail=shape((1, N), jnp.int32),
                rs_tmo=shape((1, N), jnp.bool_),
                rs_key=shape((1, N), jnp.int32))


def _computations(hlo):
    """``{name: [instruction lines]}`` of an HLO module's text."""
    comps, name = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$", line)
        if name is None and head:
            name, comps[head.group(1)] = head.group(1), []
        elif line == "}":
            name = None
        elif name is not None:
            comps[name].append(line)
    return comps


def _loop_scatters(hlo):
    """Scatter instructions in the computations a ``while`` body runs,
    nested fusions, calls and loops included."""
    comps = _computations(hlo)
    callee = re.compile(r"(?:body|condition|to_apply|calls|"
                        r"branch_computations|called_computations)="
                        r"\{?(%[\w.\-]+(?:, *%[\w.\-]+)*)")
    todo = [b for lines in comps.values() for line in lines
            for b in re.findall(r"body=%([\w.\-]+)", line)]
    assert todo, "no while loop in the compiled module"
    seen = set()
    while todo:
        c = todo.pop()
        if c in seen or c not in comps:
            continue
        seen.add(c)
        for line in comps[c]:
            for group in callee.findall(line):
                todo.extend(x.strip().lstrip("%")
                            for x in group.split(","))
    return [line.strip() for c in seen for line in comps[c]
            if re.search(r"\bscatter\(", line)]


@pytest.mark.parametrize("case", [
    # the fig. 5 grid in one call: 6 policies x 7 capacities, streamed;
    # ESFF's per-function slot counts are dense compare-and-sums, so
    # the event step holds no (serialized) scatter
    dict(L=42, stream=True, no_loop_scatter=True),
    # per-request records (`keep_per_request=True`)
    dict(L=7, stream=False, keep_responses=True),
    # retries re-link the per-request `nxt` rail inside the event body
    dict(L=7, stream=True, resil=RESIL_SHED_OLDEST),
    # ESFF-H adds the cold-slot counts to the same step
    dict(L=7, stream=True, policy="esff_h", no_loop_scatter=True),
    # a node of memory: 32 slots of 256 MB hold up to 64 instances of
    # 128 MB, and the victim prefix is a dense rank, not a scatter
    dict(L=7, stream=True, policy="faascache", slots=64, memory=True,
         no_loop_scatter=True),
], ids=["stream", "exact_keep_responses", "resil_shed_oldest",
        "esff_h_stream", "faascache_mem"])
def test_single_node_loop_compiles(shape, case):
    from repro.core.jax_engine import _sweep_metrics
    from repro.core.jax_policies import KERNELS
    case = dict(case)
    L = case.pop("L")
    policy = case.pop("policy", "esff")
    no_loop_scatter = case.pop("no_loop_scatter", False)
    C = case.pop("slots", 32)
    extra = _resil_ops(shape) if "resil" in case else {}
    if case.pop("memory", False):
        extra.update(mem_mb=shape((1, F), jnp.int32),
                     node_mb=shape((L,), jnp.int32))
    compiled = _sweep_metrics.lower(
        *_trace_args(shape, L, (C,)), **extra, kernel=KERNELS[policy],
        n_fns=F, capacity=C, queue_cap=N, **case).compile()
    hlo = compiled.as_text()
    assert hlo
    if no_loop_scatter:
        assert _loop_scatters(hlo) == []


def test_cluster_loop_compiles(shape):
    from repro.cluster.engine import _cluster_metrics
    from repro.cluster.routers import get_router
    from repro.core.jax_policies import KERNELS
    K, L = 4, 7
    compiled = _cluster_metrics.lower(
        *_trace_args(shape, L, (K, 8)), shape((K,), jnp.float64),
        kernel=KERNELS["esff"], router=get_router("jsq2"), n_nodes=K,
        n_fns=F, capacity=8, queue_cap=N, stream=True).compile()
    assert compiled.as_text()
