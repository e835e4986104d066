"""Per-function slot counts (`k_counts`, `cold_counts`, `fn_count`).

The ESFF kernels read these on every event step. They are a dense
compare-and-sum over the slot axis; the scatter-add histogram they
replaced is kept here as the reference, and the two must agree bit for
bit on every rail the engines hand the helpers: the single-node loop's
lane-vmapped (L, C) rails and one node's (C,) row of a cluster lane's
(K, C) rails.
"""
from types import SimpleNamespace

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core.jax_engine import (BUSY, COLD, IDLE, cold_counts,  # noqa: E402
                                   ensure_x64, fn_count, k_counts)

ensure_x64()

import jax.numpy as jnp  # noqa: E402

F, C = 23, 16


def _scatter_counts(slot_fn, mask):
    """The scatter-add histogram the dense count replaced."""
    return jnp.zeros((F,), jnp.int32).at[
        jnp.where(mask, slot_fn, jnp.int32(F))
    ].add(jnp.int32(1), mode="drop")


def _rails(rng, rows):
    """``rows`` random slot rails: empty (-1) slots, every state, and
    slots beyond a row's capacity left empty, as the engines keep them
    (their state is random here, so a count that read the state alone
    would show)."""
    slot_fn = rng.integers(-1, F, size=(rows, C)).astype(np.int32)
    slot_fn[rng.random((rows, C)) < 0.3] = -1
    slot_fn[:, :3] = rng.integers(0, 2, size=(rows, 3))  # repeats
    caps = rng.integers(1, C + 1, size=rows)
    slot_fn[np.arange(C)[None, :] >= caps[:, None]] = -1
    slot_state = rng.choice(np.array([COLD, IDLE, BUSY], np.int32),
                            size=(rows, C))
    return dict(slot_fn=jnp.asarray(slot_fn),
                slot_state=jnp.asarray(slot_state))


@pytest.mark.parametrize("cold", [False, True], ids=["k", "cold"])
@pytest.mark.parametrize("layout", ["lanes", "cluster_node"])
def test_dense_slot_counts_match_scatter_add(layout, cold):
    rng = np.random.default_rng(17 + 2 * cold)
    ctx = SimpleNamespace(F=F)
    counts = cold_counts if cold else k_counts

    def both(s):
        mask = s["slot_fn"] >= 0
        if cold:
            mask = mask & (s["slot_state"] == COLD)
        scalar = jax.vmap(lambda j: fn_count(s, j, cold=cold))(
            jnp.arange(F, dtype=jnp.int32))
        return counts(ctx, s), _scatter_counts(s["slot_fn"], mask), scalar

    if layout == "lanes":      # the single-node loop: (L, C) under vmap
        s = _rails(rng, 7)
        got, ref, scalar = jax.jit(jax.vmap(both))(s)
        assert got.shape == (7, F)
    else:                      # one node's row of a (K, C) cluster lane
        stack = _rails(rng, 5)
        got, ref, scalar = [], [], []
        for node in range(5):
            view = {key: a[node] for key, a in stack.items()}
            for out, x in zip((got, ref, scalar), jax.jit(both)(view)):
                out.append(x)
        got, ref, scalar = map(jnp.stack, (got, ref, scalar))
    assert got.dtype == ref.dtype == scalar.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    np.testing.assert_array_equal(np.asarray(scalar), np.asarray(ref))
    assert int(ref.sum()) > 0
