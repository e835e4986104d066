"""Training substrate: loss decreases, microbatch equivalence, optimizer
numerics (incl. int8 nu quantisation), gradient compression bounds."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.distributed.compression import psum_int8, quantize_roundtrip
from repro.models import build_model
from repro.optim import AdamWConfig, adamw_init, adamw_update
from repro.train import TrainConfig, make_train_step, synthetic_lm_batches
from repro.train.train_step import init_optimizer


@pytest.fixture(scope="module")
def tiny_setup():
    cfg = get_arch("qwen3-4b").smoke().replace(n_layers=2, d_model=64,
                                               d_ff=128, vocab_size=256)
    model = build_model(cfg)
    return cfg, model


def test_loss_decreases(tiny_setup):
    cfg, model = tiny_setup
    tcfg = TrainConfig(optimizer=AdamWConfig(lr=1e-3))
    step = jax.jit(make_train_step(model, tcfg), donate_argnums=(0, 1))
    params, _ = model.init(jax.random.key(0))
    opt = init_optimizer(tcfg, params)
    losses = []
    for batch in synthetic_lm_batches(cfg, 8, 64, 30, seed=0):
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        params, opt, m = step(params, opt, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.5, losses[::10]


def test_microbatch_equivalence(tiny_setup):
    """mb=1 and mb=4 must produce (nearly) the same update."""
    cfg, model = tiny_setup
    from repro.train.data import synthetic_lm_batch
    batch = {k: jnp.asarray(v)
             for k, v in synthetic_lm_batch(cfg, 8, 32, 0).items()}
    outs = {}
    for mb in (1, 4):
        tcfg = TrainConfig(microbatches=mb,
                           optimizer=AdamWConfig(lr=1e-3))
        step = jax.jit(make_train_step(model, tcfg))
        params, _ = model.init(jax.random.key(1))
        opt = init_optimizer(tcfg, params)
        p2, _, m = step(params, opt, batch)
        outs[mb] = (p2, float(m["loss"]))
    assert outs[1][1] == pytest.approx(outs[4][1], rel=1e-5)
    for a, b in zip(jax.tree.leaves(outs[1][0]),
                    jax.tree.leaves(outs[4][0])):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-4, atol=1e-5)


def test_adamw_matches_reference_update():
    cfg = AdamWConfig(lr=0.1, b1=0.9, b2=0.999, weight_decay=0.0,
                      grad_clip=0.0)
    p = {"w": jnp.ones((4, 4))}
    g = {"w": jnp.full((4, 4), 0.5)}
    st = adamw_init(cfg, p)
    p2, st2, _ = adamw_update(cfg, p, g, st)
    # step 1: mu_hat = g, nu_hat = g^2 -> delta = g/|g| = 1
    np.testing.assert_allclose(np.asarray(p2["w"]),
                               np.ones((4, 4)) - 0.1 * (0.5 / 0.5),
                               rtol=1e-5)


def test_adamw_quantized_nu_close_to_exact():
    rng = np.random.default_rng(0)
    p = {"w": jnp.asarray(rng.normal(size=(64, 256)), jnp.float32)}
    exact = AdamWConfig(lr=1e-2, grad_clip=0.0)
    quant = AdamWConfig(lr=1e-2, grad_clip=0.0, quantize_nu=True)
    st_e, st_q = adamw_init(exact, p), adamw_init(quant, p)
    pe, pq = p, p
    for i in range(5):
        g = {"w": jnp.asarray(rng.normal(size=(64, 256)), jnp.float32)}
        pe, st_e, _ = adamw_update(exact, pe, g, st_e)
        pq, st_q, _ = adamw_update(quant, pq, g, st_q)
    err = np.abs(np.asarray(pe["w"]) - np.asarray(pq["w"])).max()
    upd = np.abs(np.asarray(pe["w"]) - np.asarray(p["w"])).max()
    assert err < 0.12 * upd, (err, upd)   # int8 nu: small relative error


def test_quantize_roundtrip_error_bound():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(10_240,)), jnp.float32)
    y = quantize_roundtrip(x, block=256)
    blocks = np.asarray(x).reshape(-1, 256)
    bound = np.abs(blocks).max(1, keepdims=True) / 127.0
    err = np.abs(np.asarray(y).reshape(-1, 256) - blocks)
    assert (err <= bound + 1e-7).all()


def test_train_step_with_compression_still_learns(tiny_setup):
    cfg, model = tiny_setup
    tcfg = TrainConfig(optimizer=AdamWConfig(lr=1e-3),
                       compress_grads=True)
    step = jax.jit(make_train_step(model, tcfg))
    params, _ = model.init(jax.random.key(2))
    opt = init_optimizer(tcfg, params)
    losses = []
    for batch in synthetic_lm_batches(cfg, 8, 64, 20, seed=3):
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        params, opt, m = step(params, opt, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.3


def test_psum_int8_single_device():
    # axis of size 1: psum_int8 must be a (quantised) identity
    from jax.sharding import Mesh
    import jax.numpy as jnp
    mesh = jax.make_mesh((1,), ("d",))
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1024,)),
                    jnp.float32)
    out = jax.jit(
        jax.shard_map(lambda v: psum_int8(v, "d"), mesh=mesh,
                      in_specs=jax.sharding.PartitionSpec(),
                      out_specs=jax.sharding.PartitionSpec()))(x)
    err = np.abs(np.asarray(out) - np.asarray(x))
    bound = np.abs(np.asarray(x)).reshape(-1, 256).max(1) / 127.0
    assert (err.reshape(-1, 256) <= bound[:, None] + 1e-6).all()
