"""The HLO cost analyzer must agree with XLA on loop-free programs and
correctly multiply while-loop trip counts (which XLA's cost_analysis does
NOT — the motivating bug)."""
import jax
import jax.numpy as jnp
import pytest
from jax import lax

from repro.roofline.hlo_cost import analyze_hlo


@pytest.fixture(scope="module")
def xla_flops():
    def scanned(a):
        y, _ = lax.scan(lambda c, _: (c @ c, None), a, None, length=12)
        return y

    cases = {
        "matmul": (lambda a: a @ a,
                   [jax.ShapeDtypeStruct((256, 256), jnp.float32)]),
        "scan": (scanned,
                 [jax.ShapeDtypeStruct((128, 128), jnp.float32)]),
        "einsum": (lambda x, w: jnp.einsum("bsd,df->bsf", x, w),
                   [jax.ShapeDtypeStruct((8, 32, 64), jnp.float32),
                    jax.ShapeDtypeStruct((64, 128), jnp.float32)]),
    }
    out = {}
    for name, (fn, args) in cases.items():
        c = jax.jit(fn).lower(*args).compile()
        out[name] = dict(mine=analyze_hlo(c.as_text()).flops,
                         theirs=c.cost_analysis()["flops"])
    return out


def test_matches_xla_on_plain_matmul(xla_flops):
    got = xla_flops["matmul"]
    assert got["mine"] == pytest.approx(got["theirs"], rel=1e-6)
    assert got["mine"] == pytest.approx(2 * 256 ** 3, rel=1e-6)


def test_scan_flops_multiplied_by_trip_count(xla_flops):
    got = xla_flops["scan"]
    one = 2 * 128 ** 3
    # XLA counts the body once; we must count it 12x.
    assert got["theirs"] == pytest.approx(one, rel=1e-6)
    assert got["mine"] == pytest.approx(12 * one, rel=1e-6)


def test_einsum_flops(xla_flops):
    got = xla_flops["einsum"]
    assert got["mine"] == pytest.approx(2 * 8 * 32 * 64 * 128, rel=1e-6)
    assert got["mine"] == pytest.approx(got["theirs"], rel=1e-6)


def test_nested_scan_multiplies():
    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)

    def inner(a):
        y, _ = lax.scan(lambda c, _: (c @ c, None), a, None, length=5)
        return y

    def outer(a):
        y, _ = lax.scan(lambda c, _: (inner(c), None), a, None, length=3)
        return y

    c = jax.jit(outer).lower(x).compile()
    mine = analyze_hlo(c.as_text())
    assert mine.flops == pytest.approx(15 * 2 * 64 ** 3, rel=1e-6)


def test_bytes_nonzero_and_scaled():
    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)

    def scanned(a):
        y, _ = lax.scan(lambda c, _: (jnp.tanh(c @ c), None), a, None,
                        length=4)
        return y

    c = jax.jit(scanned).lower(x).compile()
    mine = analyze_hlo(c.as_text())
    assert mine.bytes_accessed > 4 * (128 * 128 * 4) * 2


def test_collectives_counted(monkeypatch):
    hlo = """
HloModule test

%cond (p: (s32[], f32[8])) -> pred[] {
  %p = (s32[], f32[8]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %k = s32[] constant(7)
  ROOT %lt = pred[] compare(%i, %k), direction=LT
}

%body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[8] get-tuple-element(%p), index=1
  %ar = f32[8]{0} all-reduce(%x), replica_groups={}, to_apply=%sum
  %one = s32[] constant(1)
  %ip = s32[] add(%i, %one)
  ROOT %t = (s32[], f32[8]) tuple(%ip, %ar)
}

%sum (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(%a, %b)
}

ENTRY %main (x: f32[8]) -> (s32[], f32[8]) {
  %x = f32[8]{0} parameter(0)
  %z = s32[] constant(0)
  %t0 = (s32[], f32[8]) tuple(%z, %x)
  ROOT %w = (s32[], f32[8]) while(%t0), condition=%cond, body=%body
}
"""
    got = analyze_hlo(hlo)
    assert got.collective_bytes["all-reduce"] == pytest.approx(7 * 32)
    assert got.collective_counts["all-reduce"] == 7
