"""JAX complete-formula curve ops vs the host golden model."""

import random
from functools import partial
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lighthouse_tpu.crypto.bls import curve
from lighthouse_tpu.crypto.bls import fields as hf
from lighthouse_tpu.crypto.bls.params import P
from lighthouse_tpu.ops import ec

rng = random.Random(0xEC)


def rand_g1():
    return curve.mul(curve.G1, rng.randrange(1, curve.R))


def rand_g2():
    return curve.mul(curve.G2, rng.randrange(1, curve.R))


def jpt1(pt):
    return tuple(jnp.asarray(c) for c in ec.g1_to_limbs(pt))


def jpt2(pt):
    return tuple(jnp.asarray(c) for c in ec.g2_to_limbs(pt))


add1 = jax.jit(partial(ec.point_add, ec.G1_OPS))
dbl1 = jax.jit(partial(ec.point_double, ec.G1_OPS))
add2 = jax.jit(partial(ec.point_add, ec.G2_OPS))
dbl2 = jax.jit(partial(ec.point_double, ec.G2_OPS))


def test_g1_add_double():
    p, q = rand_g1(), rand_g1()
    assert ec.g1_from_limbs(add1(jpt1(p), jpt1(q))) == curve.add(p, q)
    assert ec.g1_from_limbs(dbl1(jpt1(p))) == curve.double(p)


def test_g1_complete_edge_cases():
    p = rand_g1()
    inf = jpt1(None)
    # P + inf, inf + P, inf + inf, P + P (add used as double), P + (-P)
    assert ec.g1_from_limbs(add1(jpt1(p), inf)) == p
    assert ec.g1_from_limbs(add1(inf, jpt1(p))) == p
    assert ec.g1_from_limbs(add1(inf, inf)) is None
    assert ec.g1_from_limbs(add1(jpt1(p), jpt1(p))) == curve.double(p)
    assert ec.g1_from_limbs(add1(jpt1(p), jpt1(curve.neg(p)))) is None
    assert ec.g1_from_limbs(dbl1(inf)) is None


def test_g2_add_double():
    p, q = rand_g2(), rand_g2()
    assert ec.g2_from_limbs(add2(jpt2(p), jpt2(q))) == curve.add(p, q)
    assert ec.g2_from_limbs(dbl2(jpt2(p))) == curve.double(p)
    assert ec.g2_from_limbs(add2(jpt2(p), jpt2(curve.neg(p)))) is None


def test_scalar_mul_g1():
    p = rand_g1()
    for k in [1, 2, 3, 0xDEADBEEF, (1 << 64) - 1, 0]:
        bits = jnp.asarray(ec.bits_msb(k, 64))
        r = jax.jit(partial(ec.scalar_mul_bits, ec.G1_OPS))(jpt1(p), bits)
        assert ec.g1_from_limbs(r) == curve.mul(p, k)


def test_scalar_mul_g2_batched():
    pts = [rand_g2() for _ in range(4)]
    ks = [rng.randrange(1 << 64) for _ in range(4)]
    xs = tuple(
        jnp.stack([jnp.asarray(ec.g2_to_limbs(pt)[i]) for pt in pts]) for i in range(3)
    )
    bits = jnp.asarray(np.stack([ec.bits_msb(k, 64) for k in ks]))
    r = jax.jit(partial(ec.scalar_mul_bits, ec.G2_OPS))(xs, bits)
    for i in range(4):
        got = ec.g2_from_limbs(tuple(c[i] for c in r))
        assert got == curve.mul(pts[i], ks[i])


def test_tree_sum():
    pts = [rand_g1() for _ in range(7)] + [None]  # pad with identity
    xs = tuple(
        jnp.stack([jnp.asarray(ec.g1_to_limbs(pt)[i]) for pt in pts]) for i in range(3)
    )
    r = jax.jit(partial(ec.tree_sum, ec.G1_OPS))(xs)
    expect = None
    for pt in pts:
        expect = curve.add(expect, pt)
    assert ec.g1_from_limbs(r) == expect


# ------------------------------------------------- batched host conversion

_EDGE = [0, 1, 2, (1 << 16) - 1, 1 << 16, P - 2, P - 1]


def _reduced(n):
    """n ints in [0, p): the edge values first, then uniform draws."""
    return (_EDGE + [rng.randrange(P) for _ in range(n)])[:n]


def _stack(rows, shape):
    return np.stack(rows) if rows else np.zeros((0,) + shape, np.int32)


@pytest.mark.parametrize("n", [0, 1, 7, 40])
def test_g1_batch_to_limbs_matches_per_point(n):
    xs, ys = _reduced(n), _reduced(n)[::-1]
    pts = [(hf.Fq(x), hf.Fq(y)) for x, y in zip(xs, ys)]
    got = ec.g1_batch_to_limbs(pts)
    for c in range(2):
        want = _stack([ec.g1_to_limbs(pt)[c] for pt in pts], (25,))
        assert got[c].dtype == np.int32 and got[c].shape == (n, 25)
        assert np.array_equal(got[c], want)


@pytest.mark.parametrize("n", [0, 1, 7, 40])
def test_g2_batch_to_limbs_matches_per_point(n):
    v = _reduced(4 * n)
    pts = [(hf.Fq2(v[4 * i], v[4 * i + 1]), hf.Fq2(v[4 * i + 2], v[4 * i + 3]))
           for i in range(n)]
    got = ec.g2_batch_to_limbs(pts)
    for c in range(2):
        want = _stack([ec.g2_to_limbs(pt)[c] for pt in pts], (2, 25))
        assert got[c].dtype == np.int32 and got[c].shape == (n, 2, 25)
        assert np.array_equal(got[c], want)


def test_batch_limbs_of_curve_points_round_trip():
    pts = [rand_g1() for _ in range(3)]
    x, y = ec.g1_batch_to_limbs(pts)
    one = ec.g1_to_limbs(pts[0])[2]
    for i, pt in enumerate(pts):
        assert ec.g1_from_limbs((x[i], y[i], one)) == pt


@pytest.mark.parametrize("bad", [1 << 400, -1])
def test_batch_to_limbs_refuses_values_past_400_bits(bad):
    """Nothing is silently cut: a coordinate the limbs cannot hold raises."""
    one = SimpleNamespace(n=1)
    with pytest.raises(OverflowError):
        ec.g1_batch_to_limbs([(one, SimpleNamespace(n=bad))])
    with pytest.raises(OverflowError):
        ec.g2_batch_to_limbs([(hf.Fq2(1, 2), SimpleNamespace(c0=bad, c1=1))])


@pytest.mark.parametrize("nbits", [64, 13])
def test_bits_msb_batch_matches_per_scalar(nbits):
    ks = [0, 1, (1 << 64) - 1, 1 << 63, 0xDEADBEEF, 1 << 70, -5] + [
        rng.randrange(1 << 64) for _ in range(9)]
    got = ec.bits_msb_batch(ks, nbits)
    assert got.dtype == np.int32 and got.shape == (len(ks), nbits)
    assert np.array_equal(got, np.stack([ec.bits_msb(k, nbits) for k in ks]))
    assert ec.bits_msb_batch([], nbits).shape == (0, nbits)
