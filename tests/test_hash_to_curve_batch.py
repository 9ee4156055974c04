"""``hash_to_g2_uncleared_many``: the batched hash to Q0 + Q1 returns the
points ``hash_to_g2_uncleared`` returns, message for message, for four
modular exponentiations a message and three a batch; its two-exponentiation
Fq2 root agrees with ``Fq2.sqrt`` up to sign; and a lane that meets an
exceptional case is hashed by the per-message path."""

import random

import pytest

from lighthouse_tpu.crypto.bls import hash_to_curve as h2c
from lighthouse_tpu.crypto.bls.fields import Fq, Fq2
from lighthouse_tpu.crypto.bls.params import DST, P


def _messages(n: int, seed: int = 28) -> list:
    rng = random.Random(seed)
    return [rng.randbytes(32) for _ in range(n)]


def _golden(msgs):
    return [h2c.hash_to_g2_uncleared(m, DST) for m in msgs]


@pytest.mark.parametrize("msgs", [
    [],
    _messages(1),
    _messages(2),
    _messages(11),
    _messages(67),
    [b"a" * 32, b"b" * 32, b"a" * 32, b"a" * 32, b""],
], ids=["empty", "one", "two", "eleven", "sixty_seven", "repeated"])
def test_batch_equals_per_message_hash(msgs):
    got, modexp = h2c.hash_to_g2_uncleared_many(msgs, DST)
    assert got == _golden(msgs)
    assert all(isinstance(c, Fq2) for pt in got for c in pt)
    assert modexp == (4 * len(msgs) + 3 if msgs else 0)


def test_fixed_messages_take_both_sswu_branches(monkeypatch):
    """The 67 messages above root gx1 for some u and gx2 for others, so the
    equality checks cover both branches of the SSWU map."""
    taken = []
    real = h2c._sswu_root

    def spy(u, zu2, x1):
        x, y = real(u, zu2, x1)
        taken.append("gx1" if x == x1 else "gx2")
        return x, y

    monkeypatch.setattr(h2c, "_sswu_root", spy)
    msgs = _messages(67)
    got, _ = h2c.hash_to_g2_uncleared_many(msgs, DST)
    assert got == _golden(msgs)
    assert len(taken) == 2 * len(msgs)
    assert {"gx1", "gx2"} <= set(taken)


def _non_residue_fq() -> int:
    return next(n for n in range(2, 100) if not Fq(n).is_square())


def _nonsquare_d_input() -> Fq2:
    """A square of Fq2 whose d = (a0 + s)/2 is not square in Fq, so the
    root comes from -d."""
    rng = random.Random(7)
    while True:
        a = Fq2(rng.randrange(P), rng.randrange(P)).square()
        n = (a.c0 * a.c0 + a.c1 * a.c1) % P
        s = pow(n, (P + 1) // 4, P)
        if not Fq((a.c0 + s) * h2c._INV2).is_square():
            return a


def _nonsquare_input() -> Fq2:
    rng = random.Random(9)
    while True:
        a = Fq2(rng.randrange(P), rng.randrange(P))
        if not a.is_square():
            return a


@pytest.mark.parametrize("a", [
    Fq2(9, 0),                          # a1 = 0, a0 square
    Fq2(_non_residue_fq(), 0),          # a1 = 0, a0 not square
    Fq2(P - 4, 0),                      # a1 = 0, -1 times a square
    _nonsquare_d_input(),               # d not square: the root of -d
    Fq2(0, 0),                          # zero
    Fq2(3, 4).square(),                 # a generic square
    _nonsquare_input(),                 # not a square: None
], ids=["a1_zero_a0_square", "a1_zero_a0_nonsquare", "a1_zero_minus_square",
        "d_nonsquare", "zero", "generic", "nonsquare"])
def test_two_exponentiation_root_agrees_with_fq2_sqrt(a):
    want = a.sqrt()
    got, s = h2c._sqrt(a)
    n = (a.c0 * a.c0 + a.c1 * a.c1) % P
    assert s * s % P == (n if want is not None else -n % P)
    if want is None:
        assert got is None
        return
    assert got is not None and got.square() == a
    assert got in (want, -want)


@pytest.mark.parametrize("a", [Fq2(9, 0), Fq2(_non_residue_fq(), 0),
                               Fq2(3, 4).square()],
                         ids=["a0_square", "a0_nonsquare", "generic"])
def test_root_from_either_norm_root(a):
    """The gx2 branch hands over a root of the norm whose sign it does not
    choose: both signs give a root."""
    n = (a.c0 * a.c0 + a.c1 * a.c1) % P
    s = pow(n, (P + 1) // 4, P)
    for r in (s, P - s):
        assert h2c._sqrt_with_norm_root(a, r).square() == a


def _patch_field(monkeypatch, target: bytes, us):
    """hash_to_field returns ``us`` for ``target``, for both paths."""
    real = h2c.hash_to_field_fq2
    monkeypatch.setattr(
        h2c, "hash_to_field_fq2",
        lambda m, count, dst: list(us) if m == target else real(m, count, dst))


def _exceptional(case, monkeypatch, msgs):
    """Make lane 1 of ``msgs`` hit ``case``."""
    target = msgs[1]
    u0, u1 = h2c.hash_to_field_fq2(target, 2, DST)
    if case == "tv_zero":
        _patch_field(monkeypatch, target, [Fq2(0, 0), u1])
    elif case == "doubling":
        _patch_field(monkeypatch, target, [u0, u0])
    elif case == "infinity":  # Q1 = -Q0
        _patch_field(monkeypatch, target, [u0, -u0])
    elif case == "iso_kernel":  # Q0 maps to infinity: XDEN vanishes at its x
        x, _ = h2c.map_to_curve_simple_swu(u0)
        xden = list(h2c._XDEN)
        xden[0] = xden[0] - h2c._horner(xden, x)
        monkeypatch.setattr(h2c, "_XDEN", xden)


@pytest.mark.parametrize("case", ["tv_zero", "doubling", "infinity", "iso_kernel"])
def test_exceptional_lane_takes_the_per_message_path(monkeypatch, case):
    msgs = _messages(4, seed=3)
    _exceptional(case, monkeypatch, msgs)
    routed = []
    real = h2c.hash_to_g2_uncleared
    monkeypatch.setattr(h2c, "hash_to_g2_uncleared",
                        lambda m, dst: routed.append(m) or real(m, dst))
    got, modexp = h2c.hash_to_g2_uncleared_many(msgs, DST)
    assert routed == [msgs[1]]
    want = [real(m, DST) for m in msgs]
    assert got == want
    if case == "infinity":
        assert got[1] is None
    elif case == "doubling":
        q0 = h2c.iso_map(h2c.map_to_curve_simple_swu(
            h2c.hash_to_field_fq2(msgs[1], 2, DST)[0]))
        assert got[1] == h2c.add(q0, q0)
    # the routed lane spends no root when caught before the roots, and its
    # roots are spent (and counted) when caught after them
    assert modexp == 4 * (len(msgs) - (case == "tv_zero")) + 3


def test_modexp_is_four_a_message_and_three_a_batch():
    for n in (1, 5, 23):
        _, modexp = h2c.hash_to_g2_uncleared_many(_messages(n, seed=n), DST)
        assert modexp == 4 * n + 3
