"""Pairing correctness: subgroup orders, bilinearity, product check."""

from zkp2p_tpu.curve.host import (
    G1_GENERATOR,
    G2_GENERATOR,
    g1_is_on_curve,
    g1_mul,
    g1_neg,
    g2_is_on_curve,
    g2_mul,
)
from zkp2p_tpu.field.bn254 import R
from zkp2p_tpu.pairing.pairing import pairing, pairing_product_is_one
from zkp2p_tpu.field.tower import Fq12


def test_generators_on_curve():
    assert g1_is_on_curve(G1_GENERATOR)
    assert g2_is_on_curve(G2_GENERATOR)


def test_group_order():
    assert g1_mul(G1_GENERATOR, R) is None
    assert g2_mul(G2_GENERATOR, R) is None


def test_pairing_nondegenerate():
    e = pairing(G1_GENERATOR, G2_GENERATOR)
    assert e != Fq12.one()
    assert e.pow(R) == Fq12.one()


def test_bilinearity():
    a, b = 31337, 271828
    e = pairing(G1_GENERATOR, G2_GENERATOR)
    assert pairing(g1_mul(G1_GENERATOR, a), g2_mul(G2_GENERATOR, b)) == e.pow(a * b)
    assert pairing(g1_mul(G1_GENERATOR, a * b % R), G2_GENERATOR) == e.pow(a * b)


def test_pairing_product():
    a, b = 99991, 10007
    assert pairing_product_is_one(
        [
            (g1_neg(g1_mul(G1_GENERATOR, a * b % R)), G2_GENERATOR),
            (g1_mul(G1_GENERATOR, a), g2_mul(G2_GENERATOR, b)),
        ]
    )
    assert not pairing_product_is_one(
        [
            (g1_mul(G1_GENERATOR, a), G2_GENERATOR),
            (g1_mul(G1_GENERATOR, b), G2_GENERATOR),
        ]
    )


# ------------------------------------------------------ the native pairing
#
# csrc bn254_pairing_product_is_one (the Miller loops and final
# exponentiation under the service's sample verify) against this module's
# Python pairing.

import random

import pytest

from zkp2p_tpu.native import lib as native

needs_native = pytest.mark.skipif(native.get_lib() is None, reason="native toolchain unavailable")


def _native_product(pairs):
    from zkp2p_tpu.snark.native_verify import pairing_product_is_one as product

    return product(native.get_lib(), pairs)


@needs_native
@pytest.mark.parametrize("seed", range(8))
def test_native_bilinearity(seed):
    """e(aP, bQ) e(-ab P, Q) = 1 for random a, b — and not with ab + 1."""
    rng = random.Random(seed)
    a, b = rng.randrange(1, R), rng.randrange(1, R)
    left = (g1_mul(G1_GENERATOR, a), g2_mul(G2_GENERATOR, b))
    assert _native_product([left, (g1_neg(g1_mul(G1_GENERATOR, a * b % R)), G2_GENERATOR)])
    assert not _native_product([left, (g1_neg(g1_mul(G1_GENERATOR, (a * b + 1) % R)), G2_GENERATOR)])


def _product_cases():
    a, b, c = 99991, 10007, 31337
    P1, Q1 = G1_GENERATOR, G2_GENERATOR
    return {
        "empty": [],
        "one-pair": [(P1, Q1)],
        "two-ways": [(g1_neg(g1_mul(P1, a * b % R)), Q1), (g1_mul(P1, a), g2_mul(Q1, b))],
        "unbalanced": [(g1_mul(P1, a), Q1), (g1_mul(P1, b), Q1)],
        # a point at infinity on either side is a factor of one
        "infinity-g1": [(None, Q1), (g1_mul(P1, c), Q1), (g1_neg(P1), g2_mul(Q1, c))],
        "infinity-g2": [(P1, None), (g1_mul(P1, c), Q1), (g1_neg(P1), g2_mul(Q1, c))],
        "all-infinity": [(None, Q1), (P1, None)],
        "three-pairs": [
            (g1_mul(P1, a), g2_mul(Q1, b)), (g1_mul(P1, c), Q1), (g1_neg(g1_mul(P1, (a * b + c) % R)), Q1),
        ],
    }


@needs_native
@pytest.mark.parametrize("case", list(_product_cases()))
def test_native_product_matches_python(case):
    pairs = _product_cases()[case]
    assert _native_product(pairs) == pairing_product_is_one(pairs)
