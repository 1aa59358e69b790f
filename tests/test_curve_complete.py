"""The complete projective formulas (RCB 2016, algorithms 7, 8, 9 for
a = 0) on the XLA path, lane by lane against `curve/host.py`'s integers:
every lane that a Jacobian add needed a case for (P + P, P + (-P)) and
every encoding of infinity the tree uses (the all-zero triple of a pad,
the (0 : y : 0) that P + (-P) yields, the (0, 0) affine sentinel).

Tier-1: one compiled program a (curve, op), all lanes in it; the kernels'
lane-for-lane differentials against these same formulas are the slow
tests of tests/test_pallas_curve.py."""

import random
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from zkp2p_tpu.curve import host
from zkp2p_tpu.curve.jcurve import G1J, G2J, g1_jac_to_host, g2_jac_to_host
from zkp2p_tpu.field.bn254 import P, R
from zkp2p_tpu.field.jfield import FQ
from zkp2p_tpu.field.tower import Fq2


class _G1:
    curve, to_host = G1J, staticmethod(g1_jac_to_host)
    add, neg = staticmethod(host.g1_add), staticmethod(host.g1_neg)

    @staticmethod
    def point(rng):
        return host.g1_mul(host.G1_GENERATOR, rng.randrange(1, R))

    @staticmethod
    def scalar(rng):  # a non-zero element of the coordinate field
        return rng.randrange(1, P)

    @staticmethod
    def limbs(x):
        return FQ.to_mont_host(x % P)


class _G2:
    curve, to_host = G2J, staticmethod(g2_jac_to_host)
    add, neg = staticmethod(host.g2_add), staticmethod(host.g2_neg)

    @staticmethod
    def point(rng):
        return host.g2_mul(host.G2_GENERATOR, rng.randrange(1, R))

    @staticmethod
    def scalar(rng):
        return Fq2(rng.randrange(1, P), rng.randrange(P))

    @staticmethod
    def limbs(x):
        x = x if isinstance(x, Fq2) else Fq2(x, 0)
        return np.stack([FQ.to_mont_host(x.c0), FQ.to_mont_host(x.c1)])


GROUPS = {"g1": _G1, "g2": _G2}
ZERO, Y_ONLY = "zero", "y_only"  # the two projective encodings of infinity


def _proj(g, pts, zs):
    """Host points -> projective limb arrays (xZ : yZ : Z); a None point
    takes its encoding of infinity from `zs`: the all-zero triple or
    (0 : y : 0)."""
    cols = []
    for pt, z in zip(pts, zs):
        if pt is None:
            cols.append((0, 0 if z == ZERO else 7, 0))
        else:
            cols.append((pt[0] * z, pt[1] * z, z))
    return tuple(jnp.asarray(np.stack([g.limbs(c[i]) for c in cols])) for i in range(3))


def _affine(g, pts):
    return tuple(jnp.asarray(np.stack([g.limbs(0 if pt is None else pt[i]) for pt in pts])) for i in range(2))


# name -> (left, right) as functions of four random points; None = infinity
ADD_LANES = {
    "generic": lambda a, b, c, d: (a, b),
    "equal": lambda a, b, c, d: (c, c),
    "opposite": lambda a, b, c, d: (d, "neg"),
    "inf_plus_q": lambda a, b, c, d: (None, b),
    "p_plus_inf": lambda a, b, c, d: (a, None),
    "inf_plus_inf": lambda a, b, c, d: (None, None),
}
DOUBLE_LANES = ["generic", "inf_zero", "inf_y_only"]


@lru_cache(maxsize=None)
def _lanes(group, op):
    """{lane name: (got, want)} for one (curve, op): one program, every lane."""
    g = GROUPS[group]
    rng = random.Random(f"{group}/{op}")
    pts = [g.point(rng) for _ in range(4)]
    if op == "double":
        left = [pts[0], None, None]
        got = g.to_host(jax.jit(g.curve.double)(_proj(g, left, [g.scalar(rng), ZERO, Y_ONLY])))
        return dict(zip(DOUBLE_LANES, zip(got, [g.add(p, p) for p in left])))
    left, right = [], []
    for mk in ADD_LANES.values():
        l, r = mk(*pts)
        left.append(l)
        right.append(g.neg(l) if r == "neg" else r)
    # Z != 1 on the left in every finite lane; infinity on the left once as
    # the zero triple and once as (0 : y : 0)
    p = _proj(g, left, [g.scalar(rng) for _ in range(3)] + [ZERO] + [g.scalar(rng)] + [Y_ONLY])
    if op == "add":
        # Z != 1 on the right too; P + inf meets the zero triple, inf + inf the other one
        q = _proj(g, right, [g.scalar(rng) for _ in range(4)] + [ZERO, Y_ONLY])
        got = g.to_host(jax.jit(g.curve.add)(p, q))
    else:
        got = g.to_host(jax.jit(g.curve.add_mixed)(p, _affine(g, right)))  # None -> the (0, 0) sentinel
    return dict(zip(ADD_LANES, zip(got, [g.add(l, r) for l, r in zip(left, right)])))


@pytest.mark.parametrize(
    "group,op,lane",
    [(g, op, lane) for g in GROUPS for op in ("add", "add_mixed") for lane in ADD_LANES]
    + [(g, "double", lane) for g in GROUPS for lane in DOUBLE_LANES],
)
def test_complete_formula_lane_equals_host_point(group, op, lane):
    got, want = _lanes(group, op)[lane]
    assert got == want
