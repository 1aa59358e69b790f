"""Differential test: the fused Pallas G1 point-op kernels vs
curve.jcurve (interpret mode — no TPU needed).

Every lane that was once a special case is pinned: P+Q generic, P+P
(equal operands: a lane like any other to the complete formulas),
P+(-P) (comes out as (0 : y : 0)), inf+Q, P+inf, and the (0, 0) affine
sentinel for add_mixed.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from zkp2p_tpu.curve.host import G1_GENERATOR, g1_mul
from zkp2p_tpu.curve.jcurve import G1J, g1_to_affine_arrays
from zkp2p_tpu.field.jfield import FQ
from zkp2p_tpu.ops.pallas_curve import g1_add, g1_add_mixed, g1_double

# Interpret-mode execution of the fused whole-point-op kernels is ~100x
# slower than compiled; ~5 min for the four tests on the 1-core host.
pytestmark = pytest.mark.slow

rng = np.random.default_rng(4242)


def _points(n):
    return [g1_mul(G1_GENERATOR, int(k)) for k in rng.integers(1, 2**60, n)]


@pytest.fixture(scope="module")
def cases():
    # Lanes (P finite on 1..7 so the special cases bind to FINITE points):
    # [0]=inf+Q, [1]=P+P (equal operands, no case of their own),
    # [2]=P+(-P) (-> (0 : y : 0)), [3]=P+inf, [4]=inf+inf, [5:]=generic.
    aff_p = g1_to_affine_arrays([None] + _points(7))
    aff_q = g1_to_affine_arrays(_points(8))
    P_ = G1J.from_affine(aff_p)
    Q = G1J.from_affine(aff_q)
    lane = jnp.arange(8)

    def force(dst, src, i):
        return tuple(jnp.where((lane == i)[:, None], s, d) for s, d in zip(src, dst))

    Q = force(Q, P_, 1)  # equal (both finite)
    Q = force(Q, G1J.neg(P_), 2)  # negated (both finite)
    # affine-infinity sentinel lanes in q: [3] finite+inf, [4] inf+inf.
    aff_q_inf = tuple(
        jnp.where(((lane == 3) | (lane == 4))[:, None], jnp.zeros_like(c), c) for c in aff_q
    )
    Q = force(Q, G1J.infinity((8,)), 3)
    Q = force(Q, G1J.infinity((8,)), 4)
    return P_, Q, aff_p, aff_q_inf


def _eq(a, b):
    return all(bool(jnp.array_equal(x, y)) for x, y in zip(a, b))


def test_pallas_add_matches_jcurve(cases):
    P_, Q, _, _ = cases
    assert _eq(g1_add(FQ, P_, Q, True), G1J.add(P_, Q))


def test_pallas_add_mixed_matches_jcurve(cases):
    P_, _, _, aff_q = cases
    assert _eq(g1_add_mixed(FQ, P_, aff_q, True), G1J.add_mixed(P_, aff_q))


def test_pallas_double_matches_jcurve(cases):
    P_, _, _, _ = cases
    assert _eq(g1_double(FQ, P_, True), G1J.double(P_))


def test_g2_point_math_matches_jcurve():
    """The G2 kernels run `_add_math`/`_double_math` over `_Fq2Ops` on Ref
    views; running the SAME functions on plain arrays pins the Fq2
    Karatsuba + shared point core against jcurve without paying the
    (prohibitively slow) interpret-mode pallas_call for Fq2 graphs.  The
    pallas_call plumbing itself is the same BlockSpec pattern the G1
    tests above execute end-to-end."""
    import numpy as onp

    from zkp2p_tpu.curve.host import G2_GENERATOR, g2_mul, g2_neg
    from zkp2p_tpu.curve.jcurve import G2J, g2_to_affine_arrays
    from zkp2p_tpu.field.jfield import FQ2
    from zkp2p_tpu.ops.pallas_curve import (
        _consts_g2,
        _add_math,
        _add_mixed_math,
        _double_math,
        _Fq2Ops,
        _FqOps,
    )

    consts = _consts_g2(FQ2)
    f = _Fq2Ops(_FqOps(*consts[:3]), consts[3:])

    def to_lm(c):
        B = int(onp.prod(c.shape[:-2]))
        flat = c.reshape(B, 2, 16)
        return (jnp.moveaxis(flat[:, 0, :], -1, 0), jnp.moveaxis(flat[:, 1, :], -1, 0))

    def from_lm(pair, bshape):
        c0 = jnp.moveaxis(pair[0], 0, -1)
        c1 = jnp.moveaxis(pair[1], 0, -1)
        return jnp.stack([c0, c1], axis=-2).reshape(bshape + (2, 16))

    # lane 1: equal operands, lane 2: negated, lane 3: inf+Q
    pts_p = [g2_mul(G2_GENERATOR, k) for k in (5, 11, 3)] + [None]
    pts_q = [g2_mul(G2_GENERATOR, k) for k in (9, 11, 3, 7)]
    pts_q[2] = g2_neg(pts_q[2])
    P_ = G2J.from_affine(g2_to_affine_arrays(pts_p))
    Q = G2J.from_affine(g2_to_affine_arrays(pts_q))
    p_lm = tuple(to_lm(c) for c in P_)
    q_lm = tuple(to_lm(c) for c in Q)

    got = tuple(from_lm(c, (4,)) for c in _add_math(f, p_lm, q_lm))
    assert _eq(got, G2J.add(P_, Q))
    got = tuple(from_lm(c, (4,)) for c in _double_math(f, *p_lm))
    assert _eq(got, G2J.double(P_))
    aff_q = g2_to_affine_arrays(pts_q)
    got = tuple(from_lm(c, (4,)) for c in _add_mixed_math(f, p_lm, tuple(to_lm(c) for c in aff_q)))
    assert _eq(got, G2J.add_mixed(P_, aff_q))


def test_g2_run_marshalling_roundtrip(monkeypatch):
    """Exercise _run_g2's (…, 2, 16) <-> limb-major pair packing, padding
    and 6-output unpacking through a REAL (interpret-mode) pallas_call, by
    swapping in a pass-through kernel: with outs := ins the wrapper must
    return its input coordinates bit-for-bit.  The heavy Fq2 compute is
    covered by test_g2_point_math_matches_jcurve; this guards the
    plumbing the math test bypasses."""
    from zkp2p_tpu.curve.host import G2_GENERATOR, g2_mul
    from zkp2p_tpu.curve.jcurve import G2J, g2_to_affine_arrays
    from zkp2p_tpu.field.jfield import FQ2
    from zkp2p_tpu.ops import pallas_curve

    def passthrough(*refs):
        ins, outs = refs[:-6], refs[-6:]
        for o, i in zip(outs, ins[:6]):
            o[:] = i[:]

    monkeypatch.setitem(pallas_curve._G2_KERNELS, "double", passthrough)
    # 5 points: not a G2_TILE multiple, so the pad/unpad boundary runs.
    # _run_g2 directly (not the jit-wrapped g2_double) so the patched
    # kernel cannot be shadowed by a previously traced executable.
    P_ = G2J.from_affine(g2_to_affine_arrays([g2_mul(G2_GENERATOR, k) for k in range(3, 8)]))
    got = pallas_curve._run_g2("double", FQ2, P_, True)
    assert _eq(got, P_)


def test_pallas_add_padding_and_batch_dims():
    # Non-TILE-multiple batch + 2D batch dims exercise pad/reshape.
    aff = g1_to_affine_arrays(_points(6))
    P_ = G1J.from_affine(tuple(c.reshape(2, 3, 16) for c in aff))
    got = g1_double(FQ, P_, True)
    want = G1J.double(P_)
    assert got[0].shape == (2, 3, 16)
    assert all(bool(jnp.array_equal(x, y)) for x, y in zip(got, want))
