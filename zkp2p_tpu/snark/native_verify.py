"""The sample verify without the interpreter.

`snark.groth16.verify` is ~0.47 s of Python big-int tower arithmetic — a
G2 scalar multiplication by `R`, `vk_x`, four Miller loops, a generic
`pow` in Fq12 — whatever the circuit.  The proving service runs it once a
batch on the proving thread, after `finish` and before `emit`, with
nothing on the device.  This module runs the SAME check — A and C on the
curve, B on the twist and of order `R`, `vk_x = ic[0] + Σ xᵢ·ic[i+1]`,
`e(−A, B)·e(α, β)·e(vk_x, γ)·e(C, δ) = 1`, exact, nothing sampled or
skipped — as one call into the native library
(`csrc/zkp2p_native.cpp::groth16_verify_bn254`).  What stays here is
marshalling ints to `u64` limbs.

`snark.groth16.verify` stays the oracle and the voice: a native `True` is
the answer; on a native `False` the Python function runs on that proof
and ITS answer (or exception) is the caller's.  The library answers
`False` wherever it does not decide — a coordinate outside `[0, p)`, a
wrong number of public inputs, a key point off its curve — so nothing
is refused, or accepted, on its word alone except a proof that satisfies
the equation.  Which path runs is observed, not set: the native library
is loaded -> native; it is not -> Python.

Not imported by `snark.groth16`: `snark/` stays importable without the
native library.
"""

from __future__ import annotations

import ctypes
from typing import List, Sequence, Tuple

import numpy as np

from ..field.bn254 import R
from ..native.lib import _scalars_to_u64 as _limbs  # (n, 4) u64; OverflowError outside [0, 2^256)
from ..native.lib import get_lib
from .groth16 import Proof, VerifyingKey
from .groth16 import verify as verify_python

_u64p = ctypes.POINTER(ctypes.c_uint64)


def _native():
    """The native library, or None."""
    return get_lib()


def path_for() -> str:
    """Which check a sample gets: "native" where the library is loaded,
    else "python"."""
    return "native" if _native() is not None else "python"


class _NoLimbs(ValueError):
    """A point the library's layout has no word for."""


def _g1(pt) -> List[int]:
    if pt is None:
        return [0, 0]
    if not (pt[0] or pt[1]):
        raise _NoLimbs("(0, 0) is the library's point at infinity")
    return [pt[0], pt[1]]


def _g2(pt) -> List[int]:
    if pt is None:
        return [0, 0, 0, 0]
    x, y = pt
    if x.is_zero() and y.is_zero():
        raise _NoLimbs("(0, 0) is the library's point at infinity")
    return [x.c0, x.c1, y.c0, y.c1]


def _p(a: np.ndarray):
    return a.ctypes.data_as(_u64p)


def pairing_product_is_one(lib, pairs: Sequence[Tuple]) -> bool:
    """`pairing.pairing_product_is_one` in the library: `prod e(P_i, Q_i)
    == 1`, the points taken as given (canonical coordinates, no curve or
    subgroup check)."""
    g1s = _limbs([v for p, _ in pairs for v in _g1(p)])
    g2s = _limbs([v for _, q in pairs for v in _g2(q)])
    return lib.bn254_pairing_product_is_one(_p(g1s), _p(g2s), len(pairs)) == 1


def verify_native(lib, vk: VerifyingKey, proof: Proof, public_inputs: Sequence[int]) -> bool:
    """The library's answer alone: True only for a proof that satisfies
    the whole of `snark.groth16.verify`."""
    try:
        key = _limbs(
            _g1(vk.alpha_1) + _g2(vk.beta_2) + _g2(vk.gamma_2) + _g2(vk.delta_2)
            + [v for pt in vk.ic for v in _g1(pt)]
        )
        prf = _limbs(_g1(proof.a) + _g2(proof.b) + _g1(proof.c))
        pub = _limbs([int(x) % R for x in public_inputs])
    except (_NoLimbs, OverflowError):
        return False
    n_pub = len(public_inputs)
    return lib.groth16_verify_bn254(_p(key), vk.n_public, len(vk.ic), _p(prf), _p(pub), n_pub) == 1


def verify(vk: VerifyingKey, proof: Proof, public_inputs: Sequence[int], path: str) -> Tuple[bool, bool]:
    """`snark.groth16.verify(vk, proof, public_inputs)` by the path
    `path_for` chose: (the answer, whether the oracle overruled a native
    `False`).  The second is never true of a correct library."""
    if path == "native" and verify_native(_native(), vk, proof, public_inputs):
        return True, False
    ok = verify_python(vk, proof, public_inputs)
    return ok, ok and path == "native"
