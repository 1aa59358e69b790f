"""A proof's assembly without the interpreter.

After the five MSMs a Groth16 proof is ~10 group operations on the host:
the accumulators blinded with `(r, s)` and summed with the key's own
points into `(A, B, C)`.  On `curve/host.py`'s Python integers that is
70-90 ms a proof, five sixths of it one G2 double-and-add over `Fq2`
objects, on the proving thread after every batch with nothing on the
device.  This module runs the SAME expression — four G1 and one G2
scalar multiplications at full width, nine additions, every one complete
— as one call into the native library
(`csrc/zkp2p_native.cpp::groth16_assemble_bn254`).  What stays here is
marshalling ints to `u64` limbs and back.

`assemble_python` stays the oracle: group arithmetic is exact, so the
native form gives its bytes or is wrong, and the library answers nothing
(`assemble_native` -> None, the caller runs the oracle) wherever it does
not decide: a coordinate outside `[0, p)`, a scalar outside
`[0, 2^256)`, a point off its curve.  `prove_native` assembles with the
oracle, so every comparison of a device proof with it holds one form to
the other.

Not imported by `snark.groth16`: `snark/` stays importable without the
native library.
"""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

from ..curve.host import g1_add, g1_mul, g1_neg, g2_add, g2_mul
from ..field.bn254 import R
from ..field.tower import Fq2
from ..native.lib import get_lib
from .groth16 import Proof
from .native_verify import _g1, _g2, _limbs, _NoLimbs, _p


def path_for() -> str:
    """Which form a proof gets: "native" where the library is loaded, else
    "python".  Observed, not set."""
    return "native" if get_lib() is not None else "python"


def assemble_python(key, acc, r: int, s: int) -> Proof:
    """`key`: anything with `alpha_1`, `beta_1`, `delta_1`, `beta_2`,
    `delta_2` (a proving key, a device proving key); `acc`: the a, b1,
    b2, c and h accumulators, affine host points, None = infinity."""
    a_acc, b1_acc, b2_acc, c_acc, h_acc = acc
    pi_a = g1_add(g1_add(key.alpha_1, a_acc), g1_mul(key.delta_1, r))
    pi_b = g2_add(g2_add(key.beta_2, b2_acc), g2_mul(key.delta_2, s))
    pi_b1 = g1_add(g1_add(key.beta_1, b1_acc), g1_mul(key.delta_1, s))
    pi_c = g1_add(c_acc, h_acc)
    pi_c = g1_add(pi_c, g1_mul(pi_a, s))
    pi_c = g1_add(pi_c, g1_mul(pi_b1, r))
    pi_c = g1_add(pi_c, g1_neg(g1_mul(key.delta_1, r * s % R)))
    return Proof(a=pi_a, b=pi_b, c=pi_c)


def assemble_native(lib, key, acc, r: int, s: int) -> Optional[Proof]:
    """The library's answer alone, or None where it gives none."""
    a_acc, b1_acc, b2_acc, c_acc, h_acc = acc
    try:
        ins = _limbs(
            _g1(key.alpha_1) + _g1(key.beta_1) + _g1(key.delta_1) + _g2(key.beta_2) + _g2(key.delta_2)
            + _g1(a_acc) + _g1(b1_acc) + _g2(b2_acc) + _g1(c_acc) + _g1(h_acc)
            + [r, s]
        )
    except (_NoLimbs, OverflowError):
        return None
    out = np.zeros((8, 4), dtype=np.uint64)
    if lib.groth16_assemble_bn254(_p(ins), _p(ins[14:]), _p(ins[26:]), _p(out)) != 1:
        return None
    raw = out.tobytes()
    ax, ay, bx0, bx1, by0, by1, cx, cy = (int.from_bytes(raw[i : i + 32], "little") for i in range(0, 256, 32))
    return Proof(
        a=(ax, ay) if ax or ay else None,
        b=(Fq2(bx0, bx1), Fq2(by0, by1)) if bx0 or bx1 or by0 or by1 else None,
        c=(cx, cy) if cx or cy else None,
    )


def assemble(key, acc, r: int, s: int) -> Tuple[Proof, str]:
    """The proof, and the form that made it: "native", or "python" where
    the library is not loaded or gave no answer."""
    lib = get_lib()
    proof = None if lib is None else assemble_native(lib, key, acc, r, s)
    if proof is None:
        return assemble_python(key, acc, r, s), "python"
    return proof, "native"
