"""BN254 (alt_bn128) base/scalar field parameters and host-side arithmetic.

This is the host-side (Python int) mirror of the TPU limb arithmetic in
``zkp2p_tpu.field.jfield``.  It plays the role the reference delegates to
rapidsnark's x86 assembly field library and to circom's ``bigint.circom``
gadgets (reference: ``zk-email-verify-circuits/bigint.circom``,
``zk-email-verify-circuits/fp.circom:26-85``) — here it is the oracle that
every vectorised TPU kernel is tested against, and the engine for host-only
steps (trusted setup, pairing-based verification, zkey parsing).
"""

from __future__ import annotations

# Base field modulus (Fq) and scalar field modulus (Fr) of BN254.
# These are the constants baked into contracts/Verifier.sol in the reference
# (snarkjs-exported Groth16 verifier) — our proofs must live on exactly this
# curve to stay wire-compatible.
P = 21888242871839275222246405745257275088696311157297823662689037894645226208583
R = 21888242871839275222246405745257275088548364400416034343698204186575808495617

# Curve: y^2 = x^3 + 3 over Fq;  G2 twist: y^2 = x^3 + 3/(u+9) over Fq2.
CURVE_B = 3

# Generators.
G1_GEN = (1, 2)
G2_GEN = (
    (
        10857046999023057135944570762232829481370756359578518086990519993285655852781,
        11559732032986387107991004021392285783925812861821192530917403151452391805634,
    ),
    (
        8495653923123431417604973247489272438418190587263600148770280649306958101930,
        4082367875863433681332203403145435568316851327593401208105741076214120093531,
    ),
)

# BN parameter u: p(u), r(u) are the standard BN polynomials.
BN_U = 4965661367192848881
ATE_LOOP_COUNT = 6 * BN_U + 2  # 29793968203157093288

# Limb layout shared with the TPU side: 16 limbs x 16 bits = 256 bits.
LIMB_BITS = 16
NUM_LIMBS = 16
MONT_BITS = LIMB_BITS * NUM_LIMBS  # 256
MONT_R = 1 << MONT_BITS

# snarkjs / circom "bigint" layout used at the wire level by the reference app
# (app/src/helpers/binaryFormat.ts:70-78 packs RSA moduli as 121-bit x 17
# limbs).  We keep those constants for input-format parity.
CIRCOM_BIGINT_N = 121
CIRCOM_BIGINT_K = 17


def fq_add(a: int, b: int) -> int:
    return (a + b) % P


def fq_sub(a: int, b: int) -> int:
    return (a - b) % P


def fq_mul(a: int, b: int) -> int:
    return (a * b) % P


def fq_inv(a: int) -> int:
    if a % P == 0:
        raise ZeroDivisionError("inverse of zero in Fq")
    return pow(a, P - 2, P)


def fr_add(a: int, b: int) -> int:
    return (a + b) % R


def fr_sub(a: int, b: int) -> int:
    return (a - b) % R


def fr_mul(a: int, b: int) -> int:
    return (a * b) % R


def fr_inv(a: int) -> int:
    if a % R == 0:
        raise ZeroDivisionError("inverse of zero in Fr")
    return pow(a, R - 2, R)


def _mont_constants(modulus: int):
    """Montgomery constants for the 16x16-bit limb layout."""
    r_mod = MONT_R % modulus
    r2 = (r_mod * r_mod) % modulus
    # n' = -modulus^{-1} mod 2^256  (also per-limb: mod 2^16)
    n_inv = pow(modulus, -1, MONT_R)
    n_prime = (-n_inv) % MONT_R
    return r_mod, r2, n_prime


FQ_MONT_R, FQ_MONT_R2, FQ_NPRIME = _mont_constants(P)
FR_MONT_R, FR_MONT_R2, FR_NPRIME = _mont_constants(R)


def to_mont(a: int, modulus: int = P) -> int:
    return (a * MONT_R) % modulus


def from_mont(a: int, modulus: int = P) -> int:
    return (a * pow(MONT_R, -1, modulus)) % modulus


def find_fr_2adic_root() -> int:
    """A primitive 2^28-th root of unity in Fr.

    r - 1 has 2-adicity 28; this bounds our NTT domain at 2^28 points, well
    above the 2^23 domain the 6.6M-constraint reference circuit needs
    (reference README.md:79).  Verified at import-time by order checks rather
    than trusting a hardcoded factorisation.
    """
    two_adicity = 28
    assert (R - 1) % (1 << two_adicity) == 0
    assert (R - 1) % (1 << (two_adicity + 1)) != 0
    odd = (R - 1) >> two_adicity
    for g in range(2, 100):
        w = pow(g, odd, R)
        # order of w divides 2^28; it is exactly 2^28 iff w^(2^27) != 1
        if pow(w, 1 << (two_adicity - 1), R) != 1:
            return w
    raise RuntimeError("no 2^28 root of unity found")


FR_TWO_ADICITY = 28
FR_ROOT_OF_UNITY = find_fr_2adic_root()


def fr_domain_root(log_size: int) -> int:
    """Primitive 2^log_size-th root of unity in Fr."""
    if log_size > FR_TWO_ADICITY:
        raise ValueError(f"domain 2^{log_size} exceeds Fr 2-adicity {FR_TWO_ADICITY}")
    w = FR_ROOT_OF_UNITY
    for _ in range(FR_TWO_ADICITY - log_size):
        w = (w * w) % R
    return w


# ---------------------------------------------------------------------------
# GLV endomorphism (the MSM work-reduction lever every accelerator MSM
# study leads with — SZKP §IV, ZKProphet §3): BN254 has j-invariant 0,
# so phi(x, y) = (beta * x, y) with beta a primitive cube root of unity
# in Fq is an endomorphism acting as scalar multiplication by lambda, a
# cube root of unity in Fr.  Every 254-bit scalar k then splits into two
# ~128-bit half-scalars k = k1 + k2 * lambda (mod r), and
#
#     k * P  =  k1 * P  +  k2 * phi(P),
#
# turning a length-n MSM over 254-bit scalars into a length-2n MSM over
# half-length scalars: half the digit planes / Pippenger windows.
#
# Nothing below is hardcoded from a paper table: the roots of unity, the
# lattice basis, and the Barrett constants are all DERIVED here at
# import (and cross-checked — lambda*G must literally land on
# (beta, 2)), so a transcription error is an import failure, not a
# silently wrong proof.


def _jac_mul_int(pt, k: int):
    """Tiny host scalar mult (Jacobian, python ints) used only for the
    import-time phi/lambda pairing check — curve.host imports this
    module, so the check cannot use it."""
    X1, Y1 = pt
    X, Y, Z = 0, 1, 0  # infinity
    for bit in bin(k)[2:]:
        if Z:  # double
            A, B = X * X % P, Y * Y % P
            C = B * B % P
            D = 2 * ((X + B) * (X + B) - A - C) % P
            E = 3 * A % P
            X2 = (E * E - 2 * D) % P
            Y, Z = (E * (D - X2) - 8 * C) % P, 2 * Y * Z % P
            X = X2
        if bit == "1":
            if not Z:
                X, Y, Z = X1, Y1, 1
            else:  # mixed add (Z2 = 1); the loop never hits the equal/neg cases
                ZZ = Z * Z % P
                U2, S2 = X1 * ZZ % P, Y1 * ZZ * Z % P
                H, Rr = (U2 - X) % P, (S2 - Y) % P
                HH = H * H % P
                HHH, V = H * HH % P, X * HH % P
                X2 = (Rr * Rr - HHH - 2 * V) % P
                Y, Z = (Rr * (V - X2) - Y * HHH) % P, Z * H % P
                X = X2
    if not Z:
        return None
    zi = pow(Z, P - 2, P)
    return (X * zi * zi % P, Y * zi * zi % P * zi % P)


def _cube_root_of_unity(modulus: int) -> int:
    assert (modulus - 1) % 3 == 0
    for g in range(2, 100):
        w = pow(g, (modulus - 1) // 3, modulus)
        if w != 1:
            assert pow(w, 3, modulus) == 1
            return w
    raise RuntimeError("no cube root of unity found")


def _glv_lattice(lam: int):
    """Short basis (a1, b1), (a2, b2) of {(x, y): x + y*lam = 0 mod r}
    via the half-extended Euclid of the GLV paper (Algorithm 3.74 in
    Guide to ECC): successive remainders r_i = s_i*r + t_i*lam give
    lattice vectors (r_i, -t_i); stop around sqrt(r)."""
    sqrt_r = 1 << ((R.bit_length() + 1) // 2)
    rems = [(R, 0), (lam, 1)]  # (r_i, t_i)
    while rems[-1][0] >= sqrt_r:
        (r0, t0), (r1, t1) = rems[-2], rems[-1]
        q = r0 // r1
        rems.append((r0 - q * r1, t0 - q * t1))
    (rl, tl), (rl1, tl1) = rems[-2], rems[-1]
    v1 = (rl1, -tl1)
    # second vector: the shorter of (r_l, -t_l) and (r_{l+2}, -t_{l+2})
    # (one more Euclid step past the sqrt(r) crossing)
    q = rl // rl1
    cand_a = (rl, -tl)
    cand_b = (rl - q * rl1, -(tl - q * tl1))

    def _n2(v):
        return v[0] * v[0] + v[1] * v[1]

    v2 = cand_a if _n2(cand_a) <= _n2(cand_b) else cand_b
    # normalise orientation so det(v1, v2) = +r (the decomposition
    # formulas below assume it)
    det = v1[0] * v2[1] - v2[0] * v1[1]
    assert abs(det) == R, "GLV lattice determinant must be +-r"
    if det < 0:
        v2 = (-v2[0], -v2[1])
    for a, b in (v1, v2):
        assert (a + b * lam) % R == 0
        assert a != 0 and b != 0
    return v1, v2


def _glv_setup():
    lam = _cube_root_of_unity(R)
    # phi(G) = (beta, 2) for G = (1, 2): one scalar mult pins which of
    # the two cube roots in Fq pairs with this lambda.
    q = _jac_mul_int(G1_GEN, lam)
    b = _cube_root_of_unity(P)
    assert q is not None and q[1] == 2 and q[0] in (b, b * b % P), (
        "lambda*G is not (beta, 2): GLV endomorphism derivation broken"
    )
    beta = q[0]
    v1, v2 = _glv_lattice(lam)
    return lam, beta, v1, v2


GLV_LAMBDA, GLV_BETA, GLV_V1, GLV_V2 = _glv_setup()
(_GLV_A1, _GLV_B1), (_GLV_A2, _GLV_B2) = GLV_V1, GLV_V2

# Barrett constants: exact c_i = round(m_i*k/r) with m1 = b2, m2 = -b1;
# the limb kernels (JAX ops.msm, csrc) use the floor form
# c_abs = (k * MU) >> GLV_SHIFT, whose error vs the exact rounding is
# < 2 — harmless: k1 + lambda*k2 = k (mod r) holds for ANY c_i by
# construction, only the |k_i| bound grows (folded into GLV_MAX_BITS).
GLV_SHIFT = 256
_GLV_M1, _GLV_M2 = _GLV_B2, -_GLV_B1
GLV_MU1 = (abs(_GLV_M1) << GLV_SHIFT) // R
GLV_MU2 = (abs(_GLV_M2) << GLV_SHIFT) // R


def _sign(x: int) -> int:
    return 1 if x > 0 else -1


# Term form consumed by the limb kernels: k1 = k -+ |c1||a1| -+ |c2||a2|
# and k2 = -+ |c1||b1| -+ |c2||b2|, where each subtract flag folds the
# sign of c_i (= sign of m_i) and of the basis entry.
GLV_K1_TERMS = (
    (abs(_GLV_A1), _sign(_GLV_M1) * _sign(_GLV_A1) > 0),
    (abs(_GLV_A2), _sign(_GLV_M2) * _sign(_GLV_A2) > 0),
)
GLV_K2_TERMS = (
    (abs(_GLV_B1), _sign(_GLV_M1) * _sign(_GLV_B1) > 0),
    (abs(_GLV_B2), _sign(_GLV_M2) * _sign(_GLV_B2) > 0),
)

# Worst-case half-scalar magnitudes (Barrett floor error < 2 per c_i):
# |k_i| < 2 * (|basis column|_1).  ~2^128.6 for BN254.
GLV_MAX_K1 = 2 * (abs(_GLV_A1) + abs(_GLV_A2))
GLV_MAX_K2 = 2 * (abs(_GLV_B1) + abs(_GLV_B2))
GLV_MAX_BITS = max(GLV_MAX_K1.bit_length(), GLV_MAX_K2.bit_length())


def glv_decompose(k: int):
    """k (mod r) -> (k1, k2) signed ints with k = k1 + k2*lambda (mod r)
    and |k_i| < 2^GLV_MAX_BITS.  This is the HOST ORACLE: it implements
    the exact floor-Barrett limb algorithm of the C kernel (csrc
    glv_split) so the two can be diffed integer-for-integer."""
    k %= R
    c1 = (k * GLV_MU1) >> GLV_SHIFT
    c2 = (k * GLV_MU2) >> GLV_SHIFT
    k1 = k
    for c, (mag, sub) in zip((c1, c2), GLV_K1_TERMS):
        k1 = k1 - c * mag if sub else k1 + c * mag
    k2 = 0
    for c, (mag, sub) in zip((c1, c2), GLV_K2_TERMS):
        k2 = k2 - c * mag if sub else k2 + c * mag
    return k1, k2


def glv_num_planes(window: int) -> int:
    """Signed base-2^window digit planes needed for one GLV half-scalar:
    k planes hold |v| < 2^(window*k - 1) after signed recoding (the top
    digit must absorb the final carry), so k = ceil((GLV_MAX_BITS+1)/w)."""
    return -(-(GLV_MAX_BITS + 1) // window)


# Import-time self-check: a decomposition identity failure must be an
# import error, never a wrong proof.  (Covers the edge scalars the
# property tests also pin.)
for _k in (0, 1, 2, R - 1, GLV_LAMBDA, R - GLV_LAMBDA, (1 << 128) - 1, R >> 1):
    _k1, _k2 = glv_decompose(_k)
    assert (_k1 + _k2 * GLV_LAMBDA - _k) % R == 0
    assert abs(_k1) < (1 << GLV_MAX_BITS) and abs(_k2) < (1 << GLV_MAX_BITS)
del _k, _k1, _k2
