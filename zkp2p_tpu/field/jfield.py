"""Vectorised BN254 field arithmetic on TPU lanes (JAX).

This module is the TPU mirror of rapidsnark's x86-assembly field library and
of the circom bigint gadgets the reference leans on
(``zk-email-verify-circuits/bigint.circom``, ``fp.circom:26-85``).  TPUs have
no native 64x64 multiply, so field elements are **16 limbs x 16 bits in
uint32 lanes**: a 16x16-bit product fits a uint32 exactly, and its lo/hi
16-bit halves are accumulated in separate uint32 planes (each partial < 2^16,
so thousands can be summed before carry propagation).  All ops are shape-
polymorphic over leading batch dims and therefore `vmap`/`shard_map`-friendly;
multiplication is Montgomery (SOS: full schoolbook product, then one
Montgomery reduction), so a field mul is three 16-limb convolutions — pure
elementwise uint32 mul/add/shift that XLA vectorises onto the VPU.

Layout contract (shared with the host oracle ``zkp2p_tpu.field.bn254``):
  value = sum(limb[i] << (16*i)),  limb[i] < 2^16,  canonical (< modulus).
"""

from __future__ import annotations

from functools import lru_cache


import jax
import jax.numpy as jnp
import numpy as np

from .bn254 import MONT_R, P, R

LIMB_BITS = 16
NUM_LIMBS = 16
MASK = (1 << LIMB_BITS) - 1


def int_to_limbs(x: int, n: int = NUM_LIMBS) -> np.ndarray:
    """Host int -> uint32 limb vector (little-endian 16-bit limbs)."""
    return np.array([(x >> (LIMB_BITS * i)) & MASK for i in range(n)], dtype=np.uint32)


def limbs_to_int(a) -> int:
    a = np.asarray(a, dtype=np.uint64)
    return sum(int(v) << (LIMB_BITS * i) for i, v in enumerate(a))


def _shift_up(x: jnp.ndarray, k: int) -> jnp.ndarray:
    """x[i] -> x[i-k] along the limb (last) axis, zero-filled below."""
    pad = [(0, 0)] * (x.ndim - 1) + [(k, 0)]
    return jnp.pad(x, pad)[..., : x.shape[-1]]


def _carry_ladder(x: jnp.ndarray, out_limbs: int, up) -> jnp.ndarray:
    """The relax + Kogge-Stone carry core, shared by both conv layouts
    (`up` is the limb-axis shift for whichever axis holds limbs).

    Two local folds bring every limb to <= 2^16, then a generate/propagate
    doubling ladder resolves the remaining 0/1 carries in
    ceil(log2(out_limbs)) vector steps — O(log limbs) graph and runtime
    dependency chain."""
    for _ in range(2):
        x = (x & MASK) + up(x >> LIMB_BITS, 1)
    g = x >> LIMB_BITS  # 0/1 generate
    r = x & MASK
    p = (r == MASK).astype(jnp.uint32)  # propagate
    k = 1
    while k < out_limbs:
        g = g | (p & up(g, k))
        p = p & up(p, k)
        k *= 2
    return (r + up(g, 1)) & MASK


def _carry_canon(x: jnp.ndarray, out_limbs: int) -> jnp.ndarray:
    """Propagate carries: arbitrary uint32 limbs -> canonical 16-bit limbs
    (limbs on the LAST axis).  Callers guarantee limbs beyond `out_limbs`
    are zero (no value is silently truncated)."""
    L = x.shape[-1]
    if L < out_limbs:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, out_limbs - L)])
    else:
        x = x[..., :out_limbs]
    return _carry_ladder(x, out_limbs, _shift_up)


@lru_cache(maxsize=None)
def _conv_onehot(n: int, m: int) -> np.ndarray:
    """(2*n*m, n+m+1) 0/1 f32 matrix folding lo/hi partial-product planes
    onto their limb offsets: flat index (p, i, j) -> column i + j + p.
    A host array: a constant of whichever device's program uses it."""
    L = n + m + 1
    w = np.zeros((2, n, m, L), dtype=np.float32)
    for i in range(n):
        for j in range(m):
            w[0, i, j, i + j] = 1.0
            w[1, i, j, i + j + 1] = 1.0
    return w.reshape(2 * n * m, L)


# Convolution layout selector.  "matmul": the f32 one-hot matmul below
# (MXU path).  "limb_major": transpose so the BATCH is the minor axis and
# run 16 shifted VPU multiply-accumulates — XLA:TPU tiles the last two
# dims onto (8 sublanes, 128 lanes), so batch-major (B, 16) tensors use
# only 16/128 lanes on every elementwise op while limb-major (16, B)
# fills them.  Flip at runtime (e.g. ZKP2P_FIELD_CONV=limb_major) to A/B
# on hardware; both are bit-exact and differentially tested.
from ..utils.config import load_config as _load_config
from ..utils.jaxcfg import on_tpu as _on_tpu

CONV_LAYOUT = _load_config().field_conv

# Field-mul implementation selector: "auto"/"pallas" (the fused pallas
# kernel on a TPU, the XLA path elsewhere) or "xla" (force the XLA
# path).  The kernel is compiled for the chip or not used: off a TPU
# "pallas" does not arm (preflight warns), and interpret mode is only
# ever passed explicitly by the differential tests.  Builders' isolated
# kernel timings are in docs/ROOFLINE.md.
FIELD_MUL_IMPL = _load_config().field_mul


def field_mul_impl() -> str:
    """The RESOLVED field-mul implementation ("pallas" or "xla") — the
    one place the "auto" rule lives (mirror of JCurve._pallas; used by
    JPrimeField.mul and by tools that label A/B arms).  Reports its arm
    to the execution audit at every consultation (trace-time: the arm is
    baked into the compiled executable, so the record marks the trace
    that chose it)."""
    from ..utils.audit import record_arm

    impl = "pallas" if FIELD_MUL_IMPL in ("pallas", "auto") and _on_tpu() else "xla"
    record_arm("field_mul", impl)
    return impl


def _mul_wide_limb_major(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Schoolbook conv with limbs on axis 0 and the flattened batch on
    the minor axis: 16 iterations of (Lb, B) u32 multiply + two padded
    adds into a (La+Lb+1, B) accumulator, then a log-depth carry ladder
    along axis 0.  Sums per output limb <= 2*16 values < 2^16 -> u32
    accumulation exact."""
    La, Lb = a.shape[-1], b.shape[-1]
    bshape = jnp.broadcast_shapes(a.shape[:-1], b.shape[:-1])
    A = jnp.moveaxis(jnp.broadcast_to(a, bshape + (La,)), -1, 0).reshape(La, -1)
    Bv = jnp.moveaxis(jnp.broadcast_to(b, bshape + (Lb,)), -1, 0).reshape(Lb, -1)
    out_len = La + Lb + 1
    acc = jnp.zeros((out_len, A.shape[1]), dtype=jnp.uint32)
    for i in range(La):
        p = A[i][None, :] * Bv  # (Lb, B), exact u32
        acc = acc + jnp.pad(p & MASK, ((i, out_len - Lb - i), (0, 0)))
        acc = acc + jnp.pad(p >> LIMB_BITS, ((i + 1, out_len - Lb - i - 1), (0, 0)))
    out_limbs = La + Lb
    acc = acc[:out_limbs]

    def up(x, k):  # limb-axis shift, limbs on axis 0
        return jnp.pad(x, ((k, 0), (0, 0)))[: x.shape[0]]

    res = _carry_ladder(acc, out_limbs, up)
    return jnp.moveaxis(res.reshape((out_limbs,) + bshape), 0, -1)


def _mul_wide(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Full product of two 16-limb values -> 32 canonical limbs.

    Default path: schoolbook convolution as ONE f32 matmul — every
    partial product a_i*b_j < 2^32 is split into 16-bit halves (each
    exact in f32), and a precomputed 0/1 matrix folds the (2,16,16)
    planes onto their limb offsets.  Each output limb sums <= 32 values
    < 2^16, so the f32 accumulation stays integral (< 2^21 << 2^24) —
    bit-exact, and the contraction runs on the TPU MXU.
    See CONV_LAYOUT for the limb-major VPU alternative.
    """
    if CONV_LAYOUT == "limb_major":
        return _mul_wide_limb_major(a, b)
    n = a.shape[-1]
    m = b.shape[-1]
    prods = a[..., :, None] * b[..., None, :]  # (..., n, m) uint32
    lo = (prods & MASK).astype(jnp.float32)
    hi = (prods >> LIMB_BITS).astype(jnp.float32)
    planes = jnp.concatenate(
        [lo.reshape(*lo.shape[:-2], n * m), hi.reshape(*hi.shape[:-2], n * m)], axis=-1
    )
    # Precision.HIGHEST: TPU DEFAULT f32 matmul truncates operands to
    # bf16 MXU passes (8 mantissa bits — NOT exact for 16-bit limbs);
    # HIGHEST runs the full-f32 pass schedule, keeping every partial and
    # sum integral and bit-exact.
    acc = jax.lax.dot_general(
        planes,
        _conv_onehot(n, m),
        (((planes.ndim - 1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
    )  # (..., n+m+1), integral f32 < 2^21
    return _carry_canon(acc.astype(jnp.uint32), n + m)


class JPrimeField:
    """A prime field instance with device-resident Montgomery constants.

    Two global instances exist: ``FQ`` (base field, curve coordinates) and
    ``FR`` (scalar field, witnesses / NTT).  Elements are uint32 arrays of
    shape (..., 16) in Montgomery form unless a function says otherwise.
    """

    def __init__(self, modulus: int, name: str):
        from .bn254 import _mont_constants

        self.modulus = modulus
        self.name = name
        self.mont_r, self.mont_r2, self.nprime_int = _mont_constants(modulus)
        # HOST arrays: FQ / FR are built at import, and a device array
        # here would initialise the JAX backend — take the chip — in any
        # process that merely imports the prover (the fleet supervisor
        # did, through hostprof -> prover.precomp).  jnp ops take them
        # as operands all the same.
        self.n_limbs = int_to_limbs(modulus)
        self.nprime_limbs = int_to_limbs(self.nprime_int)
        self.r2_limbs = int_to_limbs(self.mont_r2)
        self.one_mont = int_to_limbs(self.mont_r)
        self.zero_limbs = np.zeros(NUM_LIMBS, dtype=np.uint32)

    # ------------------------------------------------------------ host I/O

    def to_mont_host(self, x: int) -> np.ndarray:
        return int_to_limbs((x * MONT_R) % self.modulus)

    def from_mont_host(self, limbs) -> int:
        return (limbs_to_int(limbs) * pow(MONT_R, -1, self.modulus)) % self.modulus

    def to_std_host(self, x: int) -> np.ndarray:
        return int_to_limbs(x % self.modulus)

    def array_to_mont_host(self, xs) -> np.ndarray:
        return np.stack([self.to_mont_host(int(x)) for x in xs])

    def array_to_mont_host_fast(self, xs) -> np.ndarray:
        """Vectorized (n, 16) Montgomery limbs: one bytes join + one
        frombuffer instead of a per-element 16-limb Python loop — the
        difference between seconds and minutes at venmo-scale wire counts."""
        m = self.modulus
        buf = b"".join((int(x) * MONT_R % m).to_bytes(32, "little") for x in xs)
        return np.frombuffer(buf, "<u2").astype(np.uint32).reshape(len(xs), NUM_LIMBS)

    # --------------------------------------------------------- basic arith

    def _cond_sub_n(self, a: jnp.ndarray) -> jnp.ndarray:
        """a (< 2*modulus, canonical limbs) -> a mod modulus."""
        d, borrow = self._sub_raw(a, self.n_limbs)
        return jnp.where(borrow[..., None] != 0, a, d)

    @staticmethod
    def _sub_raw(a: jnp.ndarray, b: jnp.ndarray):
        """(a - b) mod 2^256 with final borrow flag (1 if a < b).

        Two's-complement addition a + ~b + 1 through the log-depth carry
        ladder; the carry out of the top limb is the no-borrow flag."""
        n = a.shape[-1]
        x = a + (MASK - jnp.broadcast_to(b, a.shape))
        one = jnp.zeros(n, dtype=jnp.uint32).at[0].set(1)
        y = _carry_canon(x + one, n + 1)
        borrow = (1 - y[..., n]).astype(jnp.int32)
        return y[..., :n], borrow

    def add(self, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
        return self._cond_sub_n(_carry_canon(a + b, NUM_LIMBS))

    def sub(self, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
        d, borrow = self._sub_raw(a, b)
        dn = _carry_canon(d + self.n_limbs, NUM_LIMBS)
        return jnp.where(borrow[..., None] != 0, dn, d)

    def neg(self, a: jnp.ndarray) -> jnp.ndarray:
        d, _ = self._sub_raw(jnp.broadcast_to(self.n_limbs, a.shape), a)
        # -0 must stay 0, not N
        is_zero = self.is_zero(a)
        return jnp.where(is_zero[..., None], a, self._cond_sub_n(d))

    def mul(self, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
        """Montgomery product: (a*b*R^-1) mod N, R = 2^256 (SOS method).

        ZKP2P_FIELD_MUL routes the implementation: the fused VMEM
        kernel (ops.pallas_mont, docs/ROOFLINE.md) on a TPU unless
        "xla" is forced, the XLA path elsewhere."""
        if field_mul_impl() == "pallas":
            from ..ops.pallas_mont import mont_mul

            return mont_mul(self, a, b)
        t = _mul_wide(a, b)  # (..., 32)
        m = _mul_wide(t[..., :NUM_LIMBS], self.nprime_limbs)[..., :NUM_LIMBS]
        u = _mul_wide(m, self.n_limbs)  # (..., 32)
        # t + u is divisible by 2^256; sum then shift right 16 limbs.
        s = _carry_canon(t.astype(jnp.uint32) + u, 2 * NUM_LIMBS + 1)
        return self._cond_sub_n(s[..., NUM_LIMBS : 2 * NUM_LIMBS + 1][..., :NUM_LIMBS])

    def square(self, a: jnp.ndarray) -> jnp.ndarray:
        return self.mul(a, a)

    def to_mont(self, a: jnp.ndarray) -> jnp.ndarray:
        """Standard-form limbs -> Montgomery form (on device)."""
        return self.mul(a, self.r2_limbs)

    def from_mont(self, a: jnp.ndarray) -> jnp.ndarray:
        """Montgomery form -> standard-form limbs (mont-mul by 1)."""
        one = jnp.zeros_like(a).at[..., 0].set(1)
        return self.mul(a, one)

    # ----------------------------------------------------------- predicates

    @staticmethod
    def eq(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
        return jnp.all(a == b, axis=-1)

    @staticmethod
    def is_zero(a: jnp.ndarray) -> jnp.ndarray:
        return jnp.all(a == 0, axis=-1)

    @staticmethod
    def select(cond: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
        """cond ? a : b, with cond shaped (...,) against (..., 16) operands."""
        return jnp.where(cond[..., None], a, b)

    # ------------------------------------------------------------ inversion

    def pow_const(self, a: jnp.ndarray, e: int) -> jnp.ndarray:
        """a^e for a compile-time exponent.

        lax.scan over the exponent's bits (LSB first) keeps the traced graph
        at one square+select per step regardless of exponent size — the
        unrolled ladder was a 60k-op HLO graph for a 254-bit exponent.
        """
        if e == 0:
            return jnp.broadcast_to(self.one_mont, a.shape)
        bits = jnp.asarray([(e >> i) & 1 for i in range(e.bit_length())], dtype=jnp.uint32)

        def step(carry, bit):
            acc, base = carry
            acc = self.select(bit != 0, self.mul(acc, base), acc)
            base = self.square(base)
            return (acc, base), None

        acc0 = jnp.broadcast_to(self.one_mont, a.shape)
        (acc, _), _ = jax.lax.scan(step, (acc0, a), bits)
        return acc

    def inv(self, a: jnp.ndarray) -> jnp.ndarray:
        """Fermat inverse a^(N-2); 0 maps to 0 (callers select around it)."""
        return self.pow_const(a, self.modulus - 2)

    def inv_fused(self, a: jnp.ndarray) -> jnp.ndarray:
        """`inv`, but one kernel launch on TPU: pow_const's scan issues 2
        mul dispatches per exponent bit (~508 launches per call), which
        makes small-batch inversions latency-bound; the fused ladder
        (ops.pallas_mont.mont_pow) runs the whole ladder in VMEM."""
        if field_mul_impl() == "pallas":
            from ..ops.pallas_mont import mont_pow

            return mont_pow(self, a, self.modulus - 2)
        return self.inv(a)


FQ = JPrimeField(P, "fq")
FR = JPrimeField(R, "fr")


# --------------------------------------------------------------------- Fq2
#
# Fq2 = Fq[u]/(u^2 + 1): elements are pairs of Fq limb arrays, stacked on a
# new axis -2: shape (..., 2, 16).  Mirrors zkp2p_tpu.field.tower.Fq2 (host).


class JFq2Ops:
    """Fq2 arithmetic over stacked limb pairs (..., 2, 16)."""

    def __init__(self, fq: JPrimeField = FQ):
        self.fq = fq
        self.one_mont = np.stack([fq.one_mont, fq.zero_limbs])
        self.zero_limbs = np.zeros((2, NUM_LIMBS), dtype=np.uint32)

    def add(self, a, b):
        return self.fq.add(a, b)

    def sub(self, a, b):
        return self.fq.sub(a, b)

    def neg(self, a):
        return self.fq.neg(a)

    def mul(self, a, b):
        a0, a1 = a[..., 0, :], a[..., 1, :]
        b0, b1 = b[..., 0, :], b[..., 1, :]
        v0 = self.fq.mul(a0, b0)
        v1 = self.fq.mul(a1, b1)
        c0 = self.fq.sub(v0, v1)  # u^2 = -1
        c1 = self.fq.sub(
            self.fq.mul(self.fq.add(a0, a1), self.fq.add(b0, b1)),
            self.fq.add(v0, v1),
        )
        return jnp.stack([c0, c1], axis=-2)

    def square(self, a):
        return self.mul(a, a)

    def eq(self, a, b):
        return jnp.all(a == b, axis=(-1, -2))

    def is_zero(self, a):
        return jnp.all(a == 0, axis=(-1, -2))

    @staticmethod
    def select(cond, a, b):
        return jnp.where(cond[..., None, None], a, b)


FQ2 = JFq2Ops(FQ)


# ------------------------------------------------------- batched reductions


def reduce_wide(field: JPrimeField, wide: jnp.ndarray) -> jnp.ndarray:
    """Reduce a canonical-limb value of up to 31 limbs to x mod N.

    Montgomery round-trip: one Montgomery reduction computes x*2^-256 mod N
    (exact because x < 2^496 << 2^256 * N), then a mont-mul by the
    precomputed 2^512 mod N restores the 2^256 factor.  Three convolutions,
    no data-dependent control flow.
    """
    L = wide.shape[-1]
    assert L <= 31, "reduce_wide supports < 2^496 inputs"
    x = jnp.zeros(wide.shape[:-1] + (2 * NUM_LIMBS,), dtype=jnp.uint32)
    x = x.at[..., :L].set(wide)
    m = _mul_wide(x[..., :NUM_LIMBS], field.nprime_limbs)[..., :NUM_LIMBS]
    u = _mul_wide(m, field.n_limbs)  # 32 limbs
    s = _carry_canon(x + u, 2 * NUM_LIMBS + 1)
    t = field._cond_sub_n(s[..., NUM_LIMBS : 2 * NUM_LIMBS])
    # r2_limbs == 2^512 mod N, exactly the factor that undoes the 2^-256.
    return field.mul(t, field.r2_limbs)


SEGMENT_REDUCE_ROWS = 1 << 19


def lazy_segment_sum_mod(
    field: JPrimeField, values: jnp.ndarray, segment_ids: jnp.ndarray, num_segments: int
) -> jnp.ndarray:
    """Modular segment-sum: sum canonical limb values per segment, then reduce.

    Limbs are < 2^16, so uint32 per-limb accumulation is exact for up to ~2^16
    terms per segment — far above the row fan-in of any of our constraint
    systems.  This is the sparse-matvec primitive behind Az/Bz/Cz.
    """
    acc = jax.ops.segment_sum(values, segment_ids, num_segments=num_segments)

    def reduce(sums):
        return reduce_wide(field, _carry_canon(sums, NUM_LIMBS + 2))

    # `reduce_wide` multiplies wide: a (rows, 16, 16) tensor of partial
    # products, 1,440 B a segment by the v5e compiler's count — 6.0 GB of
    # temporaries at the 2^22 segments of EmailVerify(1024, 1536), twice
    # that under vmap, more than the chip has (PERF.md, PR 26).  Above
    # SEGMENT_REDUCE_ROWS the sums are reduced that many rows at a time;
    # at or under it (2^19: venmo 256/192) the program is the one it was.
    if num_segments <= SEGMENT_REDUCE_ROWS or num_segments % SEGMENT_REDUCE_ROWS:
        return reduce(acc)
    blocks = acc.reshape(num_segments // SEGMENT_REDUCE_ROWS, SEGMENT_REDUCE_ROWS, acc.shape[-1])
    return jax.lax.map(reduce, blocks).reshape(acc.shape)
