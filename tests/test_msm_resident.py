"""The h MSM from a table resident with the key (ops.msm.resident_table /
msm_resident; prover.groth16_tpu._h_table): the table against the host
curve, the MSM against `msm_windowed_signed` and the host oracle, the
window rule, and a toy batch through `prove_tpu_batch` byte-equal to
`prove_host`.

CPU, tiny shapes: one compiled program a (function, window), shared by
every case — an MSM program is ~20 s of XLA:CPU compile.  The kernels'
own differentials are tests/test_pallas_curve.py (interpret mode); the
curve ops here are the XLA formulas the chip's kernels mirror."""

import random
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from zkp2p_tpu.curve.host import G1_GENERATOR, g1_add, g1_msm, g1_mul, g1_neg
from zkp2p_tpu.curve.jcurve import G1J, g1_jac_to_host, g1_to_affine_arrays
from zkp2p_tpu.field.bn254 import R
from zkp2p_tpu.field.jfield import FQ, FR
from zkp2p_tpu.ops import msm as jmsm

N, LANES = 10, 4  # three steps of four lanes, the last padded with two holes


@lru_cache(maxsize=None)
def _table_fn(window):
    return jax.jit(lambda b: jmsm.resident_table(G1J, b, window, LANES))


_resident = jax.jit(lambda t, m, s: jmsm.msm_resident(G1J, t, m, s))  # the window is the table's: a program a shape


@lru_cache(maxsize=None)
def _signed_fn(window):
    return jax.jit(lambda b, m, s: jmsm.msm_windowed_signed(G1J, b, m, s, lanes=LANES, window=window))


def _limbs(scalars):
    return jnp.asarray(np.stack([FR.to_std_host(s) for s in scalars]))


def _points(rng, n=N):
    return [g1_mul(G1_GENERATOR, rng.randrange(1, R)) for _ in range(n)]


def _entry(table, i, k):
    """k * base i as the table holds it: a host point, None for (0, 0)."""
    words = table[i // LANES, k - 1, i % LANES]
    x, y = FQ.from_mont_host(words & 0xFFFF), FQ.from_mont_host(words >> 16)
    return None if x == 0 and y == 0 else (x, y)


@pytest.mark.parametrize("window", [4, 8])
def test_table_holds_every_multiple_affine_with_holes_kept(window):
    rng = random.Random(window)
    pts = _points(rng)
    pts[1] = pts[6] = None
    table = np.asarray(_table_fn(window)(g1_to_affine_arrays(pts)))
    n_table = 1 << (window - 1)
    assert table.shape == (3, n_table, LANES, 16) and table.dtype == np.uint32
    assert table.nbytes == 3 * LANES * n_table * jmsm.RESIDENT_ENTRY_BYTES
    for i, pt in enumerate(pts):
        want = None
        for k in range(1, n_table + 1):
            want = g1_add(want, pt) if pt is not None else None
            assert _entry(table, i, k) == want, (i, k)
    for i in range(N, 3 * LANES):  # the padding is holes too
        assert all(_entry(table, i, k) is None for k in (1, n_table))


@lru_cache(maxsize=None)
def _multiples_of_four_bases():
    """(host bases with a hole at 2, `_affine_multiples` of them for k = 1..8)."""
    pts = _points(random.Random(29), 4)
    pts[2] = None
    fn = jax.jit(lambda b: jmsm._affine_multiples(G1J, b, 8))
    return pts, tuple(np.asarray(c) for c in fn(g1_to_affine_arrays(pts)))


@pytest.mark.parametrize("k", range(1, 9))
def test_affine_multiples_are_k_times_each_base_and_a_hole_stays_a_hole(k):
    """k = 2 is the scan's first step, P + P; the inversion is one a base
    over all eight Z's, so a hole's Z = 0 must not reach it."""
    pts, (x, y) = _multiples_of_four_bases()
    assert x.shape == y.shape == (8, 4, 16)
    got = [(FQ.from_mont_host(x[k - 1, i]), FQ.from_mont_host(y[k - 1, i])) for i in range(4)]
    assert got == [(0, 0) if pt is None else g1_mul(pt, k) for pt in pts]


def _case(name, rng):
    """(points, scalars) for one named case, all of one shape."""
    pts, scalars = _points(rng), [rng.randrange(R) for _ in range(N)]
    if name == "zero_digits":
        scalars = [0] * N
    elif name == "negative_digits":
        # every base-16 and base-256 digit above the half: the recode negates all but the top one
        scalars = [int("09" + "".join(rng.choice(["a9", "9a", "bc", "de", "99"]) for _ in range(31)), 16) for _ in range(N)]
    elif name == "holes":
        pts[0] = pts[3] = pts[9] = None
        scalars[4] = 0
    elif name == "duplicate_base":
        # one lane, consecutive steps, equal digits: the accumulator EQUALS the entry it meets
        pts[LANES + 1], scalars[LANES + 1] = pts[1], scalars[1]
        pts[2 * LANES + 1], scalars[2 * LANES + 1] = pts[1], scalars[1]
    elif name == "opposite_base":
        # the same lane meets the negated entry: P + (-P), then a live point again
        pts[LANES + 2], scalars[LANES + 2] = pts[2], R - scalars[2]
    elif name == "equal_and_opposite_lanes":
        # lanes 0 and 1 hold the same bases and scalars at every step, lanes 2 and 3 opposite ones:
        # the fold's full add meets X + X and Y + (-Y), and carries the (0 : y : 0) it gets on
        for i in (0, LANES, 2 * LANES):
            pts[i + 1], scalars[i + 1] = pts[i], scalars[i]
        for i in (2, LANES + 2):
            pts[i + 1], scalars[i + 1] = g1_neg(pts[i]), scalars[i]
    else:
        assert name == "random"
    return pts, scalars


CASES = ["random", "zero_digits", "negative_digits", "holes", "duplicate_base", "opposite_base", "equal_and_opposite_lanes"]


@pytest.mark.parametrize("name", CASES)
@pytest.mark.parametrize("window", [4, 8])
def test_resident_msm_equals_signed_windowed_and_host(window, name):
    pts, scalars = _case(name, random.Random(100 * window + CASES.index(name)))
    bases = g1_to_affine_arrays(pts)
    mags, negs = jmsm.signed_digit_planes_from_limbs(_limbs(scalars), window)
    if name == "negative_digits":
        assert np.asarray(negs)[1:].all() and np.asarray(mags).all()
    if name == "zero_digits":
        assert not np.asarray(mags).any()
    got = g1_jac_to_host(_resident(_table_fn(window)(bases), mags, negs))[0]
    assert got == g1_msm(pts, scalars)
    assert got == g1_jac_to_host(_signed_fn(window)(bases, mags, negs))[0]


def test_resident_msm_vmapped_over_a_batch_with_the_table_unbatched():
    window, rng = 8, random.Random(44)
    pts = _points(rng)
    pts[7] = None
    batch = [[rng.randrange(R) for _ in range(N)] for _ in range(4)]
    batch[2] = [0] * N
    planes = [jmsm.signed_digit_planes_from_limbs(_limbs(s), window) for s in batch]
    mags, negs = (jnp.stack([p[i] for p in planes]) for i in (0, 1))
    table = _table_fn(window)(g1_to_affine_arrays(pts))
    fn = jax.jit(jax.vmap(lambda t, m, s: jmsm.msm_resident(G1J, t, m, s), in_axes=(None, 0, 0)))
    assert g1_jac_to_host(fn(table, mags, negs)) == [g1_msm(pts, s) for s in batch]


# ------------------------------------------------------------ the window rule

GIB = 1 << 30


@pytest.mark.parametrize("log_m,limit,want", [
    (16, 16 * GIB, 8),  # sha2b: 0.54 GB of table
    (19, int(15.75 * GIB), 8),  # venmo 256/192 on a v5e: 4.29 GB
    (19, 8 * GIB, 4),  # a limit that admits only w=4
    (20, 16 * GIB, 4),
    (23, 16 * GIB, None),  # venmo-full: neither, the in-scan table
    (4, 16 * GIB, 8),
])
def test_window_rule_is_a_function_of_size_bytes_a_base_and_limit(log_m, limit, want):
    from zkp2p_tpu.prover.groth16_tpu import h_table_window

    assert h_table_window(log_m, jmsm.RESIDENT_ENTRY_BYTES, limit) == want
    if want is not None:  # and it is monotone in the bytes a base
        assert (h_table_window(log_m, 64 * jmsm.RESIDENT_ENTRY_BYTES, limit) or 0) <= want


# what a 16 GiB chip is given at each domain: today's three cells (2^16 and 2^19: a chunk
# of four, w=8) and the published EmailVerify (2^22: one proof at a time, the w=4 table)
@pytest.mark.parametrize("log_m,chunk,window", [
    (16, 4, 8), (17, 4, 8), (18, 4, 8), (19, 4, 8), (20, 4, 4), (21, 2, 4), (22, 1, 4),
])
@pytest.mark.parametrize("limit", [16 * GIB, int(15.75 * GIB)])
def test_chunk_and_window_are_functions_of_the_keys_size_and_the_devices_memory(log_m, chunk, window, limit):
    from zkp2p_tpu.prover import groth16_tpu as G

    assert G.batch_chunk_for(log_m, limit) == chunk
    assert G.h_table_window(log_m, jmsm.RESIDENT_ENTRY_BYTES, limit, chunk) == window
    # what the rule plans fits what it was given, and the next chunk up would not (or is the cap)
    assert chunk == 1 or G.work_bytes_a_point(chunk) << log_m <= G.HBM_PLAN_FRACTION * limit
    assert chunk == G.BATCH_CHUNK_MAX or G.work_bytes_a_point(2 * chunk) << log_m > G.HBM_PLAN_FRACTION * limit
    # a chunk of four plans what PR 25's window rule planned: 7 KiB a point
    assert G.work_bytes_a_point(4) == 7 << 10
    assert G.h_table_window(log_m, jmsm.RESIDENT_ENTRY_BYTES, limit) == (8 if log_m <= 19 else 4 if log_m == 20 else None)


@pytest.mark.parametrize("log_m,knob,on_tpu,want", [
    (22, "auto", True, 1), (19, "auto", True, 4), (None, "auto", True, 4), (22, "auto", False, 0),
    (22, "4", True, 4), (19, "2", False, 2), (22, "four", True, 1),
])
def test_the_knob_overrides_the_rule_and_the_arm_records_the_size_chosen(monkeypatch, log_m, knob, on_tpu, want):
    from zkp2p_tpu.prover import groth16_tpu as G
    from zkp2p_tpu.utils import audit

    monkeypatch.setattr(G, "_on_tpu", lambda: on_tpu)
    monkeypatch.setattr(G, "BATCH_CHUNK", knob)
    assert G._batch_chunk_size(log_m) == want
    assert audit.gate_arms()["batch_chunk"] == str(want)


@pytest.mark.parametrize("log_m,want", [(16, 8), (20, 4), (22, 4)])
def test_the_process_takes_the_rule_and_nothing_else(monkeypatch, log_m, want):
    """`_h_table_window` is `h_table_window` at the device's memory and
    the chunk planned for the key: only the key's size and the device's
    memory can refuse a table (XLA:CPU reports no memory_stats, so the
    rule runs on the nominal chip, at the chunk a TPU would take)."""
    from zkp2p_tpu.prover import groth16_tpu as G

    monkeypatch.setattr(G, "BATCH_CHUNK", "auto")
    assert G._hbm_bytes_limit() == G.NOMINAL_HBM_BYTES
    assert G._h_table_window(log_m) == want
    chunk = G.batch_chunk_for(log_m, G.NOMINAL_HBM_BYTES)
    assert want == G.h_table_window(log_m, jmsm.RESIDENT_ENTRY_BYTES, G.NOMINAL_HBM_BYTES, chunk)


# ------------------------------------------- a toy batch through prove_tpu_batch


def _planes_to_scalars(planes, window):
    mags, negs = (np.asarray(p) for p in planes)  # (B, n_digits, n)
    n_digits = mags.shape[1]
    out = []
    for b in range(mags.shape[0]):
        col = []
        for i in range(mags.shape[2]):
            v = 0
            for j in range(n_digits):
                v = (v << window) + (-1 if negs[b, j, i] else 1) * int(mags[b, j, i])
            col.append(v % R)
        out.append(col)
    return out


def _host_g1(bases):
    x, y = (np.asarray(c) for c in bases)
    pts = []
    for i in range(x.shape[0]):
        px, py = FQ.from_mont_host(x[i]), FQ.from_mont_host(y[i])
        pts.append(None if px == 0 and py == 0 else (px, py))
    return pts


def _proj_g1(points):
    x, y = g1_to_affine_arrays(points)
    z = np.stack([np.zeros(16, np.uint32) if p is None else np.asarray(FQ.one_mont) for p in points])
    return x, y, jnp.asarray(z)


def _toy_world(monkeypatch):
    """The toy circuit for the real `prove_tpu_batch`: the h stage, the
    table's build and the resident h MSM are the real programs; the four
    witness MSMs — untouched by the table, minutes of XLA:CPU compile
    each — are answered by the host curve from the very bases and planes
    the prover hands them.  Returns (cs, pk, dpk, four witnesses)."""
    from zkp2p_tpu.curve.host import g2_msm
    from zkp2p_tpu.curve.jcurve import g2_to_affine_arrays
    from zkp2p_tpu.field.tower import Fq2 as G2_FQ2
    from zkp2p_tpu.prover import device_pk
    from zkp2p_tpu.prover import groth16_tpu as G
    from zkp2p_tpu.snark.groth16 import setup
    from zkp2p_tpu.snark.r1cs import LC, ConstraintSystem

    cs = ConstraintSystem("toy")
    out, x, y, z = cs.new_public("out"), cs.new_wire("x"), cs.new_wire("y"), cs.new_wire("z")
    cs.enforce(LC.of(x), LC.of(y), LC.of(z), "mul")
    cs.enforce(LC.of(z), LC.of(z), LC.of(out), "sq")
    cs.compute(z, lambda a, b: a * b % R, [x, y])
    wits = [cs.witness([pow(a * b % R, 2, R)], {x: a, y: b}) for a, b in [(3, 5), (2, 7), (10, 11), (1, 1)]]
    pk, _vk = setup(cs)
    dpk = device_pk(pk, cs)
    monkeypatch.setenv("ZKP2P_TPU_SHARD", "off")
    monkeypatch.setattr(G, "BATCH_CHUNK", "0")

    def host_g1(window):
        def run(bases, planes):
            pts = _host_g1(bases)
            return _proj_g1([g1_msm(pts, s) for s in _planes_to_scalars(planes, window)])
        return run

    def host_g2(window):
        def run(bases, planes):
            bx, by = (np.asarray(c) for c in bases)
            pts = []
            for i in range(bx.shape[0]):
                c = [FQ.from_mont_host(v) for v in (bx[i, 0], bx[i, 1], by[i, 0], by[i, 1])]
                pts.append(None if not any(c) else (G2_FQ2(c[0], c[1]), G2_FQ2(c[2], c[3])))
            sums = [g2_msm(pts, s) for s in _planes_to_scalars(planes, window)]
            gx, gy = g2_to_affine_arrays(sums)
            one = np.stack([np.asarray(FQ.one_mont), np.zeros(16, np.uint32)])
            z = np.stack([np.zeros((2, 16), np.uint32) if p is None else one for p in sums])
            return gx, gy, jnp.asarray(z)
        return run

    monkeypatch.setattr(G, "_jit_msm_g1", host_g1(G.MSM_WINDOW))
    monkeypatch.setattr(G, "_jit_msm_g1_narrow", host_g1(4))
    monkeypatch.setattr(G, "_jit_msm_g2", host_g2(G.MSM_WINDOW))
    monkeypatch.setattr(G, "_jit_msm_g2_narrow", host_g2(4))
    return cs, pk, dpk, wits


def _no_narrow_class(dpk):
    """The key as an imported zkey without width inference has it: every
    position of a query in its wide class."""
    import dataclasses

    none = jnp.zeros((0,), jnp.int32)
    return dataclasses.replace(
        dpk, a_nsel=none, b_nsel=none, c_nsel=none, a_wsel=jnp.arange(dpk.n_wires, dtype=jnp.int32),
        b_wsel=jnp.arange(dpk.b_sel.shape[0], dtype=jnp.int32), c_wsel=jnp.arange(dpk.c_sel.shape[0], dtype=jnp.int32))


@pytest.mark.parametrize("classed", [True, False])
def test_recode_has_one_shape(monkeypatch, classed):
    """`_recode` returns `((mags, negs), narrow), (h_mags, h_negs)` for a
    key with a narrow class and for one without (`narrow` is `()`): the
    signed planes rebuild the witness and the h scalars mod r, h at the
    resident table's window, and the narrow planes are the low
    NARROW_PLANES of a w=4 recode."""
    from zkp2p_tpu.prover import groth16_tpu as G

    _cs, _pk, dpk, wits = _toy_world(monkeypatch)
    assert int(dpk.a_nsel.shape[0]) > 0  # the toy key classes its constant-one wire narrow
    if not classed:
        dpk = _no_narrow_class(dpk)
    m, rng = 1 << dpk.log_m, random.Random(28)
    h_scalars = [rng.randrange(R) for _ in range(m - 2)] + [0, R - 1]
    h = jnp.asarray(np.stack([FR.to_mont_host(v) for v in h_scalars]))
    h_window = G._h_table_window(dpk.log_m, G.key_device(dpk))
    (w_planes, narrow), h_planes = jax.jit(G._recode, static_argnums=3)(dpk, G.witness_to_device(wits[0]), h, h_window)
    assert _planes_to_scalars(tuple(p[None] for p in w_planes), G.MSM_WINDOW) == [[v % R for v in wits[0]]]
    assert h_window == 8 and h_planes[0].shape == h_planes[1].shape == (256 // h_window, m)
    assert _planes_to_scalars(tuple(p[None] for p in h_planes), h_window) == [h_scalars]
    if not classed:
        assert narrow == ()
        return
    mags4, negs4 = jmsm.signed_digit_planes_from_limbs(FR.from_mont(G.witness_to_device(wits[0])), 4)
    assert len(narrow) == 2 and narrow[0].shape == narrow[1].shape == (G.NARROW_PLANES, dpk.n_wires)
    np.testing.assert_array_equal(narrow[0], mags4[-G.NARROW_PLANES:])
    np.testing.assert_array_equal(narrow[1], negs4[-G.NARROW_PLANES:])


def test_prove_tpu_batch_through_the_resident_table_is_byte_equal_to_prove_host(monkeypatch):
    """Two batches of four as one chunk: the table is built once."""
    from zkp2p_tpu.prover import groth16_tpu as G
    from zkp2p_tpu.snark.groth16 import prove_host
    from zkp2p_tpu.utils import trace as tr
    from zkp2p_tpu.utils.metrics import REGISTRY

    cs, pk, dpk, wits = _toy_world(monkeypatch)
    tr.reset()
    rs, ss = [11, 12, 13, 14], [21, 22, 23, 24]
    first = G.prove_tpu_batch(dpk, wits, rs=rs, ss=ss)
    again = G.prove_tpu_batch(dpk, wits, rs=rs, ss=ss)
    for i, proof in enumerate(first):
        assert proof == prove_host(pk, cs, wits[i], r=rs[i], s=ss[i]), f"proof {i} != oracle"
    assert again == first

    table = dpk._h_table_cache
    m = 1 << dpk.log_m
    assert table.shape == (1, 128, m, 16)  # w=8 wherever it fits, and a toy fits
    assert REGISTRY.gauge("zkp2p_msm_h_table_bytes").value == table.nbytes == m * 128 * 64
    recs = tr.records()
    (built,) = [r for r in recs if r["stage"].endswith("/h_table")]
    assert built["stage"] == "tpu/prove_batch/h_table" and built["window"] == 8 and built["bytes"] == table.nbytes
    h_stages = [r for r in recs if r["stage"].endswith("/stage/msm_h")]
    assert len(h_stages) == 2 and all(r["window"] == 8 and r["table"] == "resident" for r in h_stages)
    tr.reset()


@pytest.mark.parametrize("n,n_chunks", [(3, 2), (1, 1)])
def test_a_batch_above_and_below_the_chunk_gives_the_proofs_of_one_at_a_time(monkeypatch, n, n_chunks):
    """A chunk of two: three witnesses run as two chunks through one
    executable (the tail padded with its last witness), one witness as a
    batch of its own shape; either way each proof is the oracle's for its
    own (witness, r, s), and the batch span says what was chosen."""
    from zkp2p_tpu.prover import groth16_tpu as G
    from zkp2p_tpu.snark.groth16 import prove_host
    from zkp2p_tpu.utils import trace as tr

    cs, pk, dpk, wits = _toy_world(monkeypatch)
    monkeypatch.setattr(G, "BATCH_CHUNK", "2")
    tr.reset()
    rs, ss = [31, 32, 33][:n], [41, 42, 43][:n]
    proofs = G.prove_tpu_batch(dpk, wits[:n], rs=rs, ss=ss)
    assert proofs == [prove_host(pk, cs, wits[i], r=rs[i], s=ss[i]) for i in range(n)]
    (batch,) = [r for r in tr.records() if r["stage"] == "tpu/prove_batch"]
    assert (batch["n"], batch["chunk"], batch["n_chunks"], batch["log_m"]) == (n, 2, n_chunks, dpk.log_m)
    assert [r["chunk"] for r in tr.records() if r["stage"].endswith("/upload")] == list(range(n_chunks))  # a put a chunk
    h_stages = [r for r in tr.records() if r["stage"].endswith("/stage/msm_h")]
    assert [r["chunk"] for r in h_stages] == list(range(n_chunks))
    tr.reset()
