"""The device prover takes a witness in the form it arrives in
(prover.groth16_tpu._witness_rows): the standard-form (n, 4) u64 rows its
builder attached, or the bare array of them, straight to limbs; anything
else a wire at a time through `int(w) % R`, the oracle.  Which one runs is
observed from the input.

`prove_tpu_batch` here is the real one down to the upload.  The device's
programs (minutes of XLA:CPU compile each) are answered by an independent
prover, `prove_host` at the toy key and `prove_native` at sha2b, FROM THE
LIMBS THAT WERE UPLOADED: a proof is affine in its five accumulators, so
two proofs of the oracle (r, s = 0, 0 and 1, 0) give them, and the real
`_assemble` blinds them.  A proof then equals the oracle's own for the
same (witness, r, s) exactly when the limbs uploaded are that witness's."""

import dataclasses
import os
import random

import jax.numpy as jnp
import numpy as np
import pytest

from zkp2p_tpu.curve.host import g1_add, g1_neg, g2_add, g2_neg
from zkp2p_tpu.curve.jcurve import G2J, g1_to_affine_arrays, g2_to_affine_arrays
from zkp2p_tpu.field.bn254 import R
from zkp2p_tpu.field.jfield import FQ, FR
from zkp2p_tpu.prover import groth16_tpu as G
from zkp2p_tpu.prover.native_prove import _limbs16_to_u64
from zkp2p_tpu.snark import witness_check as wc
from zkp2p_tpu.snark.groth16 import prove_host, setup, verify
from zkp2p_tpu.snark.r1cs import LC, ConstraintSystem, Witness
from zkp2p_tpu.utils import trace as tr
from zkp2p_tpu.utils.metrics import REGISTRY

FORMS = ("witness", "list", "witness_row", "array")
WANT = {"witness": "rows", "list": "ints", "witness_row": "rows", "array": "rows"}


# ------------------------------------------------------------------ worlds


@dataclasses.dataclass
class World:
    cs: ConstraintSystem
    dpk: object
    vk: object
    inputs: list  # (public, private) a witness
    oracle: object  # ((n, 4) u64 rows, r, s) -> Proof, by a prover that shares nothing with prove_tpu_batch's host side

    def witnesses(self, form: str, n: int = 4) -> list:
        if form == "witness_row":
            return list(self.cs.witness_batch(self.inputs[:n]))
        ws = [self.cs.witness(*inp) for inp in self.inputs[:n]]
        return {"witness": ws, "list": [list(w) for w in ws], "array": [w.u64 for w in ws]}[form]


def _oracle(prover, *key):
    """`prover(*key, ints, r, s)` over the values `rows` hold: the oracle
    reads plain ints, whatever the device prover was handed."""
    proved = {}  # the oracle is a function of (values, r, s), and the tests come back to the same ones

    def prove(rows, r, s):
        at = (rows.tobytes(), r, s)
        if at not in proved:
            proved[at] = prover(*key, [int.from_bytes(row.tobytes(), "little") for row in rows], r=r, s=s)
        return proved[at]
    return prove


def _toy() -> World:
    cs = ConstraintSystem("toy")
    out, x, y, z = cs.new_public("out"), cs.new_wire("x"), cs.new_wire("y"), cs.new_wire("z")
    cs.enforce(LC.of(x), LC.of(y), LC.of(z), "mul")
    cs.enforce(LC.of(z), LC.of(z), LC.of(out), "sq")
    cs.compute(z, lambda a, b: a * b % R, [x, y])
    pk, vk = setup(cs)
    # the last one's wires are as wide as Fr: every limb of a row is used
    cases = [(3, 5), (2, 7), (10, 11), (R - 2, R - 3)]
    inputs = [([pow(a * b % R, 2, R)], {x: a, y: b}) for a, b in cases]
    return World(cs, G.device_pk(pk, cs), vk, inputs, _oracle(prove_host, pk, cs))


def _sha2b() -> World:
    from zkp2p_tpu.models.registry import build_sha2b
    from zkp2p_tpu.prover.native_prove import prove_native
    from zkp2p_tpu.prover.setup_device import setup_device

    cs, _ = build_sha2b()
    dpk, vk = setup_device(cs, seed="witness-forms")
    wires, rng = sorted(cs.input_wires), random.Random(34)
    inputs = [([], dict(zip(wires, (rng.randrange(256) for _ in wires)))) for _ in range(4)]
    return World(cs, dpk, vk, inputs, _oracle(prove_native, dpk))


@pytest.fixture(scope="module", params=["toy", "sha2b"])
def world(request) -> World:
    if request.param == "sha2b" and wc._native() is None:
        pytest.skip("the native library did not build")
    return {"toy": _toy, "sha2b": _sha2b}[request.param]()


@pytest.fixture(scope="module")
def toy() -> World:
    return _toy()


# ------------------------------------------------- the device, stood in for


def _proj_g1(points):
    x, y = g1_to_affine_arrays(points)
    z = np.stack([np.zeros(16, np.uint32) if p is None else np.asarray(FQ.one_mont) for p in points])
    return x, y, jnp.asarray(z)


def _proj_g2(points):
    x, y = g2_to_affine_arrays(points)
    one = np.stack([np.asarray(FQ.one_mont), np.zeros(16, np.uint32)])
    z = np.stack([np.zeros((2, 16), np.uint32) if p is None else one for p in points])
    return x, y, jnp.asarray(z)


class HostDevice:
    """Both roads' device programs, answered by `world.oracle` from the
    limbs the prover uploaded.  `uploads`: the standard-form rows of every
    chunk that reached the device, as (B, n, 4) u64."""

    def __init__(self, world: World, monkeypatch):
        self.world, self.uploads = world, []
        monkeypatch.setenv("ZKP2P_TPU_SHARD", "off")
        monkeypatch.setattr(G, "BATCH_CHUNK", "0")
        tr.reset()
        monkeypatch.setattr(G, "_prove_device", self._one_chip)
        # the one-chip road's first product is the device's too (to_mont of a sha2b chunk: 6-10 s of XLA:CPU)
        monkeypatch.setattr(G, "FR", type("StdForm", (), {"to_mont": staticmethod(lambda limbs: limbs)}))
        monkeypatch.setattr(G, "_h_table", lambda dpk: None)
        # the mesh road: placement, upload and exchange stay the real ones
        from zkp2p_tpu.parallel import mesh as pmesh

        monkeypatch.setattr(G, "_h_pod_fn", self._h_pod)
        monkeypatch.setattr(pmesh, "msm_pod_batched", self._msm_pod)

    def _accumulators(self, rows: np.ndarray):
        """The five (B,)-batched accumulators `_prove_device` returns."""
        self.uploads.append(rows)
        dpk, accs = self.world.dpk, []
        for w in rows:
            p00, p10 = self.world.oracle(w, 0, 0), self.world.oracle(w, 1, 0)
            accs.append((
                g1_add(p00.a, g1_neg(dpk.alpha_1)),
                g1_add(g1_add(p10.c, g1_neg(p00.c)), g1_neg(dpk.beta_1)),
                g2_add(p00.b, g2_neg(dpk.beta_2)),
                p00.c,  # c and h enter a proof as their sum
                None,
            ))
        a, b1, b2, c, h = zip(*accs)
        return _proj_g1(a), _proj_g1(b1), _proj_g2(b2), _proj_g1(c), _proj_g1(h)

    def _one_chip(self, dpk, w_std, watch=None):
        return self._accumulators(_limbs16_to_u64(w_std))

    def _h_pod(self, mesh, log_m):
        def run(rows, w_std):
            self._pod = iter(self._accumulators(_limbs16_to_u64(w_std)))
            b = w_std.shape[0]
            return np.zeros((b, 1 << log_m, 16), np.uint32), np.zeros((b,), np.uint32)
        return run

    def _msm_pod(self, curve, bases, planes, mesh, **kw):
        acc = next(self._pod)  # a, b1, b2, c, h: the order both roads return them in
        assert (curve is G2J) == (acc[0].ndim == 3)
        return acc


@pytest.fixture
def device(world, monkeypatch):
    yield HostDevice(world, monkeypatch)
    tr.reset()


@pytest.fixture
def toy_device(toy, monkeypatch):
    yield HostDevice(toy, monkeypatch)
    tr.reset()


def _form_counts() -> dict:
    return {f: REGISTRY.counter("zkp2p_prove_witness_form_total", {"form": f}).value for f in ("rows", "ints")}


def _prep_forms() -> list:
    return [r["witness_form"] for r in tr.records() if r["stage"] == "tpu/prove_batch/prep"]


def _pinned(n: int):
    return [101 + i for i in range(n)], [201 + i for i in range(n)]


def _prove(world: World, wits: list):
    """`prove_tpu_batch` under pinned scalars, beside what the oracle
    proves for the same values (read off the witnesses a wire at a time)."""
    rs, ss = _pinned(len(wits))
    got = G.prove_tpu_batch(world.dpk, wits, rs=rs, ss=ss)
    want = [world.oracle(_as_rows(w), r, s) for w, r, s in zip(wits, rs, ss)]
    return got, want


def _as_rows(w) -> np.ndarray:
    """What `w` says a wire at a time, as rows: the oracle's own input."""
    if isinstance(w, np.ndarray) and w.dtype == np.uint64:
        return w
    return np.frombuffer(b"".join((int(v) % R).to_bytes(32, "little") for v in w), dtype="<u8").reshape(len(w), 4)


# ------------------------------------------------------------------ parity


@pytest.mark.parametrize("form", FORMS)
def test_every_form_of_a_witness_proves_the_same_bytes(world, device, form):
    """`Witness` objects carrying rows, `list(w)` of the same (the oracle
    arm), `WitnessRow`s from `witness_batch`, the bare (n, 4) u64 arrays:
    one proof for one (witness, r, s), and the span and the counter say
    which path ran."""
    wits, before = world.witnesses(form, 2), _form_counts()
    got, want = _prove(world, wits)
    assert got == want
    if form == "array":  # there `want` was read off the rows themselves: hold it to the ints
        assert want == [world.oracle(_as_rows(w), r, s) for w, r, s in zip(world.witnesses("list", 2), *_pinned(2))]
    assert _prep_forms() == [WANT[form]]
    after = _form_counts()
    assert {f: after[f] - before[f] for f in after} == {"rows": 2 * (WANT[form] == "rows"), "ints": 2 * (WANT[form] == "ints")}
    if world.cs.name == "toy":
        assert all(verify(world.vk, p, inp[0]) for p, inp in zip(got, world.inputs))


def test_a_batch_that_mixes_forms(world, device):
    ws = [world.witnesses(form, 4)[i] for i, form in enumerate(FORMS)]
    before = _form_counts()
    got, want = _prove(world, ws)
    assert got == want and _prep_forms() == ["mixed"]
    after = _form_counts()
    assert (after["rows"] - before["rows"], after["ints"] - before["ints"]) == (3, 1)


@pytest.mark.parametrize("form", ["witness", "list"])
def test_a_short_batch_padded_with_its_last_witness_converts_it_once(world, device, form, monkeypatch):
    """The service pads a short batch to the warmed shape by repeating its
    last witness (`_prove_verified`): the same object, looked at once."""
    w0, w1 = world.witnesses(form, 2)
    converted, real = [], G._witness_std_limbs
    monkeypatch.setattr(G, "_witness_std_limbs", lambda w, out=None: (converted.append(id(w)), real(w, out))[1])
    before = _form_counts()
    got, want = _prove(world, [w0, w1, w1, w1])
    assert got == want
    assert converted == [id(w0), id(w1)]
    assert sum(_form_counts().values()) - sum(before.values()) == 2
    (up,) = device.uploads
    assert np.array_equal(up[1], up[2]) and np.array_equal(up[1], up[3]) and not np.array_equal(up[0], up[1])


@pytest.mark.parametrize("form", ["witness", "list"])
def test_a_batch_of_several_chunks(world, device, form, monkeypatch):
    """Three witnesses at a chunk of two (`ZKP2P_BATCH_CHUNK`): the second
    chunk, converted inside `dispatch`, is padded with its only witness."""
    monkeypatch.setattr(G, "BATCH_CHUNK", "2")
    wits = world.witnesses(form, 3)
    got, want = _prove(world, wits)
    assert got == want and _prep_forms() == [WANT[form]]
    assert [u.shape[0] for u in device.uploads] == [2, 2]
    assert np.array_equal(device.uploads[1][0], device.uploads[1][1])


@pytest.mark.parametrize("form", ["witness", "list", "witness_row"])
def test_the_mesh_road_beside_the_one_chip_road(toy, toy_device, form, monkeypatch):
    """1x4 on the CPU's virtual devices: the key placed, each witness's
    limbs uploaded to its chip, the exchange; the same bytes as one chip."""
    from zkp2p_tpu.utils.audit import gate_arms

    world = dataclasses.replace(toy, dpk=dataclasses.replace(toy.dpk))  # a key instance this test places
    wits = world.witnesses(form, 4)
    one_chip, want = _prove(world, wits)
    monkeypatch.setenv("ZKP2P_TPU_SHARD", "on")
    monkeypatch.setenv("ZKP2P_TPU_MESH", "1x4")
    on_mesh, _ = _prove(world, wits)
    assert gate_arms()["tpu_shard"] == "1x4" and on_mesh == one_chip == want
    assert _prep_forms() == [WANT[form]] * 2
    assert [r["mesh"] for r in tr.records() if r["stage"].endswith("/stage/exchange")] == ["1x4"]
    assert np.array_equal(*toy_device.uploads)


def test_prove_tpu_and_witness_to_device_read_the_rows(toy, toy_device):
    (w,), (ints,) = toy.witnesses("witness", 1), toy.witnesses("list", 1)
    assert G.prove_tpu(toy.dpk, w, r=7, s=9) == G.prove_tpu(toy.dpk, ints, r=7, s=9) == toy.oracle(_as_rows(ints), 7, 9)
    assert _prep_forms() == ["rows", "ints"]


def test_a_replica_sets_warm_up_batches_arrive_as_rows(toy, toy_device):
    """`ReplicaSet.warm` hands every replica one batch of the set's shape
    before it serves (wire 0 = 1, the rest 0): carrying its rows, like every
    batch it will serve, so a set's run writes no `ints` span."""
    from zkp2p_tpu.pipeline.replicas import ReplicaSet
    from zkp2p_tpu.pipeline.service import ProvingService

    rset = ReplicaSet(lambda key: ProvingService(toy.cs, key, toy.vk, toy.cs.witness, public_fn=lambda w: [w[1]], batch_size=2), toy.dpk, n=2)
    rset.warm()
    assert _prep_forms() == ["rows", "rows"]
    assert [up[:, :, 0].tolist() for up in toy_device.uploads] == [[[1, 0, 0, 0, 0]] * 2] * 2


def test_chunk_limbs_are_the_stack_of_the_oracle_arm(world):
    """What the upload takes, byte for byte: each witness's limbs in its
    place of one (chunk, n, 16) u32 array, equal to stacking the limbs of
    the `int(w) % R` arm."""
    from zkp2p_tpu.native.lib import _scalars_to_u64, _u64_to_limbs16

    ints = world.witnesses("list")
    want = np.stack([_u64_to_limbs16(_scalars_to_u64([v % R for v in w])) for w in ints])
    for form in FORMS:
        got = G._chunk_limbs(world.witnesses(form))
        assert got.dtype == np.uint32 and got.flags.c_contiguous and np.array_equal(got, want), form
    if world.cs.name == "toy":  # `prove_tpu`'s hand-off, through the real to_mont
        on_device = [np.asarray(G.witness_to_device(world.witnesses(form, 1)[0])) for form in FORMS]
        assert all(np.array_equal(d, np.stack([FR.to_mont_host(v % R) for v in ints[0]])) for d in on_device)


# ------------------------------------------------------------------ the guard


def _carrying(values, rows) -> Witness:
    w = Witness(values)
    w.u64 = rows
    return w


def test_a_witness_assigned_after_building_proves_the_new_value(toy, toy_device):
    """`w[i] = v` drops the rows: the ints are what is left, and what is
    proved."""
    (w,) = toy.witnesses("witness", 1)
    stale = w.u64.copy()
    w[2] = 12345
    assert w.u64 is None and G._witness_rows(w) is None
    got, want = _prove(toy, [w])
    assert got == want and _prep_forms() == ["ints"]
    assert int(toy_device.uploads[0][0, 2, 0]) == 12345 != int(stale[2, 0])


def _rows_like(kind: str, rows: np.ndarray):
    return {
        "shape": rows.reshape(-1, 2),  # (2n, 2)
        "transposed": np.ascontiguousarray(rows.T),
        "dtype": rows.astype(np.int64),
        "longer": np.concatenate([rows, rows[:1]]),
        "shorter": rows[:-1],
        "nested_list": rows.tolist(),
        "flat": rows[:, 0].copy(),
    }[kind]


@pytest.mark.parametrize("kind", ["shape", "transposed", "dtype", "longer", "shorter", "nested_list", "flat"])
def test_carried_rows_in_another_layout_take_the_ints_path(toy, toy_device, kind):
    (good,) = toy.witnesses("witness", 1)
    w = _carrying(list(good), _rows_like(kind, good.u64))
    assert G._witness_rows(w) is None and wc.witness_rows(toy.cs, w) is None  # one guard, both readers
    got, want = _prove(toy, [w])
    assert got == want and _prep_forms() == ["ints"]


def _row(v: int) -> np.ndarray:
    return np.frombuffer(v.to_bytes(32, "little"), dtype="<u8")


@pytest.mark.parametrize("carried", [True, False])
@pytest.mark.parametrize("value", [R, R + 1, (1 << 256) - 1])
def test_rows_not_reduced_raise_as_the_array_form_does(toy, toy_device, value, carried):
    """A row >= R (exactly R included) is not the value `int(w) % R`
    uploads: the rows path refuses it, carried or bare, and R - 1 passes."""
    (good,) = toy.witnesses("witness", 1)
    rows = good.u64.copy()
    rows[3] = _row(value)
    w = _carrying(list(good), rows) if carried else rows
    with pytest.raises(ValueError, match="witness row 3 is not reduced below the Fr modulus"):
        G.prove_tpu_batch(toy.dpk, [w])
    assert not toy_device.uploads
    rows[3] = _row(R - 1)
    G.prove_tpu_batch(toy.dpk, [w], rs=[1], ss=[2])
    assert int(toy_device.uploads[0][0, 3, 3]) == int(_row(R - 1)[3])


def test_rows_that_disagree_with_the_ints_are_what_is_checked_and_what_is_proved(toy, toy_device):
    """The self-check reads the rows and the prover proves the rows: one
    array.  Sound rows under garbage ints are admitted and their proof
    verifies; garbage rows under sound ints are rejected by the check,
    and a caller that skipped it gets the proof of the rows."""
    if wc._native() is None:
        pytest.skip("the native library did not build")
    (good,), (other,) = toy.witnesses("witness", 1), toy.witnesses("witness", 2)[1:]
    public = toy.inputs[0][0]
    w = _carrying([0] * len(good), good.u64)
    assert wc.path_for(toy.cs, [w]) == "native"
    wc.check_witness(toy.cs, w, "native")  # admitted: the rows satisfy every constraint
    (proof,) = G.prove_tpu_batch(toy.dpk, [w], rs=[5], ss=[6])
    assert proof == toy.oracle(good.u64, 5, 6) and verify(toy.vk, proof, public)

    bad = _carrying(list(good), other.u64.copy())
    bad.u64[1] = good.u64[1]  # another witness's wires under this one's public signal
    with pytest.raises((AssertionError, RuntimeError)):
        wc.check_witness(toy.cs, bad, "native")
    (proof,) = G.prove_tpu_batch(toy.dpk, [bad], rs=[5], ss=[6])
    assert proof == toy.oracle(bad.u64, 5, 6) != toy.oracle(good.u64, 5, 6)
    assert not verify(toy.vk, proof, public)
    assert _prep_forms() == ["rows", "rows"]


def test_the_rows_path_runs_no_statement_a_wire(world, device, monkeypatch):
    """Deterministic, not timed: with rows in hand the prover never
    serialises a scalar and never walks the witness."""
    import zkp2p_tpu.native.lib as nlib

    class Unwalkable(Witness):
        def __iter__(self):
            raise AssertionError("the rows path iterated the witness")

        def __getitem__(self, key):
            raise AssertionError("the rows path indexed the witness")

    def no_scalars(scalars):
        raise AssertionError("the rows path serialised scalars")

    wits = []
    for w in world.witnesses("witness", 2):
        wits.append(Unwalkable(w))
        wits[-1].u64 = w.u64
    want = [world.oracle(w.u64, r, s) for w, r, s in zip(wits, *_pinned(2))]
    monkeypatch.setattr(nlib, "_scalars_to_u64", no_scalars)
    rs, ss = _pinned(2)
    assert G.prove_tpu_batch(world.dpk, wits, rs=rs, ss=ss) == want
    assert np.array_equal(G.witness_to_device(wits[0]), nlib._u64_to_limbs16(wits[0].u64))  # `prove_tpu`'s hand-off
    with pytest.raises(AssertionError, match="serialised scalars"):
        G.prove_tpu_batch(world.dpk, [list(w.u64[:, 0]) for w in wits])


# ------------------------------------------- _check_inferred_widths on rows


@pytest.fixture(scope="module")
def trap(tmp_path_factory) -> World:
    """x·(x-1) = y: not a bit constraint, but the zkey has no C matrix and
    the importer classes x narrow (tests/test_zkey.py); the prove-time
    guard holds a witness to the inferred bound."""
    from zkp2p_tpu.formats.zkey import read_zkey, write_zkey
    from zkp2p_tpu.snark.groth16 import qap_rows

    cs = ConstraintSystem("trap")
    out, x, y = cs.new_public("out"), cs.new_wire("x"), cs.new_wire("y")
    cs.enforce(LC.of(x), LC.of(x) - 1, LC.of(y), "not-a-bit")
    cs.enforce(LC.of(y), LC.const(1), LC.of(out), "bind")
    cs.compute(y, lambda v: v * (v - 1) % R, [x])
    pk, vk = setup(cs, seed="width-trap")
    path = os.path.join(tmp_path_factory.mktemp("zkey"), "trap.zkey")
    write_zkey(path, pk, vk, qap_rows(cs))
    dpk = G.device_pk_from_zkey(read_zkey(path))
    assert np.frombuffer(dpk.inferred_narrow_wires, dtype=np.int64).tolist() == [0, x]  # the constant one, and x
    inputs = [([xv * (xv - 1) % R], {x: xv}) for xv in (1000, 5000)]  # 2^11 = 2048 between them
    return World(cs, dpk, vk, inputs, _oracle(prove_host, pk, cs))


@pytest.mark.parametrize("form", FORMS)
def test_an_inferred_width_bound_is_held_on_both_paths(trap, form, monkeypatch):
    device = HostDevice(trap, monkeypatch)
    within, beyond = trap.witnesses(form, 2)
    got, want = _prove(trap, [within])
    assert got == want and verify(trap.vk, got[0], trap.inputs[0][0]) and _prep_forms() == [WANT[form]]
    with pytest.raises(ValueError, match=r"^wire 2: witness value exceeds the width bound inferred"):
        G.prove_tpu_batch(trap.dpk, [within, beyond])
    assert len(device.uploads) == 1  # refused in `prep`, before anything reached the device
    tr.reset()
