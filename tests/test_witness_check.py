"""The witness self-check through the native products (snark.witness_check)
against its oracle, `ConstraintSystem.check_witness`: it accepts what the
loop accepts, rejects what the loop rejects IN THE LOOP'S WORDS, and the
service takes it where the library is loaded and the witness carries its
u64 rows, the loop everywhere else."""

import json
import os
import random
import sys
import threading

import numpy as np
import pytest

from zkp2p_tpu.field.bn254 import R
from zkp2p_tpu.gadgets import bigint
from zkp2p_tpu.pipeline.service import ProvingService
from zkp2p_tpu.prover import native_prove
from zkp2p_tpu.snark import witness_check as wc
from zkp2p_tpu.snark.r1cs import LC, ConstraintSystem, Witness
from zkp2p_tpu.utils import trace as program_trace
from zkp2p_tpu.utils.metrics import REGISTRY

pytestmark = pytest.mark.skipif(native_prove._lib() is None, reason="the native library did not build")


# ------------------------------------------------------------------ circuits


def _sha2b(seeds):
    from zkp2p_tpu.models.registry import build_sha2b

    cs, _ = build_sha2b()
    wires = sorted(cs.input_wires)
    rng = random.Random(31)
    return cs, [([], dict(zip(wires, (rng.randrange(256) for _ in wires)))) for _ in range(seeds)]


def _mulmod(seeds):
    """a·b mod p on 3 limbs of 100 bits: range checks tag limbs at 100
    bits, the carry chain goes wider — width tags over two u64 limbs."""
    n, k = 100, 3
    cs = ConstraintSystem("mulmod-wide")
    a, b, p = (bigint.alloc_limbs(cs, k, s) for s in "abp")
    for limbs, s in ((a, "a"), (b, "b"), (p, "p")):
        bigint.range_check_limbs(cs, limbs, n, s)
    bigint.big_mult_mod(cs, a, b, p, n)
    rng = random.Random(32)
    inputs = []
    for _ in range(seeds):
        pv = rng.randrange(1 << (n * k - 1), 1 << (n * k))
        seed = {}
        for limbs, v in ((a, rng.randrange(pv)), (b, rng.randrange(pv)), (p, pv)):
            seed.update(zip(limbs, bigint.int_to_limbs_host(v, n, k)))
        inputs.append(([], seed))
    return cs, inputs


@pytest.fixture(scope="module", params=["sha2b", "mulmod"])
def circuit(request):
    cs, inputs = {"sha2b": _sha2b, "mulmod": _mulmod}[request.param](3)
    if request.param == "mulmod":
        assert max(cs.wire_width.values()) > 64
    return cs, inputs


def _with_rows(w, rows):
    """`w` (a list of values) carrying `rows` as a builder would attach them."""
    out = Witness(w)
    out.u64 = np.ascontiguousarray(rows)
    return out


def _row(v: int) -> np.ndarray:
    return np.frombuffer(v.to_bytes(32, "little"), dtype="<u8")


def _loop_message(cs, w) -> str:
    with pytest.raises(AssertionError) as e:
        cs.check_witness(w)
    return str(e.value)


# ---------------------------------------------------------- accepts, rejects


@pytest.mark.parametrize("builder", ["witness", "witness_batch"])
def test_accepts_what_the_loop_accepts(circuit, builder):
    cs, inputs = circuit
    ws = [cs.witness(*i) for i in inputs] if builder == "witness" else list(cs.witness_batch(inputs))
    assert wc.path_for(cs, ws) == "native"
    for w in ws:
        cs.check_witness(w)
        wc.check_witness(cs, w, "native")


@pytest.mark.parametrize("fault", ["flipped_wire", "over_its_width", "first_constraint", "last_constraint"])
def test_rejects_in_the_loops_own_words(circuit, fault):
    cs, inputs = circuit
    good = cs.witness(*inputs[0])
    vals, rows = list(good), good.u64.copy()
    if fault == "flipped_wire":
        i = cs.num_wires // 2
        vals[i] = (vals[i] + 1) % R
    elif fault == "over_its_width":
        # the widest tag there is: over two limbs on the bigint circuit
        i, bits = max(cs.wire_width.items(), key=lambda t: (t[1], t[0]))
        vals[i] = 1 << bits
    else:
        con = cs.constraints[0 if fault == "first_constraint" else -1]
        i = next(iter(con.c or con.a))
        vals[i] = (vals[i] + 5) % R
    rows[i] = _row(vals[i])
    bad = _with_rows(vals, rows)
    assert wc.path_for(cs, [bad]) == "native"
    want = _loop_message(cs, vals)
    with pytest.raises(AssertionError) as e:
        wc.check_witness(cs, bad, "native")
    assert str(e.value) == want
    if fault == "over_its_width" and "tagged width bound" not in want:
        # a constraint sees the value first (the loop checks constraints
        # before tags): the tag compare alone must still flag the wire
        _, bad_tags = wc.plan_for(cs).faults(native_prove._lib(), rows)
        assert i in wc.plan_for(cs).width_wire[bad_tags]


def test_width_compare_reads_every_limb():
    """`w < 2^bits` on four u64 limbs, at bits on, under and over every
    limb boundary; nothing but tags in this circuit, so the loop's message
    is the tag's."""
    cs = ConstraintSystem("tags")
    tags = [1, 8, 63, 64, 65, 100, 127, 128, 129, 130, 192, 200, 253]
    wires = [cs.new_wire(f"t{b}") for b in tags]
    for wi, b in zip(wires, tags):
        cs.set_width(wi, b)
    top = [1] + [(1 << b) - 1 for b in tags]
    wc.check_witness(cs, _with_rows(top, np.stack([_row(v) for v in top])), "native")
    for wi, b in zip(wires, tags):
        vals = list(top)
        vals[wi] = 1 << b
        bad = _with_rows(vals, np.stack([_row(v) for v in vals]))
        with pytest.raises(AssertionError) as e:
            wc.check_witness(cs, bad, "native")
        assert str(e.value) == _loop_message(cs, vals) and f"bound of {b} bits" in str(e.value)


@pytest.mark.parametrize("extra", [0, 1, 12345])
def test_rows_that_are_not_canonical_are_rejected(circuit, extra):
    """R + x in the rows is x to the loop and to `int(w) % R`; the rows
    have to BE that value, so the check refuses them."""
    cs, inputs = circuit
    good = cs.witness(*inputs[0])
    i = cs.num_wires - 1
    rows = good.u64.copy()
    rows[i] = _row(R + extra)
    with pytest.raises(ValueError, match=f"witness row {i} .*not reduced"):
        wc.check_witness(cs, _with_rows(list(good), rows), "native")


def test_a_check_that_disagrees_with_the_loop_raises_and_does_not_pass(circuit):
    """Rows that say something else than the values: the products flag
    them, the loop accepts the values, and that is an error, not a pass."""
    cs, inputs = circuit
    good = cs.witness(*inputs[0])
    rows = good.u64.copy()
    rows[cs.num_wires // 2] = _row((good[cs.num_wires // 2] + 1) % R)
    with pytest.raises(RuntimeError, match="the two disagree"):
        wc.check_witness(cs, _with_rows(list(good), rows), "native")


def test_a_wire_assigned_after_the_build_drops_the_rows(circuit):
    cs, inputs = circuit
    for w in (cs.witness(*inputs[0]), list(cs.witness_batch(inputs[:2]))[0]):
        assert wc.path_for(cs, [w]) == "native"
        w[1] = 7
        assert w.u64 is None and wc.path_for(cs, [w]) == "python"
    assert wc.path_for(cs, [list(cs.witness(*inputs[0]))]) == "python"  # a plain list has none


def test_many_threads_share_one_plan(circuit):
    """Four replicas check on one constraint system: the plan's spare
    buffers go from hand to hand and no check reads another's products."""
    cs, inputs = circuit
    good = cs.witness(*inputs[0])
    vals, rows = list(good), good.u64.copy()
    i = cs.num_wires // 2
    vals[i] = (vals[i] + 1) % R
    rows[i] = _row(vals[i])
    bad = _with_rows(vals, rows)
    wrong, rounds = [], 12 if cs.num_constraints < 10_000 else 3

    def worker(k):
        for r in range(rounds):
            try:
                wc.check_witness(cs, bad if (k + r) % 2 else good, "native")
                rejected = False
            except AssertionError:
                rejected = True
            if rejected != bool((k + r) % 2):
                wrong.append((k, r))

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=worker, args=(k,)) for k in range(2 * (os.cpu_count() or 4))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads) and not wrong


# ------------------------------------------------------------------ the plan


def _fifty():
    cs = ConstraintSystem("fifty")
    rng = random.Random(33)
    wires = [cs.new_public("p")] + cs.new_wires(30, "w")
    for k in range(50):
        lcs = []
        for _ in range(3):
            lc = LC.const(rng.choice([0, 1, R - 1, rng.randrange(R)]))
            for wi in rng.sample(wires, rng.randrange(0, 4)):
                lc = lc + LC.of(wi, rng.choice([1, R - 1, 1 << 200, rng.randrange(R)]))
            lcs.append(lc)
        cs.enforce(*lcs, tag=f"c{k}")
    cs.enforce(LC(), LC(), LC(), "empty")  # a row with no term in A, B or C
    for wi in wires[::3]:
        cs.set_width(wi, rng.choice([1, 64, 121, 130]))
    return cs


def _terms_of(mx: wc.Matrix):
    lib = native_prove._lib()
    std = np.empty_like(mx.coeff)
    lib.fr_from_mont_batch(wc._p(mx.coeff), wc._p(std), mx.coeff.shape[0])
    rows = np.repeat(mx.seg_rows, np.diff(mx.seg_starts))
    return [(int(r), int(wi), int.from_bytes(c.tobytes(), "little")) for r, wi, c in zip(rows, mx.wire, std)]


def test_the_plan_is_the_constraint_systems():
    cs = _fifty()
    plan = wc.plan_for(cs)
    assert wc.plan_for(cs) is plan  # built once
    assert plan.n_constraints == 51 and plan.n_wires == cs.num_wires
    for m in "abc":
        want = [(r, wi, c % R) for r, con in enumerate(cs.constraints) for wi, c in getattr(con, m).items()]
        assert _terms_of(getattr(plan, m)) == want and want
    assert dict(zip(plan.width_wire.tolist(), plan.width_bits.tolist())) == cs.wire_width
    for wi, bits, limbs in zip(plan.width_wire, plan.width_bits, plan.width_limb_max):
        assert sum(int(v) << (64 * j) for j, v in enumerate(limbs)) == (1 << min(int(bits), 256)) - 1

    x = cs.new_wire("x")
    cs.enforce(LC.of(x), LC.of(x), LC.of(x), "late")
    again = wc.plan_for(cs)
    assert again is not plan and again.n_constraints == 52 and again.n_wires == cs.num_wires
    assert _terms_of(again.a)[-1] == (51, x, 1)
    cs.set_width(x, 1)
    assert wc.plan_for(cs) is not again and x in wc.plan_for(cs).width_wire
    tagged = wc.plan_for(cs)
    cs.set_width(x, 5)  # no tighter: the tag and the plan stay
    assert wc.plan_for(cs) is tagged
    cs.new_wire("y")  # no constraint names it yet, but a witness's rows are one longer
    assert wc.plan_for(cs) is not tagged and wc.plan_for(cs).n_wires == cs.num_wires


def test_a_constraint_on_a_wire_that_does_not_exist_builds_no_plan():
    cs = ConstraintSystem("stray")
    cs.enforce(LC.of(cs.new_wire()), LC.of(7), LC())  # wire 7 of 2: the library would read past the rows
    with pytest.raises(ValueError, match="names wire 7 of 2"):
        wc.plan_for(cs)


# --------------------------------------------------------------- the service


@pytest.fixture(scope="module")
def world():
    from zkp2p_tpu.prover.groth16_tpu import device_pk
    from zkp2p_tpu.snark.groth16 import setup

    cs = ConstraintSystem("svc-check")
    out = cs.new_public("out")
    x, y, z = cs.new_wire("x"), cs.new_wire("y"), cs.new_wire("z")
    cs.enforce(LC.of(x), LC.of(y), LC.of(z), "mul")
    cs.enforce(LC.of(z), LC.of(z), LC.of(out), "sq")
    cs.compute(z, lambda a, b: a * b % R, [x, y])
    pk, vk = setup(cs, seed="svc-check")

    def inputs_fn(p):
        return [int(p["out"])], {x: int(p["x"]), y: int(p["y"])}

    return cs, device_pk(pk, cs), vk, inputs_fn


def _prove_batch(dpk, wits):
    return [native_prove.prove_native(dpk, w, r=123456789, s=987654321) for w in wits]


def _service(world, tier):
    cs, dpk, vk, inputs_fn = world
    return ProvingService(
        cs, dpk, vk, witness_fn=lambda p: cs.witness(*inputs_fn(p)), public_fn=lambda w: [w[1]],
        inputs_fn=inputs_fn if tier == "batched" else None, prover_fn=_prove_batch, batch_size=4, retry_backoff_s=0.0)


def _spool_of_four(spool):
    """Three payloads whose `out` is (x·y)², one whose `out` is not."""
    for i, (xv, yv) in enumerate([(3, 5), (2, 7), (4, 4), (6, 9)]):
        with open(os.path.join(spool, f"r{i}.req.json"), "w") as f:
            json.dump({"x": xv, "y": yv, "out": pow(xv * yv, 2, R) + (i == 2)}, f)


def _request_records(spool):
    with open(str(spool).rstrip("/") + ".metrics.jsonl") as f:
        return [r for r in map(json.loads, f) if r.get("type") == "request"]


def _check_spans():
    """What a run flushes to the sink each sweep; `process_dir` leaves it in the ring."""
    return [r for r in program_trace.drain() if r["stage"].endswith("service/witness_check")]


def _checked(path):
    return REGISTRY.counter("zkp2p_service_witness_check_total", {"path": path}).value


@pytest.mark.parametrize("tier", ["scalar", "batched"])
def test_one_bad_payload_of_four_ends_bad_input_and_three_done(world, tmp_path, tier):
    spool = str(tmp_path)
    _spool_of_four(spool)
    native, python = _checked("native"), _checked("python")
    program_trace.reset()
    stats = _service(world, tier).process_dir(spool)
    assert stats["done"] == 3 and stats["error-bad-input"] == 1
    with open(os.path.join(spool, "r2.error.json")) as f:
        err = json.load(f)
    # the rejection speaks with the loop's voice: index, tag, the three values
    assert err["state"] == "error-bad-input" and err["error"] == "constraint 1 (sq) unsatisfied: 16*16 != 257"
    # the batched tier checks its four up to the third, which fails, and the scalar tier checks all four again
    assert _checked("native") - native == (7 if tier == "batched" else 4) and _checked("python") == python
    spans = _check_spans()
    assert spans and all(s["path"] == "native" for s in spans)
    assert sorted(s["n"] for s in spans) == ([1, 1, 1, 1, 4] if tier == "batched" else [1, 1, 1, 1])
    in_records = [sp for r in _request_records(spool) for sp in r.get("spans", []) if sp["name"] == "witness_check"]
    if tier == "batched":  # nothing else covers the check there: it is on every request's record
        assert len(in_records) == 4 and all(sp["n"] == 4 and sp["path"] == "native" for sp in in_records)
    else:  # `witness` covers it: nested labels in the records would count a gap twice
        assert not in_records
        assert all(s["stage"].endswith("service/witness/service/witness_check") for s in spans)


@pytest.mark.parametrize("tier", ["scalar", "batched"])
def test_without_the_library_the_service_takes_the_loop(world, tmp_path, tier, monkeypatch):
    spool = str(tmp_path)
    _spool_of_four(spool)
    svc = _service(world, tier)
    monkeypatch.setattr(wc, "_native", lambda: None)
    native, python = _checked("native"), _checked("python")
    program_trace.reset()
    stats = svc.process_dir(spool)
    assert stats["done"] == 3 and stats["error-bad-input"] == 1
    with open(os.path.join(spool, "r2.error.json")) as f:
        assert json.load(f)["error"] == "constraint 1 (sq) unsatisfied: 16*16 != 257"
    assert _checked("python") - python == (7 if tier == "batched" else 4) and _checked("native") == native
    spans = _check_spans()
    assert spans and all(s["path"] == "python" for s in spans)


def test_a_service_builds_the_plan_when_it_is_built(world):
    cs = world[0]
    cs._check_plan = None
    _service(world, "scalar")
    assert cs._check_plan is not None
