"""A proof's assembly in the native library (PR 42):
`csrc/zkp2p_native.cpp::groth16_assemble_bn254` against the oracle it
must answer as, `snark/native_assemble.py::assemble_python` (the body
`prover/groth16_tpu.py::_assemble` had, `_assemble_host` there): the same
point for every input the library takes, no answer for the others, and
through the real `prove_tpu_batch` around a stood-in device the bytes of
`prove_native`, which assembles with the oracle."""

import random
import threading
from types import SimpleNamespace

import numpy as np
import pytest

from test_replicas import needs_native, world  # noqa: F401 — `world` is a fixture

import zkp2p_tpu.native.lib as nl
from zkp2p_tpu.curve.host import G1_GENERATOR, G2_GENERATOR, g1_mul, g1_neg, g2_mul, g2_neg
from zkp2p_tpu.field.bn254 import P, R
from zkp2p_tpu.prover import groth16_tpu as G
from zkp2p_tpu.snark import native_assemble as na
from zkp2p_tpu.utils import trace as tr
from zkp2p_tpu.utils.metrics import REGISTRY


def _world(seed):
    """A key's five points and five accumulators, multiples of the
    generators by seeded full-width scalars, and a blinding."""
    rng = random.Random(seed)
    g1 = lambda: g1_mul(G1_GENERATOR, rng.randrange(1, R))  # noqa: E731
    g2 = lambda: g2_mul(G2_GENERATOR, rng.randrange(1, R))  # noqa: E731
    key = SimpleNamespace(alpha_1=g1(), beta_1=g1(), delta_1=g1(), beta_2=g2(), delta_2=g2())
    return key, [g1(), g1(), g2(), g1(), g1()], rng.randrange(1, R), rng.randrange(1, R)


def _case(name):
    """(key, acc, r, s) of a named case: what is not named is seeded."""
    key, acc, r, s = _world(name)
    kind, _, arg = name.partition(":")
    if kind == "blinding":
        r, s = ({"1": 1, "R-1": R - 1, "0": 0, "2^256-1": (1 << 256) - 1}[v] for v in arg.split(","))
    elif kind == "infinity":
        for i in range(5):
            if arg in (str(i), "all"):
                acc[i] = None
    elif name == "a=alpha_1":  # the first sum is a doubling
        acc[0] = key.alpha_1
    elif name == "a=-alpha_1":  # ... is infinity, and so is s·pi_a where r·delta_1 is nothing
        acc[0], r = g1_neg(key.alpha_1), 0
    elif name == "a=-alpha_1-r.delta_1":  # pi_a itself is infinity under a blinding
        acc[0] = g1_neg(na.assemble_python(key, [None] * 5, r, s).a)
    elif name == "b1=beta_1":
        acc[1] = key.beta_1
    elif name == "c=h":
        acc[3] = acc[4]
    elif name == "c=-h":
        acc[3] = g1_neg(acc[4])
    elif name == "b2=beta_2":
        acc[2] = key.beta_2
    elif name == "b2=-beta_2":
        acc[2] = g2_neg(key.beta_2)
    elif name == "b2=-beta_2-s.delta_2":  # pi_b itself is infinity
        acc[2] = g2_neg(na.assemble_python(key, [None] * 5, r, s).b)
    elif name == "pi_c=infinity":
        acc[3], acc[4] = g1_neg(na.assemble_python(key, acc[:3] + [None, None], r, s).c), None
    else:
        assert kind == "seeded", name
    return key, acc, r, s


CASES = (
    ["seeded:%d" % i for i in range(4)]
    + ["blinding:%s,%s" % (r, s) for r in ("1", "R-1") for s in ("1", "R-1")]
    + ["blinding:0,0", "blinding:2^256-1,2^256-1", "blinding:R-1,2^256-1"]
    + ["infinity:%s" % i for i in ("0", "1", "2", "3", "4", "all")]
    + ["a=alpha_1", "a=-alpha_1", "a=-alpha_1-r.delta_1", "b1=beta_1", "c=h", "c=-h",
       "b2=beta_2", "b2=-beta_2", "b2=-beta_2-s.delta_2", "pi_c=infinity"]
)


@needs_native
@pytest.mark.parametrize("name", CASES)
def test_the_native_assembly_is_the_oracle_s_point_for_point(name):
    key, acc, r, s = _case(name)
    want = na.assemble_python(key, acc, r, s)
    got = na.assemble_native(nl.get_lib(), key, acc, r, s)
    assert got is not None and (got.a, got.b, got.c) == (want.a, want.b, want.c)
    if name in ("a=-alpha_1", "a=-alpha_1-r.delta_1"):
        assert got.a is None
    if name == "b2=-beta_2-s.delta_2":
        assert got.b is None
    if name == "pi_c=infinity":
        assert got.c is None
    assert na.assemble(key, acc, r, s) == (want, "native")


@needs_native
@pytest.mark.parametrize("name", ["a_off_the_curve", "b2_off_the_twist", "delta_1_off_the_curve", "a_coordinate_at_p",
                                  "r_negative", "s_at_2^256", "a_at_the_library_s_infinity"])
def test_where_the_library_does_not_decide_it_gives_no_answer_and_the_oracle_s_is_the_caller_s(name):
    key, acc, r, s = _world(name)
    if name == "a_off_the_curve":
        acc[0] = (acc[0][0], (acc[0][1] + 1) % P)
    elif name == "b2_off_the_twist":
        acc[2] = (acc[2][1], acc[2][0])
    elif name == "delta_1_off_the_curve":
        key.delta_1 = (key.delta_1[1], key.delta_1[0])
    elif name == "a_coordinate_at_p":
        acc[0] = (acc[0][0] + P, acc[0][1])
    elif name == "r_negative":
        r = -r
    elif name == "s_at_2^256":
        s = 1 << 256
    else:
        acc[0] = (0, 0)
    assert na.assemble_native(nl.get_lib(), key, acc, r, s) is None
    if name != "a_at_the_library_s_infinity":  # not a point of the Python form either
        assert na.assemble(key, acc, r, s) == (na.assemble_python(key, acc, r, s), "python")


# ---------------------------------------- through prove_tpu_batch itself


@pytest.fixture
def stood_in_device(monkeypatch, world):  # noqa: F811
    """`prove_tpu_batch` itself, its `finish` and its `_assemble` the real
    ones, around a device stood in for: stages ready at once, and for
    accumulators the C++ prover's own, caught at its assembly.  Answers
    (the entry, the witnesses, their blindings, `prove_native`'s proofs)."""
    from zkp2p_tpu.prover import native_prove

    dpk = world[1]
    wits = [world[3]({"x": 2 + i, "y": 3 + i}) for i in range(3)]
    rng = random.Random("stood_in_device")
    rs, ss = ([rng.randrange(1, R) for _ in wits] for _ in range(2))
    caught = []

    def catching(dpk_, acc, r, s):
        caught.append(acc)
        return G._assemble_host(dpk_, acc, r, s)

    monkeypatch.setattr(native_prove, "_assemble_host", catching)
    want = [native_prove.prove_native(dpk, w, r, s) for w, r, s in zip(wits, rs, ss)]
    assert len(caught) == len(wits)

    def device(dpk_, w_mont, batched=False, watch=None):
        for name in G.STAGES:
            watch.enqueued(name, np.zeros(1, np.uint32))
        return tuple(np.full(w_mont.shape[0], i, np.uint32) for i in range(5))  # each accumulator by its index

    def to_host(acc):
        return [caught[proof][int(i)] for proof, i in enumerate(acc)]

    monkeypatch.setattr(G, "_prove_device", device)
    monkeypatch.setattr(G, "_h_table", lambda dpk_: None)
    monkeypatch.setattr(G, "g1_jac_to_host", to_host)
    monkeypatch.setattr(G, "g2_jac_to_host", to_host)
    monkeypatch.setattr(G, "_fed_last", {})
    monkeypatch.setenv("ZKP2P_TPU_SHARD", "off")
    monkeypatch.setattr(G, "BATCH_CHUNK", "0")
    tr.reset()
    tr.clear_context()
    return G.prove_tpu_batch, dpk, wits, rs, ss, want


def _assembled(path):
    return REGISTRY.counter("zkp2p_assemble_total", {"path": path}).value


def _finish_spans():
    return [r for r in tr.records() if r["stage"].endswith("prove_batch/finish")]


@needs_native
@pytest.mark.parametrize("library", ["loaded", "unavailable"])
def test_a_batch_s_finish_says_which_form_assembled_it_and_the_bytes_are_prove_native_s(stood_in_device, monkeypatch, library):
    prove, dpk, wits, rs, ss, want = stood_in_device
    path, other = ("native", "python") if library == "loaded" else ("python", "native")
    if library == "unavailable":  # as where the build failed: get_lib() is None
        monkeypatch.setattr(nl, "_lib", None)
        monkeypatch.setattr(nl, "_tried", True)
    before = _assembled(path), _assembled(other)
    got = prove(dpk, wits, rs=rs, ss=ss)
    assert got == want
    (finish,) = _finish_spans()
    assert finish["assemble"] == path
    assert (_assembled(path), _assembled(other)) == (before[0] + len(wits), before[1])


@needs_native
def test_the_prover_looks_its_assembly_up_by_name_one_call_a_proof(stood_in_device, monkeypatch):
    """What benchmarks/tests and five tier-1 files lean on: `_assemble(dpk,
    acc, r, s)` through the module's globals, affine host points in."""
    prove, dpk, wits, rs, ss, want = stood_in_device
    calls = []
    monkeypatch.setattr(G, "_assemble", lambda dpk_, acc, r, s: calls.append((acc, r, s)) or G._assemble_host(dpk_, acc, r, s))
    assert prove(dpk, wits, rs=rs, ss=ss) == want
    assert [(r, s) for _, r, s in calls] == list(zip(rs, ss)) and all(len(acc) == 5 for acc, _, _ in calls)


@needs_native
def test_four_threads_assemble_at_once_and_each_gets_its_own_proof():
    """Four replicas' proving threads share one library: nothing in the
    call is shared, so the answers are each thread's own."""
    worlds = [_world("thread:%d" % i) for i in range(4)]
    want = [na.assemble_python(*w) for w in worlds]
    got, lib = [None] * 4, nl.get_lib()

    def run(i):
        for _ in range(20):
            got[i] = na.assemble_native(lib, *worlds[i])
            if got[i] != want[i]:
                return

    threads = [threading.Thread(target=run, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert got == want


@needs_native
def test_load_time_self_check_refuses_a_wrong_assembly(monkeypatch):
    """`get_lib` asks the library one assembly and compares it with the
    Python form: a library whose answer is another point, or none, is no
    library, rather than a wrong proof."""
    real = na.assemble_native

    def another_point(lib, key, acc, r, s):
        proof = real(lib, key, acc, r, s)
        return type(proof)(a=proof.a, b=proof.b, c=g1_neg(proof.c))

    for wrong in (another_point, lambda lib, key, acc, r, s: None):
        monkeypatch.setattr(na, "assemble_native", wrong)
        monkeypatch.setattr(nl, "_lib", None)
        monkeypatch.setattr(nl, "_tried", False)
        assert nl.get_lib() is None
    monkeypatch.undo()
    assert nl.get_lib() is not None
