"""Distributed-axis tests on the 8-virtual-device CPU mesh (conftest).

The reference has no real distributed backend (its parallelism is S3
artifact chunking + rapidsnark threads, SURVEY.md §2.7); ours is XLA
collectives over a jax.sharding.Mesh.  These tests pin the semantics the
driver's dryrun_multichip exercises: sharded MSM == unsharded MSM == host
oracle, for every mesh width that divides 8.
"""

import jax
import numpy as np
import pytest

from zkp2p_tpu.curve.host import G1_GENERATOR, g1_msm, g1_mul
from zkp2p_tpu.curve.jcurve import G1J, g1_jac_to_host, g1_to_affine_arrays
from zkp2p_tpu.field.jfield import int_to_limbs
from zkp2p_tpu.ops import msm as jmsm
from zkp2p_tpu.parallel.mesh import make_mesh, msm_sharded, pad_to_multiple

# XLA-compile-heavy: opt-in via ZKP2P_RUN_SLOW=1 (default suite must stay
# minutes on a 1-core host; the dryrun/bench paths exercise this code too)
pytestmark = [pytest.mark.slow, pytest.mark.xslow]

N = 11  # deliberately not a multiple of any mesh size (exercises padding)


def _fixture():
    rng = np.random.default_rng(42)
    pts = [g1_mul(G1_GENERATOR, int(k)) for k in rng.integers(1, 2**62, N)]
    scalars = [int(s) for s in rng.integers(1, 2**62, N)]
    limbs = jax.numpy.asarray(np.stack([int_to_limbs(s) for s in scalars]))
    return pts, scalars, limbs


def test_make_mesh_shapes():
    assert make_mesh(8).shape["shard"] == 8
    assert make_mesh(2).shape["shard"] == 2
    assert make_mesh().size == len(jax.devices())


@pytest.mark.parametrize("n_dev", [2, 4, 8])
def test_msm_sharded_matches_host(n_dev):
    pts, scalars, limbs = _fixture()
    bases = g1_to_affine_arrays(pts)
    planes = jmsm.digit_planes_from_limbs(limbs)
    mesh = make_mesh(n_dev)
    bases_p, planes_p = pad_to_multiple(bases, planes, n_dev * 2)
    acc = msm_sharded(G1J, bases_p, planes_p, mesh, lanes=2, window=4)
    assert g1_jac_to_host(acc)[0] == g1_msm(pts, scalars)


@pytest.mark.parametrize("n_dev", [2, 4, 8])
@pytest.mark.parametrize("inverse", [False, True])
def test_ntt_sharded_matches_single_device(n_dev, inverse):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from zkp2p_tpu.field.jfield import FR
    from zkp2p_tpu.ops.ntt import intt, ntt
    from zkp2p_tpu.parallel.ntt import ntt_sharded

    log_m = 6
    m = 1 << log_m
    rng = np.random.default_rng(7)
    vals = [int.from_bytes(rng.bytes(31), "big") for _ in range(m)]
    x = jax.numpy.asarray(np.stack([FR.to_mont_host(v) for v in vals]))
    want = intt(x, log_m) if inverse else ntt(x, log_m)

    mesh = make_mesh(n_dev)
    xs = jax.device_put(x, NamedSharding(mesh, P("shard", None)))
    got = ntt_sharded(xs, log_m, mesh, inverse=inverse)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_prove_tpu_sharded_matches_host():
    """The production multi-chip prove path (sharded NTT + sharded MSM,
    prover/groth16_tpu.prove_tpu_sharded) emits the exact proof the host
    oracle does — the dryrun_multichip contract.

    ONE small config (2 devices, domain 16, unified G1 executable):
    compile count is what blows the 1-core suite budget — the full
    8-device configuration is exercised (and recorded) by the driver's
    own `dryrun_multichip` artifact every round, so the suite checks the
    dataflow's bit-exactness, not the big mesh."""
    from zkp2p_tpu.field.bn254 import R
    from zkp2p_tpu.prover.groth16_tpu import device_pk, prove_tpu_sharded
    from zkp2p_tpu.snark.groth16 import prove_host, setup, verify
    from zkp2p_tpu.snark.r1cs import LC, ConstraintSystem

    # Chain circuit sized so the domain is 16: both Bailey factors
    # divisible by the mesh width.
    cs = ConstraintSystem("chain")
    pub = cs.new_public("out")
    prev = cs.new_wire("x0")
    wires = [prev]
    for i in range(10):
        w = cs.new_wire(f"x{i + 1}")
        cs.enforce(LC.of(prev) + LC.const(i), LC.of(prev), LC.of(w))
        cs.compute(w, lambda v, k=i: (v + k) * v % R, [prev])
        wires.append(w)
        prev = w
    cs.enforce(LC.of(prev), LC.const(1), LC.of(pub), "out")
    seedv = 3
    vals = {wires[0]: seedv}
    v = seedv
    for i in range(10):
        v = (v + i) * v % R
    w = cs.witness([v], vals)
    cs.check_witness(w)
    pk, vk = setup(cs, seed="chain")
    dpk = device_pk(pk, cs)
    mesh = make_mesh(2)
    r, s = 123456789, 987654321
    got = prove_tpu_sharded(dpk, w, mesh, r=r, s=s, lanes=2, unified=True)
    want = prove_host(pk, cs, w, r=r, s=s)
    assert got == want
    assert verify(vk, got, [v])


def test_msm_sharded_bitplane_path():
    pts, scalars, limbs = _fixture()
    bases = g1_to_affine_arrays(pts)
    planes = jmsm.bit_planes_from_limbs(limbs)
    mesh = make_mesh(4)
    bases_p, planes_p = pad_to_multiple(bases, planes, 8)
    acc = msm_sharded(G1J, bases_p, planes_p, mesh, lanes=2)
    assert g1_jac_to_host(acc)[0] == g1_msm(pts, scalars)


def test_msm_pod_batched_dcn_axis():
    """A REAL collective over the dcn axis: proof batch data-parallel
    over dcn, base axis sharded over ici, one proof point per batch element crossing
    DCN — each batched result must equal the host oracle."""
    from zkp2p_tpu.parallel.mesh import make_pod_mesh, msm_pod_batched

    mesh = make_pod_mesh(2, 4)  # 2 slices x 4-wide ICI on the 8 vdevs
    pts, _, _ = _fixture()
    rng = np.random.default_rng(7)
    batch_scalars = [[int(s) for s in rng.integers(1, 2**62, N)] for _ in range(4)]
    planes = jax.numpy.stack(
        [
            jmsm.digit_planes_from_limbs(
                jax.numpy.asarray(np.stack([int_to_limbs(s) for s in sc])), 4
            )
            for sc in batch_scalars
        ]
    )
    bases, planes = pad_to_multiple(g1_to_affine_arrays(pts), planes[0], 8)[0], planes
    # pad the plane N axis to the padded base count
    pad = bases[0].shape[0] - N
    planes = jax.numpy.pad(planes, [(0, 0), (0, 0), (0, pad)])
    acc = msm_pod_batched(G1J, bases, planes, mesh, lanes=8, window=4)
    got = g1_jac_to_host(acc)
    for i, sc in enumerate(batch_scalars):
        assert got[i] == g1_msm(pts, sc), f"batch element {i}"
