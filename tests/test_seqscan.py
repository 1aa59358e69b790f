"""Sequence-parallel DFA scan vs the sequential oracle (the CP axis)."""

import numpy as np
import pytest

from zkp2p_tpu.parallel.mesh import make_mesh
from zkp2p_tpu.parallel.seqscan import dfa_scan_host, dfa_scan_sharded
from zkp2p_tpu.regexc import compiler as regexc

@pytest.mark.parametrize("n_dev", [2, 8])
@pytest.mark.parametrize("pattern", [regexc.BODY_HASH, regexc.VENMO_AMOUNT])
def test_dfa_scan_sharded_matches_host(n_dev, pattern):
    dfa = regexc.search_dfa(pattern)
    rng = np.random.default_rng(3)
    # realistic bytes: random printable + embedded matches of the pattern
    data = rng.integers(32, 127, size=256).astype(np.uint8)
    data[40:44] = np.frombuffer(b"bh=Q", dtype=np.uint8)
    data[100:105] = np.frombuffer(b"$42.0", dtype=np.uint8)
    mesh = make_mesh(n_dev)
    got = np.asarray(dfa_scan_sharded(data, dfa, mesh))
    want = dfa_scan_host(data, dfa)
    np.testing.assert_array_equal(got, want)


def test_dfa_scan_host_semantics():
    """The oracle itself: states track the search DFA with restart-on-fail
    folded into the table (dead state only via explicit -1 entries)."""
    dfa = regexc.search_dfa(regexc.VENMO_AMOUNT)
    out = dfa_scan_host(b"xx$42.yy", dfa)
    # After '$' the DFA must have left the start component; after '.' it
    # accepts; trailing bytes fall back into the searching component.
    assert out[2] != 0
    assert int(out[5]) in dfa.accept


def test_pod_mesh_shapes():
    """DCN x ICI mesh factory (pod-scale layout on virtual devices); the
    sharded DFA scan runs unchanged over the inner (ICI) axis."""
    from zkp2p_tpu.parallel.mesh import make_pod_mesh

    mesh = make_pod_mesh(2, 4)
    assert mesh.shape == {"dcn": 2, "shard": 4}
    dfa = regexc.search_dfa(regexc.VENMO_AMOUNT)
    rng = np.random.default_rng(4)
    data = rng.integers(32, 127, size=128).astype(np.uint8)
    got = np.asarray(dfa_scan_sharded(data, dfa, mesh))
    np.testing.assert_array_equal(got, dfa_scan_host(data, dfa))


def test_pod_mesh_dcn_collective():
    """A REAL collective across the dcn axis (not just mesh shapes):
    proof-batch data parallelism psums partial
    results over `dcn` while the inner `shard` axis stays live — the
    cross-slice reduction `make_pod_mesh` exists to carry."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from zkp2p_tpu.parallel.mesh import make_pod_mesh

    mesh = make_pod_mesh(2, 4)
    x = jnp.arange(2 * 4 * 3, dtype=jnp.float32).reshape(2, 4, 3)
    xs = jax.device_put(x, NamedSharding(mesh, P("dcn", "shard", None)))

    @jax.jit
    def step(v):
        # per-(dcn, shard) partial -> sum over BOTH axes via two psums:
        # the inner one rides "shard" (ICI), the outer one crosses "dcn".
        def f(blk):
            local = blk.sum(axis=(0, 1))
            ici = jax.lax.psum(local, "shard")
            return jax.lax.psum(ici, "dcn")[None, None]

        return jax.shard_map(
            f, mesh=mesh, in_specs=P("dcn", "shard", None), out_specs=P("dcn", "shard")
        )(v)

    got = np.asarray(step(xs))
    want = np.asarray(x.sum(axis=(0, 1)))
    for row in got.reshape(-1, 3):
        np.testing.assert_array_equal(row, want)
