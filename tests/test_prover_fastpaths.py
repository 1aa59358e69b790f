"""Fast-tier checks for prover host-side fast paths (no big compiles)."""

import numpy as np

from zkp2p_tpu.field.bn254 import R
from zkp2p_tpu.prover.groth16_tpu import witness_to_device


def _to_u64_rows(vals):
    rows = []
    for v in vals:
        rows.append([(v >> (64 * i)) & 0xFFFFFFFFFFFFFFFF for i in range(4)])
    return np.array(rows, dtype=np.uint64)


def test_witness_to_device_matches_host_mont_golden():
    """Both input forms (int sequence, (n, 4)-u64 limb array — the
    full-size witness cache format) must emit limbs byte-identical to
    the host-side FR.to_mont_host golden, per wire."""
    from zkp2p_tpu.field.jfield import FR

    rng = np.random.default_rng(7)
    vals = [0, 1, R - 1, R - 2, 0xFFFF, 1 << 64, (1 << 128) + 12345]
    vals += [int.from_bytes(rng.bytes(31), "little") % R for _ in range(25)]
    golden = np.stack([FR.to_mont_host(v % R) for v in vals])
    from_ints = np.asarray(witness_to_device(vals))
    from_u64 = np.asarray(witness_to_device(_to_u64_rows(vals)))
    assert from_ints.dtype == from_u64.dtype == np.uint32
    assert (from_ints == golden).all()
    assert (from_u64 == golden).all()


def test_witness_u64_fast_path_rejects_unreduced():
    """The (n, 4)-u64 fast path trusts its rows to be < R; an unreduced
    row must raise at the witness_to_device boundary instead of silently
    emitting a wrong Montgomery form (ADVICE r5 #3)."""
    import pytest

    for bad in (R, R + 1, (1 << 256) - 1):
        rows = _to_u64_rows([1, 2, bad, 3])
        with pytest.raises(ValueError, match="not reduced"):
            witness_to_device(rows)
    # boundary value R - 1 stays accepted
    witness_to_device(_to_u64_rows([R - 1]))


def test_the_matvec_reduced_in_row_blocks_is_the_matvec_reduced_in_one(monkeypatch):
    """Above SEGMENT_REDUCE_ROWS segments the rows' limb sums are reduced
    a block of rows at a time: the same field elements as the one-block
    program and as plain integers; a row count no block divides keeps the
    one-block program."""
    import jax.numpy as jnp

    from zkp2p_tpu.field import jfield
    from zkp2p_tpu.field.jfield import FR
    from zkp2p_tpu.prover import groth16_tpu as G

    rng = np.random.default_rng(11)
    n_wires, m, nnz = 12, 8, 43
    w = [int.from_bytes(rng.bytes(31), "little") % R for _ in range(n_wires)]
    coeff = [int.from_bytes(rng.bytes(31), "little") % R for _ in range(nnz)]
    wire = rng.integers(0, n_wires, nnz).astype(np.int32)
    row = np.sort(rng.integers(0, m, nnz)).astype(np.int32)
    args = (jnp.asarray(np.stack([FR.to_mont_host(c) for c in coeff])), jnp.asarray(wire), jnp.asarray(row),
            jnp.asarray(np.stack([FR.to_mont_host(v) for v in w])), m)
    one = np.asarray(G._matvec(*args))
    want = [sum(coeff[j] * w[wire[j]] for j in range(nnz) if row[j] == i) % R for i in range(m)]
    assert [FR.from_mont_host(r) for r in one] == want
    for rows in (2, 4, 3):
        monkeypatch.setattr(jfield, "SEGMENT_REDUCE_ROWS", rows)
        assert (np.asarray(G._matvec(*args)) == one).all(), rows
