"""The mesh road's two layouts, from arrays, with no program of the curve
compiled (tests/test_tpu_shard.py's byte-parity tests need a warm compile
cache and a fresh tier-1 run skips them): the key as `place_key` lays it
on a pod mesh, what the exchange leaves on each chip, and the per-chip
MSM partials that fold to the whole — on the CPU's virtual devices."""

import numpy as np
import pytest

from test_tpu_shard import _toy_wits, build_toy

from zkp2p_tpu.field.bn254 import R

MESHES = [(1, 4), (2, 2), (4, 1), (1, 8)]


@pytest.fixture(scope="module")
def toy():
    from zkp2p_tpu.prover import device_pk
    from zkp2p_tpu.snark.groth16 import setup

    cs, _out, x, y = build_toy()
    pk, _vk = setup(cs)
    wits, _ = _toy_wits(cs, x, y, [(3, 5), (2, 7), (10, 11), (R - 1, 1)])
    return cs, pk, device_pk(pk, cs), wits


def _mesh(b, s):
    from zkp2p_tpu.parallel.mesh import make_pod_mesh

    return make_pod_mesh(b, s, names=("batch", "shard"))


def _exchanged(dpk, mesh, limbs, h_std):
    """The exchange program on the placed key, fed the h stage's layout."""
    import jax
    from jax.sharding import NamedSharding

    from zkp2p_tpu.prover import groth16_tpu as G

    from jax.sharding import PartitionSpec as P

    placed = G.place_key(dpk, mesh)
    split = G._pod_split(mesh, limbs.shape[0])
    chunk = NamedSharding(mesh, G._pod_chunk_spec(mesh, split))
    # whole proofs a chip where the chunk is split; else the shared h stage's: a chip its columns of each proof
    h_layout = chunk if split else NamedSharding(mesh, P("batch", "shard"))
    fn = G._exchange_pod_fn(mesh, split, placed.a_bases[0].shape[0], placed.h_bases[0].shape[0])
    return placed, fn((placed.b_sel, placed.c_sel), jax.device_put(limbs, chunk), jax.device_put(h_std, h_layout))[:4]


def _whole_planes(cols, n_to):
    """(B, n, 16) standard-form limbs -> the unsharded (B, 64, n_to)
    unsigned w=4 digit planes, most significant first, by plain numpy."""
    cols = np.pad(cols, [(0, 0), (0, n_to - cols.shape[1]), (0, 0)])
    digits = (cols[..., None] >> (4 * np.arange(4, dtype=np.uint32))) & 15  # (B, n, 16, 4), least significant first
    return np.moveaxis(digits.reshape(*cols.shape[:2], 64)[..., ::-1], -1, 1)


@pytest.mark.parametrize("b,s", MESHES)
def test_the_placed_key_is_the_padded_key_in_shards(toy, b, s):
    """(a) The shards of each base array, in the order of the mesh's
    "shard" axis, concatenate to the key's array padded with infinity
    bases to whole steps of every shard; `b_sel` / `c_sel` are split the
    same way, so a chip holds the wire of every base it holds; the QAP
    rows are whole on every chip; the key handed in is left as it was."""
    from zkp2p_tpu.prover import groth16_tpu as G

    _cs, _pk, dpk, _wits = toy
    mesh = _mesh(b, s)
    placed = G.place_key(dpk, mesh)
    assert G.key_mesh(placed) == mesh and G.key_mesh(dpk) is None and G.key_device(placed) is None
    for f in G._POD_BASES:
        for got, want in zip(getattr(placed, f), getattr(dpk, f)):
            n, n_to = want.shape[0], got.shape[0]
            assert n <= n_to and n_to % (s * G.pod_lanes(n, s)) == 0 and G.pod_lanes(n_to, s) == G.pod_lanes(n, s)
            shards = sorted(got.addressable_shards, key=lambda sh: (sh.index[0].start or 0, sh.device.id))
            assert len(shards) == b * s and all(sh.data.shape[0] == n_to // s for sh in shards)
            whole = np.concatenate([np.asarray(sh.data) for sh in shards[::b]])  # one of each shard's `b` replicas
            assert (whole[:n] == np.asarray(want)).all() and not whole[n:].any()  # (0, 0): infinity
    for sel, bases in (("b_sel", "b1_bases"), ("c_sel", "c_bases")):
        got, want = getattr(placed, sel), np.asarray(getattr(dpk, sel))
        assert got.shape[0] == getattr(placed, bases)[0].shape[0]
        assert (np.asarray(got)[: len(want)] == want).all() and not np.asarray(got)[len(want):].any()
        for sel_sh, base_sh in zip(got.addressable_shards, getattr(placed, bases)[0].addressable_shards):
            assert sel_sh.index[0] == base_sh.index[0] and sel_sh.device == base_sh.device
    assert placed.b2_bases[0].shape[0] == placed.b1_bases[0].shape[0]  # b_sel serves both
    for f in G._QAP_ROWS:
        got = getattr(placed, f)
        assert len(got.addressable_shards) == b * s
        assert all((np.asarray(sh.data) == np.asarray(getattr(dpk, f))).all() for sh in got.addressable_shards)
    assert not placed.a_nsel.shape[0] and not placed.b_wsel.shape[0]  # no narrow class on the mesh


@pytest.mark.parametrize("b,s,n_proofs", [(1, 4, 4), (2, 2, 4), (4, 1, 4), (1, 4, 3), (1, 4, 1), (2, 2, 2), (1, 2, 3)])
def test_after_the_exchange_each_chip_holds_the_columns_of_its_bases(toy, b, s, n_proofs):
    """(b) For every proof of its group a chip holds exactly the columns
    of the unsharded digit planes that belong to the bases it holds: of
    a, b (through `b_sel`), c (through `c_sel`) and h, whether the
    group's proofs were split over its chips or, where the chips do not
    divide them, each proof's h stage was shared by them (h then arrives
    in those columns already)."""
    from zkp2p_tpu.prover import groth16_tpu as G

    _cs, _pk, dpk, wits = toy
    mesh = _mesh(b, s)
    limbs = np.stack([G._witness_std_limbs(w) for w in wits[:n_proofs]])
    m = 1 << dpk.log_m
    h_std = np.random.default_rng(7).integers(0, 1 << 16, (n_proofs, m, 16), dtype=np.uint32)  # any scalars do
    placed, planes = _exchanged(dpk, mesh, limbs, h_std)
    assert G._pod_split(mesh, n_proofs) == ((n_proofs // b) % s == 0)
    # the placed selections: past the key's own, their filler names wire 0 against an infinity base
    wants = (limbs, limbs[:, np.asarray(placed.b_sel)], limbs[:, np.asarray(placed.c_sel)], h_std)
    for got, cols, bases in zip(planes, wants, (placed.a_bases, placed.b1_bases, placed.c_bases, placed.h_bases)):
        n_to = bases[0].shape[0]
        want = _whole_planes(cols, n_to)
        assert got.shape == (n_proofs, 64, n_to)
        by_device = {sh.device: sh.index[0] for sh in bases[0].addressable_shards}
        for sh in got.addressable_shards:
            assert sh.index[2] == by_device[sh.device]  # the columns of the bases this chip holds
            assert sh.data.shape == (n_proofs // b, 64, n_to // s)  # for every proof of its group, and no more
            assert (np.asarray(sh.data) == want[sh.index]).all()


@pytest.mark.parametrize("b,s", [(1, 4), (2, 2)])
def test_the_chips_msm_partials_fold_to_the_whole_msm(toy, b, s):
    """(c) The share sums to the whole: each chip's MSM over the bases
    it holds and the digits the exchange left it, computed on the host
    from the placed arrays' shards, group-added over the "shard" axis,
    is the MSM of the whole query over the witness — the allreduce's
    claim (`msm_pod_batched`: all_gather + projective fold), without a
    program of the curve."""
    from zkp2p_tpu.curve.host import g1_add, g1_msm
    from zkp2p_tpu.prover import groth16_tpu as G

    _cs, pk, dpk, wits = toy
    mesh = _mesh(b, s)
    limbs = np.stack([G._witness_std_limbs(w) for w in wits])
    m = 1 << dpk.log_m
    rng = np.random.default_rng(11)
    h_scalars = [[int(v) for v in rng.integers(1, 1 << 62, m)] for _ in wits]
    h_std = np.stack([G._witness_std_limbs(row) for row in h_scalars])
    _placed, planes = _exchanged(dpk, mesh, limbs, h_std)
    b_sel, c_sel = np.asarray(dpk.b_sel), np.asarray(dpk.c_sel)
    queries = (
        (list(pk.a_query), [[int(v) % R for v in w] for w in wits]),
        ([pk.b1_query[i] for i in b_sel], [[int(w[i]) % R for i in b_sel] for w in wits]),
        ([pk.c_query[i] for i in c_sel], [[int(w[i]) % R for i in c_sel] for w in wits]),
        (list(pk.h_query) + [None] * (m - len(pk.h_query)), h_scalars),
    )
    for got, (points, scalars) in zip(planes, queries):
        for proof, row in enumerate(scalars):
            live = [(p, k) for p, k in zip(points, row) if p is not None]
            whole = g1_msm([p for p, _ in live], [k for _, k in live])
            folded, seen = None, 0
            for sh in got.addressable_shards:
                if not (sh.index[0].start or 0) <= proof < (sh.index[0].stop or len(wits)):
                    continue  # another group's chip
                lo = sh.index[2].start or 0
                digits = np.asarray(sh.data)[proof - (sh.index[0].start or 0)]  # (64, n_local), most significant first
                part_pts, part_ks = [], []
                for j in range(digits.shape[1]):
                    k = 0
                    for d in digits[:, j]:
                        k = 16 * k + int(d)
                    if lo + j < len(points):  # past them filler lanes sit against infinity bases: they add nothing
                        assert k == row[lo + j]  # the scalar of the base this chip holds
                        if points[lo + j] is not None:
                            part_pts.append(points[lo + j])
                            part_ks.append(k)
                part = g1_msm(part_pts, part_ks) if part_pts else None
                folded = part if folded is None else (folded if part is None else g1_add(folded, part))
                seen += 1
            assert seen == s and folded == whole
