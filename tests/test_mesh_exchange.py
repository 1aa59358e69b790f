"""The mesh road's two layouts, from arrays, with no program of the curve
compiled (tests/test_tpu_shard.py's byte-parity tests need a warm compile
cache and a fresh tier-1 run skips them): the key as `place_key` lays it
on a pod mesh, in classes; what the exchange leaves on each chip, in signed
digits; the per-chip MSM partials over both classes that fold to the whole;
and, with a stand-in for the curve that is cheap to compile, the road's five
accumulators beside `_prove_device`'s — on the CPU's virtual devices."""

import numpy as np
import pytest

from test_msm_resident import _no_narrow_class as without_widths
from test_tpu_shard import _toy_wits, build_toy

from zkp2p_tpu.field.bn254 import R

MESHES = [(1, 4), (2, 2), (4, 1), (1, 8)]
QUERIES = ("a", "b1", "b2", "c")


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


def key_classes(dpk):
    """What `place_key` is held to, from the key alone: for each query
    its (narrow, wide) classes as (positions into the query's base
    array, the wire of each), by the key's own selections; every base
    wide where it has none."""
    n_a = dpk.a_bases[0].shape[0]
    classed = int(dpk.a_nsel.shape[0]) > 0
    out = {}
    for q, sels, wire_of in (("a", (dpk.a_nsel, dpk.a_wsel), np.arange(n_a)), ("b1", (dpk.b_nsel, dpk.b_wsel), dpk.b_sel),
                             ("b2", (dpk.b_nsel, dpk.b_wsel), dpk.b_sel), ("c", (dpk.c_nsel, dpk.c_wsel), dpk.c_sel)):
        wire_of = np.asarray(wire_of)
        sels = [np.asarray(s) for s in sels] if classed else [np.zeros(0, np.int32), np.arange(len(wire_of))]
        out[q] = [(sel, wire_of[sel]) for sel in sels]
    return out


def placed_wires(placed):
    """The wire ids `_prove_batch_sharded` hands the exchange: a (narrow,
    wide) pair a query."""
    return tuple(tuple(cls[2] for cls in getattr(placed, q + "_bases")) for q in QUERIES)


def _exchanged(dpk, mesh, limbs, h_std):
    """The exchange program on the placed key, fed the h stage's layout."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from zkp2p_tpu.prover import groth16_tpu as G

    placed = G.place_key(dpk, mesh)
    split = G._pod_split(mesh, limbs.shape[0])
    chunk = NamedSharding(mesh, G._pod_chunk_spec(mesh, split))
    # whole proofs a chip where the chunk is split; else the shared h stage's: a chip its columns of each proof
    h_layout = chunk if split else NamedSharding(mesh, P("batch", "shard"))
    fn = G._exchange_pod_fn(mesh, split, placed.h_bases[0].shape[0], G.MSM_WINDOW)
    w_planes, h_planes, _done = fn(placed_wires(placed), jax.device_put(limbs, chunk), jax.device_put(h_std, h_layout))
    return placed, w_planes, h_planes


def signed_digits(cols):
    """(B, n, 16) standard-form limbs -> the unsharded signed base-16
    digits (B, 64, n) int64, most significant first, by plain Python:
    least significant first, a digit above 8 borrows 16 from the next."""
    out = np.zeros((cols.shape[0], 64, cols.shape[1]), np.int64)
    for b in range(cols.shape[0]):
        for j in range(cols.shape[1]):
            k, carry = sum(int(v) << (16 * i) for i, v in enumerate(cols[b, j])), 0
            for i in range(64):
                d = ((k >> (4 * i)) & 15) + carry
                carry = int(d > 8)
                out[b, 63 - i, j] = d - 16 * carry
            assert carry == 0
    return out


def scalars_of(mags, negs):
    """Signed digit planes (planes, n), most significant first, the low
    planes of a scalar -> the scalars they spell."""
    signed = np.where(np.asarray(negs), -1, 1) * np.asarray(mags).astype(np.int64)
    return [sum(int(d) * 16 ** (len(col) - 1 - i) for i, d in enumerate(col)) for col in signed.T]


def host_points(curve, bases):
    """A class's affine Montgomery arrays -> host points, None for the
    (0, 0) infinity filler."""
    from zkp2p_tpu.curve.jcurve import G2J, g1_jac_to_host, g2_jac_to_host

    x, y = (np.asarray(c) for c in bases)
    z = np.zeros_like(x)
    live = (x.reshape(len(x), -1) | y.reshape(len(y), -1)).any(axis=1)
    z[live] = np.asarray(curve.F.one_mont)
    return (g2_jac_to_host if curve is G2J else g1_jac_to_host)((x, y, z))


def host_pod_msm(curve, bases, planes, mesh=None, **kw):
    """`msm_pod_batched` answered on the host from what it is handed:
    every class's bases against the scalars its signed planes spell,
    summed over the classes; (B,)-batched projective, Z = 1."""
    from test_witness_forms import _proj_g1, _proj_g2

    from zkp2p_tpu.curve.host import g1_add, g1_msm, g2_add, g2_msm
    from zkp2p_tpu.curve.jcurve import G2J

    msm, add, proj = (g2_msm, g2_add, _proj_g2) if curve is G2J else (g1_msm, g1_add, _proj_g1)
    assert len(bases) == len(planes) == len(kw["lanes"])
    sums = []
    for b in range(planes[0][0].shape[0]):
        acc = None
        for cls, (mags, negs) in zip(bases, planes):
            live = [(p, k % R) for p, k in zip(host_points(curve, cls), scalars_of(mags[b], negs[b])) if p is not None]
            part = msm([p for p, _ in live], [k for _, k in live]) if live else None
            acc = part if acc is None else (acc if part is None else add(acc, part))
        sums.append(acc)
    return proj(sums)


@pytest.mark.parametrize("classed", [True, False], ids=["classed", "no-widths"])
@pytest.mark.parametrize("b,s", MESHES)
def test_the_placed_key_is_the_padded_key_in_shards(toy, b, s, classed):
    """(a) Each query of the placed key is a (narrow, wide) pair of
    classes, (x, y, wire) each: the shards of a class, in the order of
    the mesh's "shard" axis, concatenate to the bases the key's own
    selection names, padded with infinity bases to whole steps of every
    shard, with the wire of every base beside it in the same shards
    (filler: wire 0); each base of the key is in exactly one class, once;
    a, b1 and c are padded to one count a class (they share a program)
    and b2 keeps its own; a key without widths has every base wide; h
    and the QAP rows as before; the key handed in is left as it was."""
    from zkp2p_tpu.prover import groth16_tpu as G

    _cs, _pk, dpk, _wits = toy
    dpk = dpk if classed else without_widths(dpk)
    mesh = _mesh(b, s)
    placed = G.place_key(dpk, mesh)
    assert G.key_mesh(placed) == mesh and G.key_mesh(dpk) is None and G.key_device(placed) is None

    def whole(arr, n_to):  # a sharded array read back shard by shard, one of each shard's `b` replicas
        shards = sorted(arr.addressable_shards, key=lambda sh: (sh.index[0].start or 0, sh.device.id))
        assert len(shards) == b * s and all(sh.data.shape[0] == n_to // s for sh in shards)
        return np.concatenate([np.asarray(sh.data) for sh in shards[::b]])

    want = key_classes(dpk)
    assert classed == bool(len(want["a"][0][0]))
    for q in QUERIES:
        pair, unplaced = getattr(placed, q + "_bases"), getattr(dpk, q + "_bases")
        assert len(pair) == 2 and all(len(cls) == 3 for cls in pair)
        seen = np.concatenate([sel for sel, _ in want[q]])
        assert sorted(seen) == list(range(unplaced[0].shape[0]))  # each base once
        for k, (cls, (sel, wires)) in enumerate(zip(pair, want[q])):
            n, n_to = len(sel), cls[0].shape[0]
            most = n if q == "b2" else max(len(want[g][k][0]) for g in ("a", "b1", "c"))
            lanes = G.pod_lanes(most, s) if k else G.pod_narrow_lanes(most, s, cap=4096 if q == "b2" else 16384)
            assert n_to == (most + (-most) % (s * lanes) if most else 0)
            assert (G.pod_lanes(n_to, s) if k else G.pod_narrow_lanes(n_to, s, cap=4096 if q == "b2" else 16384)) == lanes
            for got, src in zip(cls[:2], unplaced):
                got = whole(got, n_to)
                assert (got[:n] == np.asarray(src)[sel]).all() and not got[n:].any()  # (0, 0): infinity
            got = whole(cls[2], n_to)
            assert (got[:n] == wires).all() and not got[n:].any()
            for part in cls[1:]:  # x, y and the wires in the same shards
                for a_sh, b_sh in zip(cls[0].addressable_shards, part.addressable_shards):
                    assert a_sh.index[0] == b_sh.index[0] and a_sh.device == b_sh.device
        if not classed:
            assert pair[0][0].shape[0] == 0
    assert [cls[0].shape[0] for cls in placed.a_bases] == [cls[0].shape[0] for cls in placed.b1_bases] == [
        cls[0].shape[0] for cls in placed.c_bases]
    for got, src in zip(placed.h_bases, dpk.h_bases):
        n, n_to = src.shape[0], got.shape[0]
        assert n_to == n + (-n) % (s * G.pod_lanes(n, s))
        got = whole(got, n_to)
        assert (got[:n] == np.asarray(src)).all() and not got[n:].any()
    for f in G._QAP_ROWS:
        got = getattr(placed, f)
        assert len(got.addressable_shards) == b * s
        assert all((np.asarray(sh.data) == np.asarray(getattr(dpk, f))).all() for sh in got.addressable_shards)
    for f in ("b_sel", "c_sel", "a_nsel", "a_wsel", "b_nsel", "b_wsel", "c_nsel", "c_wsel"):
        assert not getattr(placed, f).shape[0]  # the classes hold what they said
    assert int(dpk.a_wsel.shape[0]) and dpk.a_bases[0].ndim == 2  # the key handed in: as it was


@pytest.mark.parametrize("b,s,n_proofs", [(1, 4, 4), (2, 2, 4), (4, 1, 4), (1, 4, 3), (1, 4, 1), (2, 2, 2), (1, 2, 3)])
def test_after_the_exchange_each_chip_holds_the_columns_of_its_bases(toy, b, s, n_proofs):
    """(b) For every proof of its group a chip holds exactly the columns
    of the unsharded SIGNED digit planes that belong to the bases it
    holds, class by class: the low three planes of its narrow bases'
    wires, all 64 of its wide bases' wires (each query's, through the
    wire ids beside the bases) and of its h columns, whether the group's
    proofs were split over its chips or, where the chips do not divide
    them, each proof's h stage was shared by them (h then arrives in
    those columns already)."""
    from zkp2p_tpu.prover import groth16_tpu as G

    _cs, _pk, dpk, wits = toy
    mesh = _mesh(b, s)
    limbs = np.stack([G._witness_std_limbs(w) for w in wits[:n_proofs]])
    m = 1 << dpk.log_m
    h_std = np.random.default_rng(7).integers(0, 1 << 16, (n_proofs, m, 16), dtype=np.uint32)  # any scalars do
    h_std[..., 15] &= 0x2FFF  # below 2^254, as an Fr scalar is: the top signed digit absorbs its carry
    placed, w_planes, h_planes = _exchanged(dpk, mesh, limbs, h_std)
    assert G._pod_split(mesh, n_proofs) == ((n_proofs // b) % s == 0)
    digits = signed_digits(limbs)
    checks = [(h_planes, placed.h_bases, np.pad(signed_digits(h_std), [(0, 0), (0, 0), (0, placed.h_bases[0].shape[0] - m)]), 64)]
    for pair, planes in zip((getattr(placed, q + "_bases") for q in QUERIES), w_planes):
        for k, (cls, got) in enumerate(zip(pair, planes)):
            want = digits[:, :, np.asarray(cls[2])]  # past the key's own, the filler names wire 0 against an infinity base
            if not k:
                assert not want[:, :-G.NARROW_PLANES].any()  # a narrow wire's upper planes: provably zero
            checks.append((got, cls, want, 64 if k else G.NARROW_PLANES))
    for (mags, negs), bases, want, n_planes in checks:
        n_to = bases[0].shape[0]
        assert mags.shape == negs.shape == (n_proofs, n_planes, n_to) and negs.dtype == bool
        if not n_to:
            continue  # a class the key has no base in (the toy's b has no narrow wire): nothing to hold
        by_device = {sh.device: sh.index[0] for sh in bases[0].addressable_shards}
        for m_sh, n_sh in zip(mags.addressable_shards, negs.addressable_shards):
            assert m_sh.index == n_sh.index and m_sh.device == n_sh.device
            assert m_sh.index[2] == by_device[m_sh.device]  # the columns of the bases this chip holds
            assert m_sh.data.shape == (n_proofs // b, n_planes, n_to // s)  # for every proof of its group, and no more
            mag, neg = np.asarray(m_sh.data).astype(np.int64), np.asarray(n_sh.data)
            assert mag.max(initial=0) <= 8  # half a window: the table a step holds 8 multiples
            assert (np.where(neg, -mag, mag) == want[:, -n_planes:][m_sh.index]).all()


@pytest.mark.parametrize("b,s", [(1, 4), (2, 2)])
def test_the_chips_msm_partials_fold_to_the_whole_msm(toy, b, s):
    """(c) The share sums to the whole: each chip's MSM over the bases
    it holds, in both classes, and the signed digits the exchange left
    it, computed on the host from the placed arrays' shards, group-added
    over the classes and over the "shard" axis, is the MSM of the whole
    query over the witness — the allreduce's claim (`msm_pod_batched`:
    a chip's classes summed, all_gather + projective fold), without a
    program of the curve."""
    from zkp2p_tpu.curve.host import g1_add, g1_msm
    from zkp2p_tpu.curve.jcurve import G1J
    from zkp2p_tpu.prover import groth16_tpu as G

    _cs, pk, dpk, wits = toy
    mesh = _mesh(b, s)
    limbs = np.stack([G._witness_std_limbs(w) for w in wits])
    m = 1 << dpk.log_m
    rng = np.random.default_rng(11)
    h_scalars = [[int(v) for v in rng.integers(1, 1 << 62, m)] for _ in wits]
    h_std = np.stack([G._witness_std_limbs(row) for row in h_scalars])
    placed, w_planes, h_planes = _exchanged(dpk, mesh, limbs, h_std)
    b_sel, c_sel = np.asarray(dpk.b_sel), np.asarray(dpk.c_sel)
    queries = (
        (list(pk.a_query), [[int(v) % R for v in w] for w in wits], placed.a_bases, w_planes[0]),
        ([pk.b1_query[i] for i in b_sel], [[int(w[i]) % R for i in b_sel] for w in wits], placed.b1_bases, w_planes[1]),
        ([pk.c_query[i] for i in c_sel], [[int(w[i]) % R for i in c_sel] for w in wits], placed.c_bases, w_planes[3]),
        (list(pk.h_query) + [None] * (m - len(pk.h_query)), h_scalars, ((placed.h_bases),), (h_planes,)),
    )
    for points, scalars, classes, planes in queries:
        for proof, row in enumerate(scalars):
            live = [(p, k) for p, k in zip(points, row) if p is not None]
            whole = g1_msm([p for p, _ in live], [k for _, k in live])
            folded, seen = None, 0
            for cls, (mags, negs) in zip(classes, planes):
                pts = {sh.index[0].start or 0: host_points(G1J, (sh.data, y.data))
                       for sh, y in zip(cls[0].addressable_shards, cls[1].addressable_shards)}
                for m_sh, n_sh in zip(mags.addressable_shards, negs.addressable_shards):
                    if not (m_sh.index[0].start or 0) <= proof < (m_sh.index[0].stop or len(wits)):
                        continue  # another group's chip
                    local = proof - (m_sh.index[0].start or 0)
                    ks = scalars_of(np.asarray(m_sh.data)[local], np.asarray(n_sh.data)[local])
                    held = [(p, k % R) for p, k in zip(pts[m_sh.index[2].start or 0], ks) if p is not None]
                    part = g1_msm([p for p, _ in held], [k for _, k in held]) if held else None
                    folded = part if folded is None else (folded if part is None else g1_add(folded, part))
                    seen += 1
            assert seen == s * len(classes) and folded == whole


# --- the road's accumulators beside the one-chip road's, over a stand-in for the curve -----------------------------
# A group that is cheap to compile where the real programs of the curve take minutes on XLA:CPU: the integers mod
# P_LIN under addition, an element carried as the Y of a point.  Affine (x, y) stands for y, the (0, 0) filler for 0;
# a negated point is (x, -y), as on the curve; the MSMs, the recode, the classes, the lanes, the shards and the fold
# are the program's own.  A resident table's words hold it too: an element is under 2^16, one limb of x beside one
# of y, and the field under the coordinates (the build divides by Z, here 1) is the integers mod P_LIN.
P_LIN = 65521


class _LinField:
    def __init__(self, elem):
        import jax.numpy as jnp

        self.zero_limbs = jnp.zeros(elem, jnp.uint32)
        self.one_mont = jnp.ones(elem, jnp.uint32)

    def neg(self, y):
        return (P_LIN - y) % P_LIN

    def mul(self, a, b):
        return a * b % P_LIN

    def inv_fused(self, a):
        import jax.numpy as jnp

        return jnp.ones_like(a)  # of a product of Z's, each 1

    def is_zero(self, a):
        return (a == 0).all(axis=-1)

    def select(self, cond, a, b):
        import jax.numpy as jnp

        return jnp.where(cond[..., None], a, b)


class _LinCurve:
    def __init__(self, elem):
        self.F, self.elem = _LinField(elem), elem

    def infinity(self, batch_shape=()):
        import jax.numpy as jnp

        z = jnp.zeros(tuple(batch_shape) + self.elem, jnp.uint32)
        return (z, z, z)

    def from_affine(self, a):
        return (a[0], a[1], (a[1] != 0).astype(a[1].dtype))

    def add(self, p, q):
        return (p[0] | q[0], (p[1] + q[1]) % P_LIN, p[2] | q[2])

    def add_mixed(self, p, a):
        return self.add(p, self.from_affine(a))

    def double(self, p):
        return self.add(p, p)


def _linear_world(monkeypatch, classed, seed, h_window=None):
    """A synthetic key over the stand-in group, a fake h stage the two
    roads share (h = the first m wires: any function of the witness
    does), fresh programs on both roads, and what each MSM must sum to.
    `h_window`: the window both roads' rule answers for the resident h
    table (None: the multiples in the scan)."""
    import jax
    import jax.numpy as jnp

    from zkp2p_tpu.prover import groth16_tpu as G

    rng = np.random.default_rng(seed)
    n_wires, log_m = 41, 3
    g1, g2 = _LinCurve((1,)), _LinCurve((2, 1))
    monkeypatch.setattr(G, "G1J", g1)
    monkeypatch.setattr(G, "G2J", g2)
    monkeypatch.setattr(G, "h_evals", lambda dpk, w_mont: w_mont[: 1 << log_m])
    monkeypatch.setattr(G, "_h_table_window", lambda log_m, device=None, mesh=None: h_window)
    for name, fn in (("_jit_msm_g1", G._msm_g1), ("_jit_msm_g2", G._msm_g2), ("_jit_msm_g1_narrow", G._msm_g1_narrow),
                     ("_jit_msm_g2_narrow", G._msm_g2_narrow), ("_jit_msm_h_resident", G._msm_h_resident)):
        monkeypatch.setattr(G, name, jax.jit(jax.vmap(fn, in_axes=(None, 0))))  # traced here, over the stand-in
    monkeypatch.setattr(G, "_jit_h_table", jax.jit(G._h_table_fn, static_argnames="window"))
    monkeypatch.setattr(G, "_jit_h_planes", jax.jit(jax.vmap(G._h_and_planes, in_axes=(None, 0, None)), static_argnums=2))
    G._h_pod_fn.cache_clear()

    def fake_h_shard(mesh, log_m_, most):
        return lambda rows, starts, w_std: (w_std[:, : 1 << log_m_], None)

    monkeypatch.setattr(G, "_h_shard_fn", fake_h_shard)
    widths = np.where(rng.random(n_wires) < 0.7, rng.integers(1, G.NARROW_WIDTH + 1, n_wires), 254).astype(np.int32)
    widths[0] = 1
    b_sel = np.sort(rng.choice(n_wires, 17, replace=False)).astype(np.int32)
    c_sel = np.sort(rng.choice(np.arange(2, n_wires), 29, replace=False)).astype(np.int32)

    def pts(n, elem):
        y = rng.integers(1, P_LIN, (n,) + elem, dtype=np.uint32)
        y[rng.random(n) < 0.1] = 0  # holes, as a pruned query keeps none and c_query's public wires are
        return jnp.asarray(np.zeros_like(y)), jnp.asarray(y)

    sels = {}
    for q, ids in (("a", np.arange(n_wires, dtype=np.int32)), ("b", b_sel), ("c", c_sel)):
        n, w = G.class_sels(widths if classed else None, ids)
        sels[q + "_nsel"], sels[q + "_wsel"] = jnp.asarray(n), jnp.asarray(w)
    z = jnp.zeros((1, 16), jnp.uint32)
    dpk = G.DeviceProvingKey(
        n_public=1, n_wires=n_wires, log_m=log_m, a_coeff=z, a_wire=jnp.zeros((1,), jnp.int32), a_row=jnp.zeros((1,), jnp.int32),
        b_coeff=z, b_wire=jnp.zeros((1,), jnp.int32), b_row=jnp.zeros((1,), jnp.int32),
        a_bases=pts(n_wires, (1,)), b1_bases=pts(len(b_sel), (1,)), b2_bases=pts(len(b_sel), (2, 1)), c_bases=pts(len(c_sel), (1,)),
        h_bases=pts(1 << log_m, (1,)), b_sel=jnp.asarray(b_sel), c_sel=jnp.asarray(c_sel), **sels,
        alpha_1=None, beta_1=None, beta_2=None, delta_1=None, delta_2=None)

    def witness():
        return [int(rng.integers(0, 1 << min(int(w), 11))) if w <= G.NARROW_WIDTH else int(rng.integers(0, 1 << 62)) ** 4 % R
                for w in widths]

    def sums(wit):  # what each of the five MSMs is, in the group: sum of scalar x point
        def dot(ys, ks):
            ys = np.asarray(ys).reshape(len(ks), -1)[:, 0]  # a G2 stand-in's two components ride together: the first
            return sum(int(k) * int(y) for k, y in zip(ks, ys)) % P_LIN
        b_ks, c_ks = [wit[i] for i in b_sel], [wit[i] for i in c_sel]
        return (dot(dpk.a_bases[1], wit), dot(dpk.b1_bases[1], b_ks), dot(dpk.b2_bases[1], b_ks), dot(dpk.c_bases[1], c_ks),
                dot(dpk.h_bases[1], wit[: 1 << log_m]))

    return dpk, witness, sums


@pytest.mark.parametrize("h_window", [None, 4, 8], ids=["h-scan", "h-table4", "h-table8"])
@pytest.mark.parametrize("classed", [True, False], ids=["classed", "no-widths"])
@pytest.mark.parametrize("b,s,n_proofs", [(1, 4, 4), (1, 4, 1), (2, 2, 4), (2, 2, 2)],
                         ids=["1x4-split", "1x4-shared", "2x2-split", "2x2-shared"])
def test_the_mesh_road_s_accumulators_are_the_one_chip_road_s(monkeypatch, b, s, n_proofs, classed, h_window):
    """`_prove_batch_sharded` beside `_prove_device` on the same key and
    witnesses, the curve stood in for by a group that compiles in
    seconds: the five accumulators are equal element for element, and
    are the sums of scalar x point — for a key with a narrow class and
    for one without, on 1x4 and 2x2, the chunk split over a group's
    chips (a chunk of four) and shared by them (a batch of one on 1x4, a
    proof a group on 2x2: the step widths a chunk of one takes), the h
    MSM with its multiples in the scan and against a resident table at
    either window: on the mesh each chip's table of its own shard of
    the h bases (`resident_table_pod`), h exchanged at the table's
    window, one build a placed key."""
    import jax.numpy as jnp

    from zkp2p_tpu.field.jfield import FR
    from zkp2p_tpu.prover import groth16_tpu as G

    dpk, witness, sums = _linear_world(monkeypatch, classed, seed=31 + n_proofs, h_window=h_window)
    try:
        mesh = _mesh(b, s)
        wits = [witness() for _ in range(n_proofs)]
        limbs = np.stack([G._witness_std_limbs(w) for w in wits])
        assert G._pod_split(mesh, n_proofs) == (n_proofs == 4)
        placed = G.place_key(dpk, mesh)
        assert bool(placed.a_bases[0][0].shape[0]) == classed
        on_mesh = G._prove_batch_sharded(placed, limbs, mesh)
        table = getattr(placed, "_h_table_cache", None)
        if h_window is None:
            assert table is None
        else:  # a chip its own shard's multiples, 2^(w-1) a base, in steps: sharded as the bases are
            n_h = placed.h_bases[0].shape[0]
            lanes = G.pod_table_lanes(n_h, s)
            assert table.shape == (n_h // lanes, 1 << (h_window - 1), lanes, 1)
            by_device = {sh.device: sh.index[0] for sh in placed.h_bases[0].addressable_shards}
            for sh in table.addressable_shards:
                rows = by_device[sh.device]
                assert (sh.index[0].start * lanes, sh.index[0].stop * lanes) == (rows.start or 0, rows.stop or n_h)
            G._prove_batch_sharded(placed, limbs, mesh)
            assert placed._h_table_cache is table  # memoised on the placed key
        one_chip = G._prove_device(dpk, FR.to_mont(jnp.asarray(limbs)))
        for name, got, want, elem in zip(G.STAGES[1:], on_mesh, one_chip, ((1,), (1,), (2, 1), (1,), (1,))):
            assert got[1].shape == want[1].shape == (n_proofs,) + elem, name
            assert (np.asarray(got[1]) == np.asarray(want[1])).all(), name
        for i, wit in enumerate(wits):
            assert tuple(int(np.asarray(acc[1][i]).ravel()[0]) for acc in on_mesh) == sums(wit)
    finally:
        G._h_pod_fn.cache_clear()  # traced over the fake h stage: not for the next test of this process
