"""Device-mesh parallelism: batched proving with sharded MSMs over ICI.

The reference's only parallelism is artifact chunking + rapidsnark's
shared-memory threads (SURVEY.md §2.7); the TPU build gets real
distributed axes:

  - "batch": data parallelism over independent proofs (vmap + sharding),
    the batched-onramp configuration of BASELINE.json.
  - "shard": model parallelism over the MSM base-point axis — each device
    accumulates bucket/plane partial sums for its slice of the zkey, and
    ONE group-operation all-reduce (all_gather + local projective fold)
    combines them over ICI.  This is the Pippenger partial-sum allreduce
    of SURVEY.md §2.7 expressed with XLA collectives instead of NCCL.

Everything is `shard_map` over a `jax.sharding.Mesh`, so the same program
runs on 1 chip, a v5e-8 slice, or (with a "dcn" outer axis) multi-host —
the driver's `dryrun_multichip` exercises it on virtual CPU devices.
"""

from __future__ import annotations

from functools import lru_cache
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..curve.jcurve import AffPoint, ProjPoint, JCurve
from ..ops.msm import horner_fold_planes, msm_plane_sums, resident_plane_sums, resident_table


def make_mesh(n_devices: Optional[int] = None, axis: str = "shard") -> Mesh:
    devs = jax.devices()[: n_devices or len(jax.devices())]
    return Mesh(np.array(devs), (axis,))


def make_pod_mesh(n_dcn: int, n_ici: Optional[int] = None, names=("dcn", "shard")) -> Mesh:
    """Multi-slice mesh for pod-scale configs (the v5e-256 shape of
    BASELINE.json): the outer `dcn` axis spans slices (data-center
    network — carry only the proof-batch data parallelism there, one
    all-gather of proof points per batch), the inner axis rides ICI and
    carries the MSM base-axis sharding (`msm_pod_batched`).  On a single
    host this builds the same layout over virtual devices, which is how
    the driver's dryrun and the tests exercise it."""
    devs = jax.devices()
    if n_ici is None:
        n_ici = len(devs) // n_dcn
    if n_ici < 1 or n_dcn * n_ici > len(devs):
        raise ValueError(f"need {n_dcn}x{n_ici or '?'} devices, have {len(devs)}")
    return Mesh(np.array(devs[: n_dcn * n_ici]).reshape(n_dcn, n_ici), names)


@lru_cache(maxsize=None)
def _msm_pod_fn(curve: JCurve, mesh: Mesh, dcn_axis: str, ici_axis: str, lanes: Tuple[int, ...], window: int):
    def local(bases, planes):
        # one entry a class: this chip's shard of the class's bases, and
        # (mags, negs), each (B_local, n_planes, n_local): this slice's
        # share of the proof batch over those bases
        part = None
        for bs, (mags, negs), width in zip(bases, planes, lanes):
            sums = jax.vmap(lambda m, n: msm_plane_sums(curve, bs, m, n, lanes=width, window=window))(mags, negs)
            # Horner over the planes for the whole batch at once: its kernels have the
            # shape of the fold below, whatever the class
            acc = horner_fold_planes(
                curve, curve.infinity(mags.shape[:1]), tuple(jnp.moveaxis(c, 1, 0) for c in sums), window)
            part = acc if part is None else curve.add(part, acc)
        return _allreduce(curve, mesh, part, dcn_axis, ici_axis)

    # a spec is a prefix of its argument's tree: every coordinate of every
    # class's bases, every class's mags and negs
    in_specs = (P(ici_axis), P(dcn_axis, None, ici_axis))
    return jax.jit(jax.shard_map(local, mesh=mesh, in_specs=in_specs, out_specs=P(), check_vma=False))


def _allreduce(curve: JCurve, mesh: Mesh, part: ProjPoint, dcn_axis: str, ici_axis: str) -> ProjPoint:
    """A chip's (B_local,) partials -> the (B,) sums, on every chip."""
    # ICI allreduce within the slice: combine base-axis partials
    gathered = jax.lax.all_gather(part, ici_axis, axis=1)
    acc = _fold_gathered_batched(curve, gathered, mesh.shape[ici_axis])
    # DCN all-gather across slices: assemble the full proof batch
    # (one point per proof — the only cross-slice traffic, matching
    # the make_pod_mesh contract of data-parallel-only over dcn)
    return tuple(jax.lax.all_gather(c, dcn_axis, axis=0, tiled=True) for c in acc)


def _fold_gathered_batched(curve: JCurve, gathered: ProjPoint, n: int) -> ProjPoint:
    """Fold per-device partials with a batch axis: gathered components
    are (B_local, n_dev, ...); scan over the device axis."""

    def body(acc, p):
        return curve.add(acc, p), None

    moved = tuple(jnp.moveaxis(c, 1, 0) for c in gathered)
    acc, _ = jax.lax.scan(body, curve.infinity((moved[0].shape[1],)), moved)
    return acc


def msm_pod_batched(
    curve: JCurve,
    bases: Sequence[AffPoint],
    planes: Sequence[Tuple[jnp.ndarray, jnp.ndarray]],
    mesh: Mesh,
    dcn_axis: str = "dcn",
    ici_axis: str = "shard",
    lanes: Sequence[int] = (64,),
    window: int = 4,
) -> ProjPoint:
    """Batched MSM over a pod mesh (`make_pod_mesh`): the proof batch is
    data-parallel over the `dcn` axis (each slice proves its share of
    the batch) while each slice shards the base-point axis over its ICI
    `shard` axis — the v5e-256 configuration of BASELINE.json, with the
    only DCN traffic being one proof point per batch element.

    A shard's MSM is the one-chip road's on signed digits
    (`msm_windowed_signed`'s table and accumulate, `ops.msm.msm_plane_sums`;
    the lanes folded before the planes, so that every class's Horner
    fold and the allreduce's fold are one pair of kernels to lower), and
    the query comes in classes, one entry of
    `bases`, `planes` and `lanes` each (the key's narrow class at its few
    low planes and wide steps beside the wide one at all of them;
    `prover.groth16_tpu.place_key`): a class's `planes` are `(mags,
    negs)`, each (B, n_planes, N), B divisible by the dcn width, N by
    the ici width (a placed key's classes are padded to it once, and the
    prover hands everything over already laid out as the program's
    `in_specs` want it, so nothing is resharded).  Each chip sums its
    classes' partials before the one all_gather + fold.  Returns
    (B,)-batched projective points, replicated everywhere."""
    assert len(bases) == len(planes) == len(lanes) > 0
    for bs, (mags, _negs) in zip(bases, planes):
        assert mags.shape[0] % mesh.shape[dcn_axis] == 0, "batch must divide the dcn axis"
        assert bs[0].shape[0] % mesh.shape[ici_axis] == 0, "pad the base axis first"
    return _msm_pod_fn(curve, mesh, dcn_axis, ici_axis, tuple(lanes), window)(tuple(bases), tuple(planes))


# The h MSM of a key placed on the mesh: the bases are the key's, so each
# chip keeps the window multiples of the shard it holds (`ops.msm`'s
# resident table, one a chip: no base and no entry crosses ICI) and a
# shard's MSM is `msm_resident`'s accumulate against it.


@lru_cache(maxsize=None)
def _resident_table_pod_fn(curve: JCurve, mesh: Mesh, ici_axis: str, window: int, lanes: int):
    def local(bases):
        return resident_table(curve, bases, window, lanes)

    return jax.jit(jax.shard_map(local, mesh=mesh, in_specs=(P(ici_axis),), out_specs=P(ici_axis), check_vma=False))


def resident_table_pod(curve: JCurve, bases: AffPoint, mesh: Mesh, window: int, lanes: int, ici_axis: str = "shard") -> jnp.ndarray:
    """`resident_table` of each chip's shard of `bases` (sharded over
    `ici_axis`, N divisible by its width), built where the shard lies:
    the layout of `ops.msm`, (steps, 2^(w-1), lanes, 16), sharded on
    `steps`, a chip's share of the bases padded to whole steps of
    `lanes`."""
    assert bases[0].shape[0] % mesh.shape[ici_axis] == 0, "pad the base axis first"
    return _resident_table_pod_fn(curve, mesh, ici_axis, window, lanes)(tuple(bases))


@lru_cache(maxsize=None)
def _msm_pod_resident_fn(curve: JCurve, mesh: Mesh, dcn_axis: str, ici_axis: str):
    def local(table, mags, negs):
        # this chip's table, unbatched under the proofs of its slice's share
        sums = jax.vmap(lambda m, n: resident_plane_sums(curve, table, m, n))(mags, negs)
        part = horner_fold_planes(
            curve, curve.infinity(mags.shape[:1]), tuple(jnp.moveaxis(c, 1, 0) for c in sums), int(table.shape[1]).bit_length())
        return _allreduce(curve, mesh, part, dcn_axis, ici_axis)

    planes = P(dcn_axis, None, ici_axis)
    return jax.jit(jax.shard_map(local, mesh=mesh, in_specs=(P(ici_axis), planes, planes), out_specs=P(), check_vma=False))


def msm_pod_resident(
    curve: JCurve,
    table: jnp.ndarray,
    planes: Tuple[jnp.ndarray, jnp.ndarray],
    mesh: Mesh,
    dcn_axis: str = "dcn",
    ici_axis: str = "shard",
) -> ProjPoint:
    """`msm_pod_batched` over the one class of bases `table` was built
    from (`resident_table_pod`), at the table's window: `planes` are
    `(mags, negs)`, each (B, 256 / window, N), laid out as there.  A
    shard's MSM is `msm_resident`'s accumulate (a select and one
    `add_mixed` a plane a step; `ops.msm.resident_plane_sums`, the lanes
    folded before the planes as `msm_pod_batched` folds them), vmapped
    over the slice's proofs with the table unbatched; then the same ONE
    all_gather + fold.  Returns (B,)-batched projective points,
    replicated everywhere."""
    mags, negs = planes
    assert mags.shape[0] % mesh.shape[dcn_axis] == 0, "batch must divide the dcn axis"
    assert mags.shape[2] % mesh.shape[ici_axis] == 0, "pad the base axis first"
    return _msm_pod_resident_fn(curve, mesh, dcn_axis, ici_axis)(table, mags, negs)
