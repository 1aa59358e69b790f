"""The TPU Groth16 prover: witness limbs in, proof points out.

This is the `prover=tpu` backend the build exists for (BASELINE.json
north star) — the drop-in for snarkjs `groth16 prove` /
rapidsnark (`dizkus-scripts/5_gen_proof.sh`, `6_gen_proof_rapidsnark.sh`):
same zkey material + witness in, same proof out, verified by the same
pairing equation (`contracts/Verifier.sol:340-380`).

Dataflow (six stage programs a chunk of witnesses, SURVEY.md §7 step 6):

  witness w (mont limbs, n_wires x 16)
    ├─ Az/Bz/Cz: gather coeffs -> Montgomery mul -> modular segment-sum
    │  over rows (the sparse matvec; zero scatter)
    ├─ H: iNTT -> coset shift -> NTT -> (a·b - c)·Z⁻¹ -> iNTT -> unshift
    └─ 4 G1 MSMs + 1 G2 MSM over signed digit planes (ops.msm)
  host: the ~10 scalar ops that blind with (r, s) and assemble (A, B, C)

Two roads, both behind `prove_tpu_batch` (`prove_tpu` is a batch of
one): `_prove_device` on one chip, and `_prove_batch_sharded` on a
("batch", "shard") pod mesh where ZKP2P_TPU_SHARD=on: the key placed on
the mesh once (`place_key`), the h stage a chip its own proofs, an
exchange, then the MSMs over each chip's share of the bases (seven
stage programs a chunk).

Determinism contract: given the same (witness, r, s) this emits the exact
proof `snark.groth16.prove_host` does — the two provers are diffed
point-by-point in tests, the same way the reference pins a known-good
proof vector in `test/ramp.test.js:193-196`.

Batching: `prove_tpu_batch` vmaps the whole pipeline over independent
witnesses sharing one key — the reference has no analog (browser proves
one email at a time); this is the TPU data-parallel axis.
"""

from __future__ import annotations

import contextlib
import queue
import secrets
import threading
import time
from dataclasses import dataclass
from functools import lru_cache
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..curve.host import G1Point, G2Point
from ..curve.jcurve import (
    ADD_LAW,
    AffPoint,
    G1J,
    G2J,
    g1_jac_to_host,
    g1_to_affine_arrays,
    g2_jac_to_host,
    g2_to_affine_arrays,
)
from ..field.bn254 import R
from ..field.jfield import FR, lazy_segment_sum_mod
from ..ops.msm import (
    RESIDENT_ENTRY_BYTES,
    default_lanes,
    msm_resident,
    msm_windowed_signed,
    resident_table,
    signed_digit_planes_from_limbs,
)
from ..ops.ntt import LADDER as NTT_LADDER, coset_shift, intt, ntt
from ..snark import native_assemble
from ..snark.groth16 import Proof, ProvingKey, coset_gen, domain_size_for, qap_rows
from ..snark.r1cs import ConstraintSystem
from ..snark.witness_check import rows_of, unreduced_rows
from ..utils.audit import record_arm as _record_arm
from ..utils.config import load_config as _load_config
from ..utils.jaxcfg import on_tpu as _on_tpu

# The one knob of this module that is a module constant: the config's
# import-time snapshot (utils.config: default -> env, with provenance).
BATCH_CHUNK = _load_config().batch_chunk
# The witness MSMs' digits: signed 4-bit windows, ~72 point-adds a base
# against the 256 of the bit-plane formulation, an 8-entry multiples
# table a scan step (a negative digit is (x, -y) for free), on the mesh
# road as on one chip.
MSM_WINDOW = 4

# The h MSM's window multiples live in a table resident with the key
# (ops.msm.resident_table) where the device can hold it: h_bases depend
# on the key alone, so the table is built once and the window is a
# function of what the key's size lets the chip hold — w=8 (32 planes,
# 128 multiples a base), else w=4 (64 planes, 8 multiples), else the
# in-scan table (`_msm_g1`; on a mesh `msm_pod_batched`).  On a mesh a
# chip keeps the table of the S-th of the bases it holds.  The batch
# chunk follows the same two things: as many proofs at a time, up to
# four, as the device holds beside the key.  Both rules plan the fullest
# chip's `chip_bytes_a_point` under HBM_PLAN_FRACTION of its memory.
# KEY_BYTES_A_POINT and PROOF_BYTES_A_POINT were calibrated on the
# ledger's peak_hbm_bytes (PERF_LEDGER.jsonl, PR 24, a chunk of four,
# before any table): 3,088,086,016 at 2^19 with a key of 459,873,288 is
# 877 B a point of key and 1,253 a proof; 459,698,176 at 2^16 is 7,014 B
# a point for key and four proofs.  1 KiB and 1.5 KiB are the larger
# readings rounded up, and give the 7 KiB a point that the window rule
# planned at a chunk of four since PR 25.  Checked against the 2^22 run
# (PERF.md, PR 26).  The fifth left free is the allocator's fragmentation
# and the table build's own temporaries (2.5 GB at 2^19, w=8, which the
# batch's working set has not claimed yet when the table is built).
# Where the backend reports no memory_stats (XLA:CPU) the same rules run
# on NOMINAL_HBM_BYTES, one v5e chip's.
HBM_PLAN_FRACTION = 0.8
KEY_BYTES_A_POINT = 1 << 10
PROOF_BYTES_A_POINT = 3 << 9
BATCH_CHUNK_MAX = 4
NOMINAL_HBM_BYTES = 16 << 30
POD_TABLE_LANES = 256
# A key placed on a mesh (`place_key`): of KEY_BYTES_A_POINT the QAP rows,
# which every chip holds whole, are KEY_ROWS_BYTES_A_POINT (163 MB at 2^19:
# 311 B a point, rounded up); the bases, in S-ths, the rest.  A proof whose
# h stage is shared by the S chips of its group (`_h_shard_fn`) costs a
# chip an S-th of PROOF_BYTES_A_POINT and, whatever S, what it holds whole:
# the witness and the all_gathers of three vectors.  Calibrated on the
# program compiled for a described v5e:2x2 at 2^23 (PERF.md, PR 37:
# `memory_analysis` plans 6.92 GB of temporaries, 0.13 GB of h and a
# 0.31 GB witness a chip for one proof, 878 B a point, of which 384 are the
# quarter): 494, rounded up.
KEY_ROWS_BYTES_A_POINT = 3 << 7
SHARED_WHOLE_BYTES_A_POINT = 1 << 9


def work_bytes_a_point(chunk: int) -> int:
    """Device bytes a domain point that the key and `chunk` proofs in
    flight are planned to take on one chip."""
    return KEY_BYTES_A_POINT + chunk * PROOF_BYTES_A_POINT


class KeyDoesNotFit(ValueError):
    """No chunk of a key's proofs fits beside the key where it is placed."""


def chip_bytes_a_point(chunk: int, n_batch: int = 1, n_shard: int = 1) -> float:
    """Device bytes a domain point planned on the FULLEST chip of an
    `n_batch` x `n_shard` placement for the key and a chunk of `chunk`
    proofs: `work_bytes_a_point(chunk)` on one chip.  On a mesh
    (`place_key`) a chip holds the QAP rows whole and an S-th of the
    bases, and of its group's chunk / n_batch proofs either whole proofs
    (the chunk is split, `_pod_split`: a proof's h stage on one chip) or
    an S-th of each with a whole witness beside it (`_h_shard_fn`)."""
    a_group = chunk // n_batch
    if a_group % n_shard == 0:
        proofs = a_group // n_shard * PROOF_BYTES_A_POINT
    else:
        proofs = a_group * (PROOF_BYTES_A_POINT / n_shard + SHARED_WHOLE_BYTES_A_POINT)
    return KEY_ROWS_BYTES_A_POINT + (KEY_BYTES_A_POINT - KEY_ROWS_BYTES_A_POINT) / n_shard + proofs


def batch_chunk_for(log_m: int, bytes_limit: int, n_batch: int = 1, n_shard: int = 1) -> int:
    """Proofs a chunk of `prove_tpu_batch` for a key of 2^log_m domain
    points placed on `n_batch` x `n_shard` devices of `bytes_limit` each
    (one chip: 1 x 1): BATCH_CHUNK_MAX where the fullest chip's share
    (`chip_bytes_a_point`) fits under HBM_PLAN_FRACTION of it, half as
    many while it does not, never fewer than a proof a group; and where
    that does not fit either, `KeyDoesNotFit` with the bytes."""
    chunk = max(BATCH_CHUNK_MAX, n_batch)
    while True:
        planned = int(chip_bytes_a_point(chunk, n_batch, n_shard) * (1 << log_m))
        if planned <= HBM_PLAN_FRACTION * bytes_limit:
            return chunk
        if chunk // 2 < n_batch or (chunk // 2) % n_batch:
            raise KeyDoesNotFit(
                f"a key of 2^{log_m} domain points fits no chunk on {n_batch}x{n_shard} devices of {bytes_limit} B: "
                f"a chunk of {chunk} plans {planned} B on the fullest chip, over {HBM_PLAN_FRACTION:g} of it "
                f"({int(HBM_PLAN_FRACTION * bytes_limit)} B); place the key on a mesh with more shards (ZKP2P_TPU_MESH)")
        chunk //= 2


def h_table_window(
    log_m: int, entry_bytes: int, bytes_limit: int, chunk: int = BATCH_CHUNK_MAX, n_batch: int = 1, n_shard: int = 1,
) -> Optional[int]:
    """The widest signed window whose multiples table (2^(w-1) entries
    of `entry_bytes` a base, 2^log_m bases, an `n_shard`-th of them a
    chip) fits a device of `bytes_limit` beside the fullest chip's share
    of the key and of a chunk of `chunk` proofs on an `n_batch` x
    `n_shard` placement (`chip_bytes_a_point`; one chip: 1 x 1, the
    whole of each); None: neither does."""
    for window in (8, 4):
        a_point = (entry_bytes << (window - 1)) / n_shard + chip_bytes_a_point(chunk, n_batch, n_shard)
        if a_point * (1 << log_m) <= HBM_PLAN_FRACTION * bytes_limit:
            return window
    return None


@lru_cache(maxsize=None)
def _hbm_bytes_limit(device=None) -> int:
    """The memory of the device a key lives on (`key_device`); without
    one, the process's first device."""
    stats = (device or jax.devices()[0]).memory_stats() or {}
    return int(stats.get("bytes_limit") or NOMINAL_HBM_BYTES)


def key_arrays_home(log_m: int):
    """Where a key of 2^log_m domain points lives as `load_dpk` and
    `setup_device` hand it over: `jnp.asarray` (the process's default
    device, as every key has) where one device can prove from it, else
    `np.asarray`, the host: a key no single chip holds beside one proof
    (`batch_chunk_for` raises: 2^23 on a v5e) can only be placed on a
    mesh, and `place_key` lays it there from the host a shard a chip,
    so chip 0 never holds the 4.6 GB whole beside its own share.
    `prove_native` reads either as it is."""
    try:
        batch_chunk_for(log_m, _hbm_bytes_limit())
    except KeyDoesNotFit:
        return np.asarray
    return jnp.asarray


def _h_table_window(log_m: int, device=None, mesh=None) -> Optional[int]:
    """The window at which a key of 2^log_m domain points on `device`,
    or placed on `mesh` (a chip's table: its shard's), keeps a resident h
    table; None: the table does not fit, and the h MSM builds its
    multiples in the scan (`_msm_g1`, `msm_pod_batched`).  Either road
    hands the window of the table it holds to the program that recodes h."""
    limit = _hbm_bytes_limit(device)
    shape = (1, 1) if mesh is None else (mesh.shape["batch"], mesh.shape["shard"])
    # off a TPU `auto` does not chunk (0): plan the chunk a TPU would take
    chunk = _batch_chunk_size(log_m, device, mesh) or batch_chunk_for(log_m, limit, *shape)
    return h_table_window(log_m, RESIDENT_ENTRY_BYTES, limit, chunk, *shape)


def _parse_mesh_spec(spec: str, n_devices: int) -> Optional[Tuple[int, int]]:
    """ZKP2P_TPU_MESH -> (batch_width, shard_width).  "BxS" gives B
    data-parallel batch groups of S base-axis shards; a bare int N is
    "1xN"; "" auto-sizes to 1x<all devices>.  Malformed or non-positive
    specs return None (the caller fails CLOSED to the vmap arm — the
    same malformed-knob rule as _batch_chunk_size)."""
    spec = (spec or "").strip().lower()
    if not spec:
        return (1, n_devices)
    try:
        if "x" in spec:
            b_s = spec.split("x", 1)
            b, s = int(b_s[0]), int(b_s[1])
        else:
            b, s = 1, int(spec)
    except ValueError:
        return None
    if b < 1 or s < 1:
        return None
    return (b, s)


# pod meshes memoised by shape: Mesh construction is cheap but the
# shard_map executable caches (parallel.mesh._msm_pod_fn) key on the
# Mesh instance — one instance per shape keeps them warm across proves.
_POD_MESH_CACHE: Dict[Tuple[int, int], object] = {}


def _shard_mesh():
    """The sharded-arm gate + mesh resolver (fresh config read per call,
    like the scheduler's sched_arm): ZKP2P_TPU_SHARD must be literally
    "on" — anything else fails CLOSED to the single-device vmap path —
    and ZKP2P_TPU_MESH shapes the ("batch", "shard") pod mesh.  Records
    the `tpu_shard` gate with the RESOLVED shape ("off" | "2x4"), so a
    sharded prove is digest-distinguishable from the vmap arm and an
    unsatisfiable mesh is an on-record disarm, never a silent one."""
    cfg = _load_config()
    if cfg.tpu_shard != "on":
        _record_arm("tpu_shard", "off")
        return None
    n_dev = len(jax.devices())
    shape = _parse_mesh_spec(cfg.tpu_mesh, n_dev)
    if shape is None or shape[0] * shape[1] > n_dev:
        _record_arm("tpu_shard", "off")
        return None
    b, s = shape
    mesh = _POD_MESH_CACHE.get((b, s))
    if mesh is None:
        from ..parallel.mesh import make_pod_mesh

        mesh = make_pod_mesh(b, s, names=("batch", "shard"))
        _POD_MESH_CACHE[(b, s)] = mesh
    _record_arm("tpu_shard", mesh_name(mesh))
    return mesh


@dataclass
class DeviceProvingKey:
    """Proving key resident as device arrays (the zkey, TPU-shaped)."""

    n_public: int
    n_wires: int
    log_m: int
    # Sparse QAP rows for A and B (including public binding rows):
    # canonical Montgomery coefficients, wire gather indices, row segment
    # ids.  No C matrix — C evaluations on the domain are A∘B pointwise
    # for a satisfying witness (binding rows have B = 0), the same reason
    # the snarkjs .zkey coefficient section stores only A and B.
    a_coeff: jnp.ndarray
    a_wire: jnp.ndarray
    a_row: jnp.ndarray
    b_coeff: jnp.ndarray
    b_wire: jnp.ndarray
    b_row: jnp.ndarray
    # MSM bases (affine Montgomery limbs; (0,0) = infinity hole).  The b
    # and c queries are PRUNED: only ~50% of wires appear in any B row
    # (and ~60% in C, measured on the venmo circuit), and an infinity
    # base contributes nothing for any witness — so b1/b2/c keep just the
    # non-infinity lanes plus the wire-index gather maps b_sel/c_sel.
    # The G2 MSM (3x the per-point cost of G1) halves outright.
    a_bases: AffPoint
    b1_bases: AffPoint
    b2_bases: AffPoint
    c_bases: AffPoint
    h_bases: AffPoint  # coset-Lagrange H basis, m lanes (zkey section 9)
    b_sel: jnp.ndarray  # wire indices backing b1/b2 lanes
    c_sel: jnp.ndarray  # wire indices backing c lanes
    # Width-classed MSM split (snark.r1cs wire_width: constraint-backed
    # value bounds — ~90% of venmo wires are SHA/DFA bits).  Positions
    # into each query's base array whose wire value is provably < 2^11
    # ("narrow": 3 signed w=4 digit planes suffice) vs the rest ("wide").
    # Empty narrow arrays (zkey import, width-free circuits) degrade to
    # the single-class path.
    a_nsel: jnp.ndarray
    a_wsel: jnp.ndarray
    b_nsel: jnp.ndarray
    b_wsel: jnp.ndarray
    c_nsel: jnp.ndarray
    c_wsel: jnp.ndarray
    # Host-side blinding points for final assembly.
    alpha_1: G1Point
    beta_1: G1Point
    beta_2: G2Point
    delta_1: G1Point
    delta_2: G2Point
    # Wires whose narrow classing came from zkey bit-pattern INFERENCE
    # (not ConstraintSystem width tags): packed int64 ids as bytes —
    # hashable, so it rides the pytree aux tuple and survives
    # flatten/unflatten (a rebuilt key keeps its prove-time width
    # guard).  None for cs-built keys.
    inferred_narrow_wires: Optional[bytes] = None


_DPK_ARRAY_FIELDS = (
    "a_coeff", "a_wire", "a_row", "b_coeff", "b_wire", "b_row",
    "a_bases", "b1_bases", "b2_bases", "c_bases", "h_bases",
    "b_sel", "c_sel",
    "a_nsel", "a_wsel", "b_nsel", "b_wsel", "c_nsel", "c_wsel",
)
_DPK_META_FIELDS = ("n_public", "n_wires", "log_m", "alpha_1", "beta_1", "beta_2", "delta_1", "delta_2", "inferred_narrow_wires")


def _dpk_flatten(d: "DeviceProvingKey"):
    return tuple(getattr(d, f) for f in _DPK_ARRAY_FIELDS), tuple(getattr(d, f) for f in _DPK_META_FIELDS)


def _dpk_unflatten(meta, children) -> "DeviceProvingKey":
    return DeviceProvingKey(**dict(zip(_DPK_ARRAY_FIELDS, children)), **dict(zip(_DPK_META_FIELDS, meta)))


jax.tree_util.register_pytree_node(DeviceProvingKey, _dpk_flatten, _dpk_unflatten)


def key_device(dpk: "DeviceProvingKey"):
    """The device a key is pinned to (`place_key`), where the prover
    then runs: a batch's inputs are put beside the key, every eager take
    and pad follows its operands, and the chunk and the h window are
    planned for that device's memory.  None for a key as `load_dpk`,
    `setup_device` and `device_pk` make it, pinned nowhere: it lives and
    proves on the process's default device, through the programs a
    one-chip service has always run (an argument pinned to a device is
    lowered with a sharding attribute, so pinned and unpinned keys do
    not share programs: PERF.md, PR 30).  None too for a key placed on
    a mesh (`key_mesh`)."""
    a = dpk.a_coeff
    return next(iter(a.devices())) if getattr(a, "committed", False) and key_mesh(dpk) is None else None


def key_mesh(dpk: "DeviceProvingKey"):
    """The pod mesh a key is placed on (`place_key(dpk, mesh)`): only
    `_prove_batch_sharded` on that mesh reads such a key.  None for
    every other key."""
    from jax.sharding import NamedSharding

    sharding = getattr(dpk.a_coeff, "sharding", None)
    return sharding.mesh if isinstance(sharding, NamedSharding) else None


def mesh_name(mesh) -> str:
    """A ("batch", "shard") pod mesh by its shape, "1x4": the `mesh`
    attribute of the mesh road's spans, and the `tpu_shard` gate's arm."""
    return f"{mesh.shape['batch']}x{mesh.shape['shard']}"


def pod_lanes(n: int, n_ici: int, proofs_a_group: int = BATCH_CHUNK_MAX) -> int:
    """The step width of a pod MSM's wide class and of its h MSM over
    `n` bases, padded or not, in `n_ici` shards, under a chunk of
    `proofs_a_group` proofs a batch group: 64 for a chunk of
    BATCH_CHUNK_MAX, or a shard's whole share where that is less (tiny
    CI circuits stay at lanes ~ n/S instead of padding 16x to a 64-lane
    step).  `place_key` pads the bases to a multiple of `n_ici *
    pod_lanes(n, n_ici)`, so every device sees whole steps.  A smaller
    chunk takes steps as many times wider: a step builds its table of
    multiples once whatever the chunk (8 mixed adds in a row on `lanes`
    points: latency, not work), so a chunk of one at 64 lanes would pay
    a chunk of four's steps for a quarter of its accumulate; at 256 it
    runs a quarter of the steps, its accumulate (planes x lanes x
    proofs) what a chunk of four's is (`msm_windowed_signed` pads a
    share that is no whole number of them)."""
    return max(1, min(64, -(-n // n_ici))) * max(1, BATCH_CHUNK_MAX // proofs_a_group)


def pod_narrow_lanes(n: int, n_ici: int, proofs_a_group: int = BATCH_CHUNK_MAX, cap: int = 16384) -> int:
    """The step width of a pod MSM's narrow class over `n` bases in
    `n_ici` shards: the one-chip road's rule on a chip's share
    (`default_lanes`: a sixteenth of it, rounded up so that a share
    padded to whole steps answers the same; `cap` 16,384 for G1 as
    `_msm_g1_narrow`, 4,096 for G2 as `_msm_g2_narrow`), and no narrower
    than gives a step's accumulate, NARROW_PLANES x lanes x proofs, the
    adds of the wide class's (every plane x `pod_lanes` x proofs:
    16,384) — three planes at the wide class's 64 lanes would be 768
    adds a step, on the latency floor — for a chunk of four and of one
    alike; never more than the share."""
    share = -(-n // n_ici)
    wide_step = (256 // MSM_WINDOW) * pod_lanes(n, n_ici, proofs_a_group) * proofs_a_group
    floor = -(-wide_step // (NARROW_PLANES * proofs_a_group))
    return max(1, min(share, cap, max(default_lanes(share + 15, cap), floor)))


_POD_QUERIES = ("a", "b1", "b2", "c")  # the witness MSMs: on a mesh each in two classes


def _narrow_cap(query: str) -> int:
    """The most lanes a step of `query`'s narrow class takes on a mesh:
    `_msm_g1_narrow`'s, and `_msm_g2_narrow`'s for b2."""
    return 4096 if query == "b2" else 16384


_QAP_ROWS = ("a_coeff", "a_wire", "a_row", "b_coeff", "b_wire", "b_row")


def place_key(dpk: "DeviceProvingKey", where) -> "DeviceProvingKey":
    """The key placed on `where`, a device or a ("batch", "shard") pod
    mesh; one `tpu/place_key` span a call, and its bytes counted by
    `zkp2p_key_placed_bytes_total`.

    A device: the key pinned to it.  Where it lives elsewhere, a copy,
    device to device (a key is read from disk once whatever the number
    of replicas): a new instance, whose resident h table and class
    splits (`_h_table_cache`, `_split_cache`) its first batch builds on
    that device.  Where it already lives there, the same instance with
    the same buffers, pinned: the keys of a replica set are all pinned,
    so their programs are lowered once between them (and compiled a
    device).

    A mesh: a new instance that only the mesh road reads
    (`_prove_batch_sharded`; `key_mesh` tells).  Each witness query
    (`a_bases`, `b1_bases`, `b2_bases`, `c_bases`) becomes a pair of
    classes, `(narrow, wide)`, each `(x, y, wire)`: the bases the key's
    own classing selects (`a_nsel` / `a_wsel`, `b_*`, `c_*`:
    `class_sels`) with the wire of every base beside it, each base in
    one of the two and the whole array kept nowhere; a key with no
    narrow class (an imported zkey without widths) has every base in
    the wide one and none in the narrow.  Every class, and `h_bases`,
    is padded to whole steps of every shard (`pod_narrow_lanes`,
    `pod_lanes`; the filler is an infinity base that names wire 0) and
    committed `P("shard")`, a quarter a chip on 1x4, so each chip holds
    an equal share of each class and the wire of each base it holds.
    The three G1 queries' classes are padded to one base count a class,
    so they run ONE program (`_prove_device`'s `unify`: b1, half of
    a's bases, then costs what a does; a two-class program more to
    lower costs a start-up more); b2, three times the work a base,
    keeps its own count.  The eight selections are empty: the classes
    hold what they said.  The QAP rows, which the h program reads
    whole, are committed to every chip.  After this no batch moves a
    byte of key."""
    from jax.sharding import Mesh

    from ..utils.metrics import REGISTRY
    from ..utils.trace import trace

    on_mesh = isinstance(where, Mesh)
    with trace("tpu/place_key", **({"mesh": mesh_name(where)} if on_mesh else {"device": str(where)})) as span:
        placed = _place_on_mesh(dpk, where) if on_mesh else _pin_to_device(dpk, where)
        span["bytes"] = sum(
            shard.data.nbytes
            for f in _DPK_ARRAY_FIELDS for x in jax.tree_util.tree_leaves(getattr(placed, f))
            for shard in x.addressable_shards)
    REGISTRY.counter("zkp2p_key_placed_bytes_total").inc(span["bytes"])
    return placed


def _pin_to_device(dpk: "DeviceProvingKey", device) -> "DeviceProvingKey":
    if not isinstance(dpk.a_coeff, jax.Array) or dpk.a_coeff.devices() != {device}:
        return jax.device_put(dpk, device)
    pin = lambda tree: jax.tree_util.tree_map(lambda x: jax.device_put(x, device), tree)  # noqa: E731
    for f in _DPK_ARRAY_FIELDS:
        setattr(dpk, f, pin(getattr(dpk, f)))
    for cache in ("_h_table_cache", "_split_cache"):
        if getattr(dpk, cache, None) is not None:
            setattr(dpk, cache, pin(getattr(dpk, cache)))
    return dpk


def _place_on_mesh(dpk: "DeviceProvingKey", mesh) -> "DeviceProvingKey":
    import dataclasses

    from jax.sharding import NamedSharding, PartitionSpec as P

    n_ici = mesh.shape["shard"]
    sharded, whole = NamedSharding(mesh, P("shard")), NamedSharding(mesh, P())

    def in_shards(x, n, lanes):  # padded to whole steps of `lanes` on every shard, of `n` bases
        n_to = n + (-n) % (n_ici * lanes) if n else 0
        return jax.device_put(np.pad(x, [(0, n_to - x.shape[0])] + [(0, 0)] * (x.ndim - 1)), sharded)

    def classes(bases, sels, wire_of):
        """The (narrow, wide) pair of a query, unpadded: the key's own
        classing; without one (`a_nsel` empty) every base is wide.
        `wire_of`: the wire of each base, None where they are in order.
        Cut on the host, in numpy, wherever the key lives (a key no chip
        holds is there already): an eager take or pad on the device is a
        program to lower and compile every start, and eight classes
        would be a few dozen."""
        bases, sels = tuple(np.asarray(c) for c in bases), tuple(np.asarray(sel) for sel in sels)
        if not int(dpk.a_nsel.shape[0]):
            sels = (np.zeros((0,), np.int32), np.arange(bases[0].shape[0], dtype=np.int32))
        return [tuple(c[sel] for c in bases) + (sel if wire_of is None else np.asarray(wire_of)[sel],) for sel in sels]

    b_sels = (dpk.b_nsel, dpk.b_wsel)
    pairs = {"a": classes(dpk.a_bases, (dpk.a_nsel, dpk.a_wsel), None), "b1": classes(dpk.b1_bases, b_sels, dpk.b_sel),
             "b2": classes(dpk.b2_bases, b_sels, dpk.b_sel), "c": classes(dpk.c_bases, (dpk.c_nsel, dpk.c_wsel), dpk.c_sel)}
    g1_most = [max(pairs[q][k][0].shape[0] for q in ("a", "b1", "c")) for k in (0, 1)]
    fields = {f: jax.device_put(getattr(dpk, f), whole) for f in _QAP_ROWS}
    for q, pair in pairs.items():
        n_narrow, n_wide = (cls[0].shape[0] for cls in pair) if q == "b2" else g1_most
        steps = (pod_narrow_lanes(n_narrow, n_ici, cap=_narrow_cap(q)), pod_lanes(n_wide, n_ici))
        fields[q + "_bases"] = tuple(
            tuple(in_shards(c, n, lanes) for c in cls) for cls, n, lanes in zip(pair, (n_narrow, n_wide), steps))
    n_h = dpk.h_bases[0].shape[0]
    fields["h_bases"] = tuple(in_shards(np.asarray(c), n_h, pod_lanes(n_h, n_ici)) for c in dpk.h_bases)
    none = jax.device_put(np.zeros((0,), np.int32), whole)
    for f in ("b_sel", "c_sel", "a_nsel", "a_wsel", "b_nsel", "b_wsel", "c_nsel", "c_wsel"):
        fields[f] = none
    return dataclasses.replace(dpk, **fields)


def _key_on_mesh(dpk: "DeviceProvingKey", mesh) -> "DeviceProvingKey":
    """`dpk` as the mesh road reads it: itself where it was placed on
    `mesh`, else its placed form, made by the first batch that needs it
    and memoised on the instance like the h table (the benchmark hands
    the service the key `load_dpk` gave it)."""
    if key_mesh(dpk) == mesh:
        return dpk
    memo = getattr(dpk, "_mesh_key_cache", None)
    if memo is None or memo[0] != mesh:
        memo = (mesh, place_key(dpk, mesh))
        setattr(dpk, "_mesh_key_cache", memo)
    return memo[1]


def _rows_to_arrays(rows: Sequence[dict], m: int, home=jnp.asarray) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Sparse QAP rows -> (coeff mont limbs, wire ids, row ids), row
    after row (`_row_blocks` counts on the order), each through `home`
    (`key_arrays_home`).  The coefficient conversion is the vectorized
    bytes->limbs path — at venmo-scale nnz counts a per-element limb
    loop costs minutes."""
    vals: List[int] = []
    wires: List[int] = []
    row_ids: List[int] = []
    for j, terms in enumerate(rows):
        for wire, coeff in terms.items():
            vals.append(coeff % R)
            wires.append(wire)
            row_ids.append(j)
    if not vals:  # degenerate all-zero matrix
        vals, wires, row_ids = [0], [0], [m - 1]
    return (
        home(FR.array_to_mont_host_fast(vals)),
        home(np.array(wires, dtype=np.int32)),
        home(np.array(row_ids, dtype=np.int32)),
    )


# Width classing (see DeviceProvingKey): wires with constraint-backed
# value bounds < 2^NARROW_WIDTH need only NARROW_PLANES signed w=4 digit
# planes (k planes exactly hold v < 2^(4k-1) after signed recoding).
NARROW_WIDTH = 11
NARROW_PLANES = 3


def widths_array(cs: "ConstraintSystem") -> np.ndarray:
    """cs.wire_width dict -> dense per-wire bound array (254 = unbounded)."""
    widths = np.full(cs.num_wires, 254, dtype=np.int32)
    for w, bits in cs.wire_width.items():
        widths[w] = bits
    return widths


def class_sels(widths: Optional[np.ndarray], wire_ids: np.ndarray):
    """(narrow positions, wide positions) into a base array whose row p
    holds the point for wire wire_ids[p] — THE classing rule, shared by
    device_pk_from_rows and setup_device so the dev-setup and pk-import
    paths can never drift."""
    if widths is None:
        n = len(wire_ids)
        return np.zeros(0, dtype=np.int32), np.arange(n, dtype=np.int32)
    narrow = widths[wire_ids] <= NARROW_WIDTH
    return (
        np.flatnonzero(narrow).astype(np.int32),
        np.flatnonzero(~narrow).astype(np.int32),
    )


def device_pk(pk: ProvingKey, cs: ConstraintSystem) -> DeviceProvingKey:
    """Host ProvingKey + R1CS -> device arrays.  One-time load, amortised
    over every proof (the TPU analog of the browser's IndexedDB zkey cache,
    `app/src/helpers/zkp.ts:56-61`)."""
    rows = qap_rows(cs)
    widths = widths_array(cs)
    return device_pk_from_rows(
        pk, [t[0] for t in rows], [t[1] for t in rows], domain_size_for(cs), cs.num_wires,
        widths=widths,
    )


def infer_zkey_widths(zk) -> np.ndarray:
    """Recover the narrow width class from an imported zkey's coeff
    section by detecting circom's bit-constraint rows x·(x-1)=0
    (circomlib Num2Bits emits A={x:1}, B={x:1, one:-1}, C=0; also
    matched with A/B swapped).  The zkey stores no C matrix, so the
    pattern is NOT conclusive — x·(x-1)=y matches identically — which
    is why every prove on an inferred-width key runs the witness-bound
    validator (`_check_inferred_widths`): a witness that breaks an
    inferred bound raises instead of silently dropping digit planes.

    Recovers the ~10x witness-MSM cut for ceremony keys (the production
    import path) that dev-setup keys get from ConstraintSystem width
    tags."""
    from collections import defaultdict

    a_rows: Dict[int, Dict[int, int]] = defaultdict(dict)
    b_rows: Dict[int, Dict[int, int]] = defaultdict(dict)
    for mat, row, wire, v in zk.coeffs:
        (a_rows if mat == 0 else b_rows)[row][wire] = v
    widths = np.full(zk.n_vars, 254, dtype=np.int32)
    widths[0] = 1  # constant-one wire
    for r in set(a_rows) | set(b_rows):
        A, B = a_rows.get(r, {}), b_rows.get(r, {})
        for X, Y in ((A, B), (B, A)):
            if len(X) == 1 and len(Y) == 2 and 0 in Y:
                ((w, xv),) = X.items()
                if w != 0 and xv == 1 and Y.get(w) == 1 and Y[0] == R - 1:
                    widths[w] = 1
    return widths


def _check_inferred_widths(
    dpk: DeviceProvingKey,
    witness: Sequence[int],
    w_std: Optional[np.ndarray] = None,
) -> None:
    """Host-side guard for inferred-width keys: every wire classed
    narrow must actually fit the narrow digit planes.  No-op for keys
    built from a ConstraintSystem, whose `check_witness`/`check_widths`
    already enforce the tagged bounds.

    `w_std`: optional (n_wires, 4) u64 standard-form limb view of the
    witness (prove_native already builds one) — the check vectorizes
    over it instead of looping Python bigints."""
    blob = dpk.inferred_narrow_wires
    if not blob:
        return
    wires = np.frombuffer(blob, dtype=np.int64)
    bound = 1 << (4 * NARROW_PLANES - 1)
    if w_std is None:
        # build a limb view of just the narrow wires (to_bytes is
        # C-speed; a pure-Python bigint comparison loop over ~90% of a
        # venmo key's wires costs seconds per witness at batch=64)
        from ..native.lib import _scalars_to_u64

        w_std = _scalars_to_u64([witness[j] % R for j in wires])
        wires_idx = np.arange(len(wires))
    else:
        wires_idx = wires
    vals = np.asarray(w_std)[wires_idx]
    bad = (vals[:, 1:].any(axis=1)) | (vals[:, 0] >= bound)
    if not bad.any():
        return
    i = int(wires[int(np.flatnonzero(bad)[0])])
    raise ValueError(
        f"wire {i}: witness value exceeds the width bound inferred "
        f"from the zkey's bit-constraint pattern — the circuit uses "
        f"x*(x-1)=y somewhere; re-import with infer_widths=False"
    )


def device_pk_from_zkey(zk, infer_widths: bool = True) -> DeviceProvingKey:
    """snarkjs zkey (formats.zkey.ZkeyData) -> device arrays: the
    ceremony-key import path (`app/src/helpers/zkp.ts:13` chunk flow).
    The zkey coeff section already contains the public binding rows, so
    the QAP rows come from the file, not from a ConstraintSystem.  Width
    metadata is recovered from the bit-constraint pattern by default
    (`infer_zkey_widths`), guarded at prove time."""
    a_rows, b_rows = zk.qap_row_arrays()
    widths = infer_zkey_widths(zk) if infer_widths else None
    dpk = device_pk_from_rows(
        zk.to_proving_key(), a_rows, b_rows, zk.domain_size, zk.n_vars, widths=widths
    )
    if widths is not None:
        dpk.inferred_narrow_wires = (
            np.flatnonzero(widths <= NARROW_WIDTH).astype(np.int64).tobytes()
        )
    return dpk


def _prune_sel(flags: Sequence[bool]) -> np.ndarray:
    sel = [i for i, f in enumerate(flags) if f]
    if not sel:
        sel = [0]  # degenerate: keep one (infinity) lane
    return np.array(sel, dtype=np.int32)


def device_pk_from_rows(
    pk: ProvingKey,
    a_rows: Sequence[dict],
    b_rows: Sequence[dict],
    m: int,
    n_wires: int,
    widths: Optional[np.ndarray] = None,
) -> DeviceProvingKey:
    log_m = m.bit_length() - 1
    a = _rows_to_arrays(a_rows, m)
    b = _rows_to_arrays(b_rows, m)
    h_pts = list(pk.h_query) + [None] * (m - len(pk.h_query))
    b_sel = _prune_sel(
        [p1 is not None or p2 is not None for p1, p2 in zip(pk.b1_query, pk.b2_query)]
    )
    c_sel = _prune_sel([p is not None for p in pk.c_query])

    all_wires = np.arange(n_wires, dtype=np.int32)
    a_nsel, a_wsel = class_sels(widths, all_wires)
    b_nsel, b_wsel = class_sels(widths, np.asarray(b_sel))
    c_nsel, c_wsel = class_sels(widths, np.asarray(c_sel))
    return DeviceProvingKey(
        n_public=pk.n_public,
        n_wires=n_wires,
        log_m=log_m,
        a_coeff=a[0], a_wire=a[1], a_row=a[2],
        b_coeff=b[0], b_wire=b[1], b_row=b[2],
        a_bases=g1_to_affine_arrays(pk.a_query),
        b1_bases=g1_to_affine_arrays([pk.b1_query[i] for i in b_sel]),
        b2_bases=g2_to_affine_arrays([pk.b2_query[i] for i in b_sel]),
        c_bases=g1_to_affine_arrays([pk.c_query[i] for i in c_sel]),
        h_bases=g1_to_affine_arrays(h_pts),
        b_sel=jnp.asarray(b_sel),
        c_sel=jnp.asarray(c_sel),
        a_nsel=jnp.asarray(a_nsel), a_wsel=jnp.asarray(a_wsel),
        b_nsel=jnp.asarray(b_nsel), b_wsel=jnp.asarray(b_wsel),
        c_nsel=jnp.asarray(c_nsel), c_wsel=jnp.asarray(c_wsel),
        alpha_1=pk.alpha_1,
        beta_1=pk.beta_1,
        beta_2=pk.beta_2,
        delta_1=pk.delta_1,
        delta_2=pk.delta_2,
    )


def _witness_rows(witness) -> Optional[np.ndarray]:
    """The witness as its standard-form (n, 4) u64 rows, where it arrives
    as them: the array itself (the .bench_cache format, prove_native's
    view) or the `u64` its builder attached (`snark.r1cs.Witness`,
    `WitnessRow`), under the guard the service's self-check reads them by
    (`witness_check.rows_of`).  None for anything else (a plain list,
    rows an assignment dropped, rows of another shape or dtype): that
    takes the `int(w) % R` path.  Observed from the input, never set."""
    return rows_of(witness, getattr(witness, "u64", witness))


def _check_u64_reduced(rows: np.ndarray) -> None:
    """Reject (n, 4)-u64 witness rows >= R.  The fast path trusts its
    input to already be reduced (the .bench_cache contract) — an
    unreduced row would silently emit a wrong Montgomery form and an
    unverifiable proof, so the boundary asserts it (vectorized
    compares; negligible next to to_mont)."""
    bad = unreduced_rows(rows)
    if bad.size:
        raise ValueError(
            f"witness row {int(bad[0])} is not reduced below the Fr modulus: the "
            f"(n, 4)-u64 fast path requires canonical scalars (< R); "
            f"reduce mod R before witness_to_device"
        )


def _witness_std_limbs(witness, out: Optional[np.ndarray] = None) -> np.ndarray:
    """Host witness -> (n, 16) u32 standard-form 16-bit limbs, into `out`
    where given.  A witness that is or carries its rows (`_witness_rows`)
    is those rows, checked canonical: numpy from end to end.  Any other
    sequence goes a wire at a time through `int(w) % R` and one bytes
    join: the oracle the rows are compared against."""
    from ..native.lib import _scalars_to_u64, _u64_to_limbs16

    rows = _witness_rows(witness)
    if rows is None:
        rows = _scalars_to_u64([int(w) % R for w in witness])
    else:
        _check_u64_reduced(rows)
    return _u64_to_limbs16(rows, out)


def _chunk_limbs(span: Sequence) -> np.ndarray:
    """One chunk's witnesses as the (chunk, n, 16) u32 array its upload
    takes: each witness's limbs written once, straight into its place (no
    list of arrays and a stack: a second pass over 128 MB at 2^19 x 4).  A
    witness that is in the chunk again (a short batch padded by repeating
    its last) is converted once and copied.  A fresh array a chunk: the
    upload may still be reading the last one."""
    limbs = np.empty((len(span), len(span[0]), 16), dtype=np.uint32)
    first: Dict[int, int] = {}
    for i, wit in enumerate(span):
        j = first.setdefault(id(wit), i)
        if j == i:
            _witness_std_limbs(wit, out=limbs[i])
        else:
            limbs[i] = limbs[j]
    return limbs


def witness_to_device(witness) -> jnp.ndarray:
    """Host witness -> Montgomery limb matrix (n_wires, 16): the
    vectorized standard-form limbs plus ONE device to_mont mul."""
    return FR.to_mont(jnp.asarray(_witness_std_limbs(witness)))


def _matvec(coeff, wire, row, w_mont, m):
    vals = FR.mul(coeff, w_mont[wire])
    return lazy_segment_sum_mod(FR, vals, row, m)


def abc_evals(dpk: DeviceProvingKey, w_mont: jnp.ndarray):
    """Az/Bz/Cz evaluations on the domain: the sparse-matvec stage of
    the H ladder."""
    m = 1 << dpk.log_m
    with jax.named_scope("matvec"):
        a_ev = _matvec(dpk.a_coeff, dpk.a_wire, dpk.a_row, w_mont, m)
        b_ev = _matvec(dpk.b_coeff, dpk.b_wire, dpk.b_row, w_mont, m)
        return a_ev, b_ev, FR.mul(a_ev, b_ev)


def h_evals(dpk: DeviceProvingKey, w_mont: jnp.ndarray) -> jnp.ndarray:
    """Coset evaluations d_j = (A·B - C)(g·w^j) on device, (m, 16) mont
    limbs — the scalars MSM'd against the coset-Lagrange h_bases.

    Same ladder as the host oracle `snark.groth16.coset_quotient_evals`
    (the snarkjs `groth16 prove` dataflow: 3 iNTT + 3 coset NTT, no
    division — Z is constant on the coset and folded into h_bases), every
    step batched on limb lanes."""
    g = coset_gen(dpk.log_m)

    def to_coset(ev):
        with jax.named_scope("intt"):
            coeffs = intt(ev, dpk.log_m)
        with jax.named_scope("coset_ntt"):
            return ntt(coset_shift(coeffs, g, dpk.log_m), dpk.log_m)

    a_ev, b_ev, c_ev = abc_evals(dpk, w_mont)
    a_cos, b_cos, c_cos = to_coset(a_ev), to_coset(b_ev), to_coset(c_ev)
    return FR.sub(FR.mul(a_cos, b_cos), c_cos)


def _h_and_planes(dpk: DeviceProvingKey, w_mont: jnp.ndarray, h_window: int):
    h = h_evals(dpk, w_mont)
    with jax.named_scope("recode"):
        return _recode(dpk, w_mont, h, h_window)


def _recode(dpk: DeviceProvingKey, w_mont: jnp.ndarray, h: jnp.ndarray, h_window: int):
    """Witness and h scalars -> signed digit planes, most significant
    first: `((w_mags, w_negs), narrow), (h_mags, h_negs)`.  The witness
    at MSM_WINDOW, h at `h_window`: the window of the resident table
    the key's device holds, MSM_WINDOW where it holds none (static: the
    caller reads it off the table it passes to the h MSM).  `narrow` is the low NARROW_PLANES of the witness's w=4 recode:
    wires with width bounds <= 2^11 only populate those — the upper 61
    planes are provably zero and never reach an MSM; `()` for a key
    with no narrow class (zkey import): shapes are static under jit,
    so that prunes at trace time."""
    w_std = FR.from_mont(w_mont)
    w_mags, w_negs = signed_digit_planes_from_limbs(w_std, MSM_WINDOW)
    h_mags, h_negs = signed_digit_planes_from_limbs(FR.from_mont(h), h_window)
    narrow = ()
    if int(dpk.a_nsel.shape[0]) > 0:
        # a recode of its own: the narrow MSMs run at w=4 whatever the
        # wide window is (equal today, and XLA folds the two into one)
        n4_mags, n4_negs = signed_digit_planes_from_limbs(w_std, 4)
        narrow = (n4_mags[-NARROW_PLANES:], n4_negs[-NARROW_PLANES:])
    return ((w_mags, w_negs), narrow), (h_mags, h_negs)


def _msm_g1(bases, planes):
    # lanes from the static base count: wide steps keep the VPU batch
    # large (TPU ops are latency-bound at small batches — see
    # ops.msm.default_lanes).
    lanes = default_lanes(bases[0].shape[0])
    return msm_windowed_signed(G1J, bases, *planes, lanes=lanes, window=MSM_WINDOW)


def _msm_g1_narrow(bases, planes):
    # 3-plane signed w=4 MSM for width-bounded wires: ~3.5 adds/pt at
    # batch=16 vs ~40 on the wide path.  Wider lanes keep the per-step
    # batch (NARROW_PLANES x lanes) off the latency floor.
    lanes = default_lanes(bases[0].shape[0], cap=16384)
    return msm_windowed_signed(G1J, bases, *planes, lanes=lanes, window=4)


def _msm_g2_narrow(bases, planes):
    lanes = default_lanes(bases[0].shape[0], cap=4096)
    return msm_windowed_signed(G2J, bases, *planes, lanes=lanes, window=4)


def _msm_g2(bases, planes):
    lanes = default_lanes(bases[0].shape[0], cap=2048)
    return msm_windowed_signed(G2J, bases, *planes, lanes=lanes, window=MSM_WINDOW)


def _h_table_fn(bases, window: int):
    return resident_table(G1J, bases, window, default_lanes(bases[0].shape[0]))


def _msm_h_resident(table, planes):
    """The h MSM against the key's resident multiples table."""
    return msm_resident(G1J, table, *planes)


# Stage-wise jits, NOT one fused program: XLA compile time scales with
# traced-graph size, so the pipeline is a handful of small executables
# with intermediates staying on device between stages.  Each is vmapped
# over the witnesses of a chunk, the key unbatched.  Since b/c pruning
# the G1 MSMs run at three different lane counts (a: all wires, b1:
# |b_sel|, c: |c_sel|), so jit re-specializes _msm_g1 per shape — the
# ~50% runtime cut on b1/b2/c outweighs the extra first-proof compiles
# (and the persistent cache amortises them across processes).
_jit_h_planes = jax.jit(jax.vmap(_h_and_planes, in_axes=(None, 0, None)), static_argnums=2)
_jit_msm_g1 = jax.jit(jax.vmap(_msm_g1, in_axes=(None, 0)))
_jit_msm_g2 = jax.jit(jax.vmap(_msm_g2, in_axes=(None, 0)))
_jit_msm_g1_narrow = jax.jit(jax.vmap(_msm_g1_narrow, in_axes=(None, 0)))
_jit_msm_g2_narrow = jax.jit(jax.vmap(_msm_g2_narrow, in_axes=(None, 0)))
_jit_msm_h_resident = jax.jit(jax.vmap(_msm_h_resident, in_axes=(None, 0)))
_jit_h_table = jax.jit(_h_table_fn, static_argnames="window")


def _take_planes(planes, sel):
    """Signed planes are a (mags, negs) pair; both gather on wires."""
    return tuple(jnp.take(p, sel, axis=-1) for p in planes)


def _h_table(dpk: DeviceProvingKey) -> Optional[jnp.ndarray]:
    """The key's resident h table, built by the first prove that needs
    it and memoised on the instance like `_split_cache` (not a pytree
    field: its bytes never ride into a jitted stage as part of the key);
    None where `_h_table_window` says the h MSM builds its multiples in
    the scan.  A key placed on a mesh (`key_mesh`) keeps it in shards:
    each chip the table of the h bases it holds, built where they lie
    (`resident_table_pod` at `pod_table_lanes`; the span says `mesh`).
    `bytes` and `zkp2p_msm_h_table_bytes` count every chip's."""
    from ..utils.metrics import REGISTRY
    from ..utils.trace import trace

    mesh = key_mesh(dpk)
    window, table = _h_table_window(dpk.log_m, key_device(dpk), mesh), None
    replicas = 1 if mesh is None else mesh.shape["batch"]  # every batch group holds the shards again
    if window is not None:
        table = getattr(dpk, "_h_table_cache", None)
        if table is None:
            with trace("h_table", window=window, **({} if mesh is None else {"mesh": mesh_name(mesh)})) as span:
                if mesh is None:
                    table = _jit_h_table(dpk.h_bases, window=window)
                else:
                    from ..parallel.mesh import resident_table_pod

                    n_h, n_ici = dpk.h_bases[0].shape[0], mesh.shape["shard"]
                    table = resident_table_pod(G1J, dpk.h_bases, mesh, window, pod_table_lanes(n_h, n_ici))
                table = jax.block_until_ready(table)
                span["bytes"] = int(table.nbytes) * replicas
            setattr(dpk, "_h_table_cache", table)
    REGISTRY.gauge("zkp2p_msm_h_table_bytes").set(0 if table is None else table.nbytes * replicas)
    return table


def pod_table_lanes(n: int, n_ici: int) -> int:
    """The step width of a placed key's resident h table over `n` bases
    in `n_ici` shards: POD_TABLE_LANES, and never more than the share.
    The cap is the chip's reading of a share (PERF.md, PR 43): wider
    steps cost more an add (256 lanes 640.7 ms for a 2^19 share x 4
    proofs, 1,024 lanes 723.4, 4,096 887.7; a 2^21 share x 1 at w=4
    4,939 / 5,009 / 5,551), and 64 and 256 lanes read within 4% of each
    other, either way, at a 2^16 and a 2^19 share."""
    return max(1, min(-(-n // n_ici), POD_TABLE_LANES))


def _take_bases(bases, pos):
    return tuple(jnp.take(c, pos, axis=0) for c in bases)


def _pad_msm(bases, planes, n_to: int):
    """Pad an MSM's inputs to `n_to` bases: the (0, 0) infinity sentinel
    and zero digit planes contribute nothing, and equal shapes let MSMs
    share one compiled executable."""
    n = bases[0].shape[0]
    if n_to and n < n_to:
        bases = tuple(jnp.pad(c, [(0, n_to - n)] + [(0, 0)] * (c.ndim - 1)) for c in bases)
        planes = tuple(jnp.pad(p, [(0, 0)] * (p.ndim - 1) + [(0, n_to - n)]) for p in planes)
    return bases, planes


STAGES = ("h_planes", "msm_a", "msm_b1", "msm_b2", "msm_c", "msm_h")
MESH_STAGES = ("h_planes", "exchange") + STAGES[1:]  # the mesh road's seven


class _StageWatch:
    """Writes one `stage/<name>` span per device stage of a batch.  The
    proving thread names a stage as it enqueues it (`enqueued`: a few
    bytes of its result to wait on, and when the host began to enqueue
    it — the instant it had enqueued the stage before); this thread
    waits for those results in turn.  One device runs what was enqueued
    in order, so a stage ends when its result is ready and starts at the
    later of the previous stage's end and its own enqueue: the spans
    partition `device`, whose start is `t0`.  The eager gathers and pads
    between two stages run between them on the device too, and count to
    the later one.  A thread of its own, because the runtime bounds the
    programs in flight and holds the enqueuing thread inside `dispatch`
    while the first stages retire (PERF.md, PR 24): read from there,
    their ends would all read as dispatch's.

    A chunk's witnesses on their way to the device are no stage
    (`uploaded`): the `upload` span runs from where the chunk's first
    stage may start (`t0`; a later chunk's, the instant the host had
    enqueued the chunk before) to the limbs being ready there, beside
    `stage/h_planes` and inside it, and moves no stage's start.

    `t_ready` is when the last stage waited for was ready: after `close`,
    the instant the device had nothing left."""

    def __init__(self, t0: float):
        from ..utils.trace import adopt_context, adopt_stack, current_context, current_stack

        self.chunk = 0
        self.t_ready: Optional[float] = None
        self._t = t0
        self._q: "queue.SimpleQueue" = queue.SimpleQueue()
        stack, ctx = current_stack(), current_context()

        def run():
            from ..utils.trace import record

            adopt_stack(stack)  # the spans nest under `device`, open on the proving thread
            adopt_context(ctx)
            t_ready, failed = t0, False
            for item in iter(self._q.get, None):
                if isinstance(item, threading.Event):  # `settled`: every span enqueued before it is written
                    item.set()
                    continue
                if failed:
                    continue
                name, chunk, t_enqueue, value, attrs = item
                try:
                    jax.block_until_ready(value)
                except Exception:  # noqa: BLE001 — the proving thread meets it where it reads the accumulator
                    failed = True  # no span more; whoever waits in `settled` is still answered
                    continue
                if name == "upload":  # the chunk's witnesses, not a stage: the stage clock stays
                    record(name, t_enqueue, time.time(), chunk=chunk, **attrs)
                    continue
                t_start, t_ready = max(t_ready, t_enqueue), time.time()
                record("stage/" + name, t_start, t_ready, chunk=chunk, **attrs)
                self.t_ready = t_ready

        self._thread = threading.Thread(target=run, name="zkp2p-stage-watch", daemon=True)
        self._thread.start()

    def enqueued(self, name: str, value, **attrs) -> None:
        self._q.put((name, self.chunk, self._t, value, attrs))
        self._t = time.time()

    def uploaded(self, limbs, nbytes: int):
        """The chunk's witnesses, put to the device before its first stage
        is enqueued: `limbs`, which it returns, is ready when they have
        arrived."""
        self._q.put(("upload", self.chunk, self._t, limbs, {"bytes": nbytes}))
        return limbs

    def settled(self) -> Optional[float]:
        """Returns when every stage enqueued so far has its result ready
        and its span written: `t_ready`, the instant the last of them was."""
        done = threading.Event()
        self._q.put(done)
        done.wait()
        return self.t_ready

    def close(self) -> None:
        """Returns when the last stage enqueued has its result ready and
        its span written."""
        self._q.put(None)
        self._thread.join()


_first_element = jax.jit(lambda x: jax.lax.slice(x, (0,) * x.ndim, (1,) * x.ndim))


def _enqueued(watch: Optional[_StageWatch], name: str, value, **attrs):
    if watch is not None:
        watch.enqueued(name, value, **attrs)
    return value


def _msm_enqueued(watch: Optional[_StageWatch], name: str, value, **attrs):
    """`_enqueued` for the five MSM stages: the span carries the curve's
    addition law (`add`), as `h_planes` carries its ladder."""
    return _enqueued(watch, name, value, **{"add": ADD_LAW, **attrs})


def _prove_device(dpk: DeviceProvingKey, w_mont: jnp.ndarray, watch: Optional[_StageWatch] = None):
    """The five big MSMs of a chunk of witnesses, `w_mont` (B, n_wires,
    16); everything else about the proof is host-cheap.  The b/c MSMs
    run only over their pruned non-infinity lanes (plane columns
    gathered through b_sel/c_sel), and with width metadata each witness
    MSM splits into a narrow class (3 signed w=4 planes — the ~90% of
    wires that are constraint-bounded bits/bytes) and a wide class (full
    planes); the two partial sums combine with one projective add per
    query.  On a TPU the MSMs of a class are padded to one base count,
    so they share ONE compiled executable (each cold TPU MSM compile
    measured ~2 min)."""
    unify = _on_tpu()
    h_table = _h_table(dpk)
    h_window = MSM_WINDOW if h_table is None else int(h_table.shape[1]).bit_length()
    (w_planes, w_narrow), h_planes = _jit_h_planes(dpk, w_mont, h_window)
    if watch is not None:
        # a few bytes that are ready when the stage is, cut from a plane by a
        # program of their own: the stage's program stays as it is, and no
        # plane is kept alive to wait on
        watch.enqueued("h_planes", _first_element(h_planes[0]), ntt=NTT_LADDER)

    def msm_h(n_to: int = 0):
        """The h stage, enqueued: against the resident table where the
        key has one, else `_msm_g1` builds the multiples in its scan,
        over `n_to` bases where it shares the query MSMs' executable."""
        if h_table is not None:
            return _msm_enqueued(watch, "msm_h", _jit_msm_h_resident(h_table, h_planes),
                             window=h_window, table="resident")
        return _msm_enqueued(watch, "msm_h", _jit_msm_g1(*_pad_msm(dpk.h_bases, h_planes, n_to)),
                         window=MSM_WINDOW, table="scan")

    if not int(dpk.a_nsel.shape[0]):  # no narrow class: one MSM a query
        # with a resident h table h no longer shares the unified
        # executable, so padding a/b1/c up to the (domain-sized) h base
        # count would be pure waste — unify the three query MSMs among
        # themselves only
        g1_n = 0 if not unify else max(
            dpk.a_bases[0].shape[0], dpk.b1_bases[0].shape[0], dpk.c_bases[0].shape[0],
            *(() if h_table is not None else (dpk.h_bases[0].shape[0],)),
        )
        b_planes = _take_planes(w_planes, dpk.b_sel)
        c_planes = _take_planes(w_planes, dpk.c_sel)
        h_acc = msm_h(g1_n)
        return (
            _msm_enqueued(watch, "msm_a", _jit_msm_g1(*_pad_msm(dpk.a_bases, w_planes, g1_n))),
            _msm_enqueued(watch, "msm_b1", _jit_msm_g1(*_pad_msm(dpk.b1_bases, b_planes, g1_n))),
            _msm_enqueued(watch, "msm_b2", _jit_msm_g2(dpk.b2_bases, b_planes)),
            _msm_enqueued(watch, "msm_c", _jit_msm_g1(*_pad_msm(dpk.c_bases, c_planes, g1_n))),
            h_acc,
        )

    # Unify shapes WITHIN each class (a/b1/c wide together, narrows
    # together) but NOT with the h MSM: the wide query classes are ~6%
    # of wires while h spans the full domain — padding them to h's size
    # would burn ~16x the work the classing just removed.  Three G1
    # executables total (narrow, query-wide, h).
    g1_wide_n = g1_narrow_n = 0
    if unify:
        g1_wide_n = max(dpk.a_wsel.shape[0], dpk.b_wsel.shape[0], dpk.c_wsel.shape[0])
        g1_narrow_n = max(dpk.a_nsel.shape[0], dpk.b_nsel.shape[0], dpk.c_nsel.shape[0])

    # The split bases/wire arrays depend only on the KEY — memoise them
    # on the dpk instance so the gathers (O(key size) HBM copies) run
    # once per key, not once per proof.
    split = getattr(dpk, "_split_cache", None)
    if split is None:
        split = {}
        setattr(dpk, "_split_cache", split)

    def key_split(name, bases, sel, wires_of):
        got = split.get((name, "b"))
        if got is None:
            got = _take_bases(bases, sel)
            split[(name, "b")] = got
            split[(name, "w")] = jnp.take(wires_of, sel) if wires_of is not None else sel
        return got, split[(name, "w")]

    def query(name, bases, nsel, wsel, wires_of):
        """One witness MSM (a/b1/c): narrow + wide class partial sums.
        wires_of maps base positions to wire ids (None = identity)."""
        accs = []
        if int(nsel.shape[0]):
            nb, nw = key_split(name + ".n", bases, nsel, wires_of)
            accs.append(_jit_msm_g1_narrow(*_pad_msm(nb, _take_planes(w_narrow, nw), g1_narrow_n)))
        if int(wsel.shape[0]):
            wb, ww = key_split(name + ".w", bases, wsel, wires_of)
            accs.append(_jit_msm_g1(*_pad_msm(wb, _take_planes(w_planes, ww), g1_wide_n)))
        return accs[0] if len(accs) == 1 else G1J.add(accs[0], accs[1])

    def query_g2(name, bases, nsel, wsel, wires_of):
        accs = []
        if int(nsel.shape[0]):
            nb, nw = key_split(name + ".n", bases, nsel, wires_of)
            accs.append(_jit_msm_g2_narrow(nb, _take_planes(w_narrow, nw)))
        if int(wsel.shape[0]):
            wb, ww = key_split(name + ".w", bases, wsel, wires_of)
            accs.append(_jit_msm_g2(wb, _take_planes(w_planes, ww)))
        return accs[0] if len(accs) == 1 else G2J.add(accs[0], accs[1])

    return (
        _msm_enqueued(watch, "msm_a", query("a", dpk.a_bases, dpk.a_nsel, dpk.a_wsel, None)),
        _msm_enqueued(watch, "msm_b1", query("b1", dpk.b1_bases, dpk.b_nsel, dpk.b_wsel, dpk.b_sel)),
        _msm_enqueued(watch, "msm_b2", query_g2("b2", dpk.b2_bases, dpk.b_nsel, dpk.b_wsel, dpk.b_sel)),
        _msm_enqueued(watch, "msm_c", query("c", dpk.c_bases, dpk.c_nsel, dpk.c_wsel, dpk.c_sel)),
        msm_h(),
    )


def _assemble(dpk: DeviceProvingKey, acc, r: int, s: int) -> Proof:
    """A proof from its accumulators (affine host points) and its blinding: the native
    library's one call where it is loaded, else the oracle (`snark/native_assemble.py`)."""
    from ..utils.metrics import REGISTRY
    proof, path = native_assemble.assemble(dpk, acc, r, s)
    REGISTRY.counter("zkp2p_assemble_total", {"path": path}).inc()
    return proof


_assemble_host = native_assemble.assemble_python  # the oracle, on Python integers: `prove_native` assembles with it


def prove_tpu(
    dpk: DeviceProvingKey,
    witness: Sequence[int],
    r: Optional[int] = None,
    s: Optional[int] = None,
) -> Proof:
    """One proof: a batch of one (the same programs at B=1, the same
    bytes — group arithmetic is exact)."""
    return prove_tpu_batch(dpk, [witness], None if r is None else [r], None if s is None else [s])[0]


# The mesh road's two programs before its MSMs, each ONE shard_map over
# the pod mesh.  They must be shard_maps, not jits over mesh-sharded
# inputs: on a real mesh JAX refuses to partition a Mosaic kernel
# automatically ("wrap the call in a shard_map" — found on the four-chip
# host, PERF.md PR 21), and every field product of the h stage is one.
# Two layouts, and the exchange between them.  The h stage wants whole
# witnesses: the proofs of a batch group (B over the mesh's "batch"
# axis) are `split` over the group's S chips where S divides them — one
# proof a chip for a chunk of four on 1x4, `_h_pod_fn` — and otherwise
# the group's chips SHARE each proof's h stage, a chip an S-th of every
# vector (a batch of one, a chunk of two on 1x4: `_h_shard_fn`, the form
# a 2^23 domain needs, where one proof's h stage fits no chip).  The MSMs
# want, on each chip, the scalars of the bases it holds, for every proof
# of the group: the exchange delivers them and recodes them (a shared h
# comes out in those columns already).  One rule for every chunk and
# mesh, and no knob; it follows from their sizes.  The sharded MSMs use
# the unsigned formulation: group
# arithmetic is exact, so the proof bytes match the one-chip road's.
def _pod_split(mesh, n_proofs: int) -> bool:
    """Whether a chunk of `n_proofs` is split over each group's chips."""
    return (n_proofs // mesh.shape["batch"]) % mesh.shape["shard"] == 0


def _pod_takes(mesh, n_proofs: int, log_m: int) -> bool:
    """Whether the mesh road can run a chunk of `n_proofs` over a 2^log_m
    domain: the groups divide the chunk, and the chunk is split or each
    group's chips divide the domain a shared h stage gives them in
    blocks (not a shard width that is no power of two, nor a toy domain
    smaller than it)."""
    n_batch, n_ici = mesh.shape["batch"], mesh.shape["shard"]
    return n_proofs % n_batch == 0 and (_pod_split(mesh, n_proofs) or (1 << log_m) % n_ici == 0)


def _pod_chunk_spec(mesh, split: bool):
    from jax.sharding import PartitionSpec as P

    return P(("batch", "shard")) if split else P("batch")


@lru_cache(maxsize=None)
def _h_pod_fn(mesh, log_m: int):
    """The h stage of a chunk split over the chips, whole proofs a chip:
    w (B, n_wires, 16) standard-form limbs -> h (B, m, 16) standard-form
    limbs, each chip its share of the chunk."""
    from types import SimpleNamespace

    from jax.sharding import PartitionSpec as P

    def local(rows, w_std):
        key = SimpleNamespace(log_m=log_m, **dict(zip(_QAP_ROWS, rows)))
        h = FR.from_mont(jax.vmap(h_evals, in_axes=(None, 0))(key, FR.to_mont(w_std)))
        # the last is `done`: one limb of h a witness, ready when the stage is (_StageWatch waits on it)
        return h, h[:, 0, 0]

    chunk = _pod_chunk_spec(mesh, True)
    return jax.jit(jax.shard_map(local, mesh=mesh, in_specs=(P(), chunk), out_specs=(chunk, chunk), check_vma=False))


def _row_blocks(dpk: "DeviceProvingKey", n_ici: int):
    """Where each chip's rows of the QAP matrices lie in the row-sorted
    entry arrays (`_rows_to_arrays` writes row after row), for a domain
    in `n_ici` blocks of rows: `((a_starts, a_most), (b_starts, b_most))`,
    `starts[c]` the first entry of block c's rows and `most` the most
    entries any block has, the static length `_h_shard_fn` slices.  Read
    off the key once and memoised on the instance like the h table."""
    memo = getattr(dpk, "_row_blocks_cache", None)
    if memo is None or memo[0] != n_ici:
        bounds = np.arange(n_ici + 1, dtype=np.int64) << dpk.log_m >> (n_ici.bit_length() - 1)

        def blocks(rows):
            starts = np.searchsorted(np.asarray(rows), bounds).astype(np.int32)
            return starts[:-1], int(np.diff(starts).max())

        memo = (n_ici, (blocks(dpk.a_row), blocks(dpk.b_row)))
        setattr(dpk, "_row_blocks_cache", memo)
    return memo[1]


def _matvec_block(coeff, wire, row, start, most: int, w_mont, first_row, n_rows: int):
    """One block of `_matvec`'s rows, `n_rows` from `first_row`, over a
    matrix whole on the chip: the block's entries are a slice of `most`
    from `start` (clamped at the arrays' end, so it may begin among an
    earlier block's entries), and an entry of another block's row is
    dropped by its row id."""
    cut = lambda x: jax.lax.dynamic_slice_in_dim(x, start, most)  # noqa: E731
    local = cut(row) - first_row
    vals = FR.mul(cut(coeff), w_mont[cut(wire)])
    return lazy_segment_sum_mod(FR, vals, jnp.where((local >= 0) & (local < n_rows), local, n_rows), n_rows)


@lru_cache(maxsize=None)
def _h_shard_fn(mesh, log_m: int, most: Tuple[int, int]):
    """The h stage of proofs their group's chips SHARE: w (B, n_wires,
    16) standard-form limbs, whole on every chip of a group -> h (B, m,
    16) standard-form limbs, a chip its block of m / S columns of each
    of its group's proofs: the block whose h bases `place_key` gave it.
    One program over the mesh (`parallel.ntt`): each chip sums its own
    block of rows of the two matvecs, the six transforms cross the chips
    once each, the quotient is pointwise.  `most`: `_row_blocks`' static
    slice lengths.  Bit-equal to `h_evals` (tests/test_h_sharded.py)."""
    from jax.sharding import PartitionSpec as P

    from ..parallel.ntt import TABLE_SPECS, intt_block_to_strided, ntt_strided_to_block, shard_tables

    n_ici = mesh.shape["shard"]
    n_rows = (1 << log_m) // n_ici

    def local(rows, starts, tables, w_std):
        chip = jax.lax.axis_index("shard")
        w_mont = FR.to_mont(w_std)
        with jax.named_scope("matvec"):
            def matvec(matrix, start, n):
                return jax.vmap(lambda w: _matvec_block(*matrix, start[chip], n, w, chip * n_rows, n_rows))(w_mont)

            a_ev, b_ev = matvec(rows[:3], starts[0], most[0]), matvec(rows[3:], starts[1], most[1])
            evals = jnp.stack([a_ev, b_ev, FR.mul(a_ev, b_ev)], axis=1)  # (B, 3, m / S, 16)
        with jax.named_scope("intt"):
            coeffs = intt_block_to_strided(evals, tables, n_ici)
        with jax.named_scope("coset_ntt"):
            cos = ntt_strided_to_block(coeffs, tables, n_ici)
        h = FR.from_mont(FR.sub(FR.mul(cos[:, 0], cos[:, 1]), cos[:, 2]))
        return h, h[:, :1, 0]  # `done`, a limb a chip: ready when the stage is

    cols = P("batch", "shard")
    program = jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(P(), P(), TABLE_SPECS, P("batch")), out_specs=(cols, cols), check_vma=False))

    def run(rows, starts, w_std):
        return program(rows, starts, shard_tables(mesh, log_m), w_std)

    run.program = program  # (rows, starts, tables, w_std): what a test lowers from shapes alone
    return run


def h_ici_bytes(mesh, n_proofs: int, log_m: int) -> int:
    """What the h stage of a chunk of `n_proofs` moves between chips,
    summed over the chips that receive it: six transforms a proof over
    the S chips of its group (`parallel.ntt.ici_bytes_a_transform`)
    where the chunk's proofs are shared, 0 where each chip computes
    whole proofs of its own."""
    from ..parallel.ntt import ici_bytes_a_transform

    return 0 if _pod_split(mesh, n_proofs) else 6 * n_proofs * ici_bytes_a_transform(log_m, mesh.shape["shard"])


@lru_cache(maxsize=None)
def _exchange_pod_fn(mesh, split: bool, n_h: int, h_window: int):
    """From the h stage's layout to the MSMs': over ICI the group's
    witnesses are all-gathered (a class's bases name any wire) and each
    chip's h goes out by `all_to_all`, so that a chip holds, for every
    proof of its group, the domain columns of its h bases; then each
    chip cuts the wires of the bases it holds, class by class (`wires`:
    a `(narrow, wide)` pair of wire ids a query, the placed key's, in
    the shards of the bases), out of the witnesses and recodes its
    columns, and only those, to SIGNED digit planes
    (`signed_digit_planes_from_limbs` at MSM_WINDOW; h at `h_window`,
    the window of the table the chip holds, `_recode`'s rule): `(mags,
    negs)`, each (B, planes, n) — every plane for a wide class and for
    h, the low NARROW_PLANES for a narrow class, whose wires' upper
    planes are provably zero (`_recode`), recoded from the low limb
    alone — the layout `msm_pod_batched` and `msm_pod_resident` consume,
    already where the bases are.  `n_h` is the placed key's padded h
    base count.  Without `split` nothing crosses: every chip of a group
    holds the group's witnesses and h whole."""
    from jax.sharding import PartitionSpec as P

    n_ici = mesh.shape["shard"]

    def planes(cols, window=MSM_WINDOW):  # (B, n, 16) -> (mags, negs), each (B, 256 / window, n)
        return tuple(jnp.moveaxis(p, 0, 1) for p in signed_digit_planes_from_limbs(cols, window))

    def narrow_planes(low):
        """(B, n) low limbs -> (mags, negs), each (B, NARROW_PLANES, n):
        the low NARROW_PLANES planes of `planes`, computed alone.  A
        recoded digit depends on the digits below it only (one above
        half a window borrows from the next), and NARROW_PLANES digits
        lie in the low limb: three are recoded, not 64 to keep three,
        and one limb of a narrow wire is gathered, not sixteen."""
        assert NARROW_PLANES * MSM_WINDOW <= 16  # the low limb's bits
        half, carry, mags, negs = 1 << (MSM_WINDOW - 1), 0, [], []
        for k in range(NARROW_PLANES):  # least significant first
            e = ((low >> (MSM_WINDOW * k)) & (2 * half - 1)) + carry
            neg = e > half
            mags.append(jnp.where(neg, 2 * half - e, e))
            negs.append(neg)
            carry = neg.astype(low.dtype)
        return jnp.stack(mags[::-1], axis=1), jnp.stack(negs[::-1], axis=1)  # most significant first

    def local(wires, w_std, h_std):
        if split:
            h_std = jnp.pad(h_std, [(0, 0), (0, n_h - h_std.shape[1]), (0, 0)])
            w_std = jax.lax.all_gather(w_std, "shard", axis=0, tiled=True)
            # each chip's S-th of the columns to the chip that holds their bases, the split
            # axis leading (split along the columns in place, the same all_to_all compiles
            # for two minutes at 2^19 and plans half a gigabyte: PERF.md, PR 32)
            b_loc, n_loc = h_std.shape[0], n_h // n_ici
            h_mine = jax.lax.all_to_all(
                h_std.reshape(b_loc, n_ici, n_loc, 16).swapaxes(0, 1), "shard", split_axis=0, concat_axis=0, tiled=True,
            ).reshape(n_ici * b_loc, n_loc, 16)
        else:
            h_mine = h_std
        with jax.named_scope("recode"):
            low = w_std[..., 0]
            queries = tuple(
                (narrow_planes(jnp.take(low, narrow, axis=1)), planes(jnp.take(w_std, wide, axis=1)))
                for narrow, wide in wires)
            h = planes(h_mine, h_window)
        return queries, h, h[0][:1, 0, 0]  # the last is `done`, a digit a chip: ready when the stage is

    chunk, cols = _pod_chunk_spec(mesh, split), P("batch", None, "shard")
    return jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(P("shard"), chunk, chunk if split else P("batch", "shard")),
        out_specs=(cols, cols, P(("batch", "shard"))), check_vma=False,
    ))


def exchange_bytes(mesh, n_proofs: int, n_wires: int, n_h: int) -> int:
    """What the exchange of a chunk of `n_proofs` moves between chips,
    summed over the chips that receive it: each of a group's S chips
    takes the other S-1 chips' witnesses whole and an S-th of their h
    (`n_h` columns, padded), 64 B a scalar; 0 where the chunk is not
    split."""
    n_ici = mesh.shape["shard"]
    if not _pod_split(mesh, n_proofs):
        return 0
    return n_proofs * (n_ici - 1) * (n_wires + n_h // n_ici) * 64


def _prove_batch_sharded(dpk: DeviceProvingKey, limbs: np.ndarray, mesh, watch: Optional[_StageWatch] = None):
    """One prove_tpu_batch chunk on a ("batch", "shard") pod mesh, over
    a key placed on it (`place_key`): nothing of the key moves.  The
    chunk's witnesses, `limbs` (B, n_wires, 16) standard-form on the
    host, are uploaded once each to the chip that computes their h
    (`_h_pod_fn`: the group's proofs spread over its chips where they
    divide, `proofs_a_chip` on the stage's span), or whole to every
    chip of a group that shares each proof's h stage (`_h_shard_fn`,
    where they do not: `h_shards` and `ici_bytes` on the span, and
    `zkp2p_h_ici_bytes_total`); the exchange
    (`_exchange_pod_fn`, a stage of its own, `bytes` over ICI) leaves
    on every chip the signed digit planes of the columns whose bases it
    holds, class by class; and every MSM runs base-axis-sharded over
    the inner "shard" axis, a shard's MSM the one-chip road's
    (`ops.msm.msm_plane_sums`: `msm_windowed_signed`'s table and
    accumulate): each of the four witness MSMs over the
    key's narrow class (its low NARROW_PLANES planes, at
    `pod_narrow_lanes`) and its wide class (every plane, at
    `pod_lanes`), the two partial sums added on the chip, then
    ONE group-op allreduce a query (all_gather + projective fold — ICI
    on real hardware, host rings on the virtual CPU mesh;
    parallel.mesh.msm_pod_batched).  The h MSM reads the placed key's
    resident table, each chip the multiples of its own shard of the h
    bases (`_h_table`; `parallel.mesh.msm_pod_resident`: h recoded at
    the table's window, `msm_resident`'s accumulate, the same
    allreduce), and where the window rule gives the placement none it is
    a wide class of its own, its multiples in the scan.  a, b1 and c
    share one program (`place_key` pads their classes to one count), b2
    and h have one each.  Seven stages, each span with `mesh`; the four
    query spans say `narrow` and `wide`, the bases a chip holds in each
    class (padding included; `narrow` 0 for a key without widths), all
    five MSM spans `digits` ("signed"), and `msm_h` its `window` and
    `table` (`resident` | `scan`) as on one chip.  Returns the same five
    (B,)-batched accumulators `_prove_device` emits, so chunks from
    either arm concatenate identically downstream."""
    from jax.sharding import NamedSharding

    from ..parallel.mesh import msm_pod_batched, msm_pod_resident

    from ..utils.metrics import REGISTRY

    n_ici, on = mesh.shape["shard"], mesh_name(mesh)
    n_proofs, n_wires = limbs.shape[0], limbs.shape[1]
    split = _pod_split(mesh, n_proofs)
    w_std = jax.device_put(limbs, NamedSharding(mesh, _pod_chunk_spec(mesh, split)))
    if watch is not None:
        watch.uploaded(w_std, limbs.nbytes)
    rows = tuple(getattr(dpk, f) for f in _QAP_ROWS)
    if split:
        h_std, done = _h_pod_fn(mesh, dpk.log_m)(rows, w_std)
        _enqueued(watch, "h_planes", done, ntt=NTT_LADDER, mesh=on, proofs_a_chip=n_proofs // mesh.size)
    else:
        (a_starts, a_most), (b_starts, b_most) = _row_blocks(dpk, n_ici)
        h_std, done = _h_shard_fn(mesh, dpk.log_m, (a_most, b_most))(rows, (a_starts, b_starts), w_std)
        crossed = h_ici_bytes(mesh, n_proofs, dpk.log_m)
        # a chip takes part in every proof of its group, a share of each
        _enqueued(watch, "h_planes", done, ntt=NTT_LADDER, mesh=on, proofs_a_chip=n_proofs // mesh.shape["batch"],
                  h_shards=n_ici, ici_bytes=crossed)
        REGISTRY.counter("zkp2p_h_ici_bytes_total").inc(crossed)
    n_h, a_group = dpk.h_bases[0].shape[0], n_proofs // mesh.shape["batch"]
    h_table = _h_table(dpk)  # this key's, in shards: built by the batch's own `_h_table` call, before `dispatch`
    h_window = MSM_WINDOW if h_table is None else int(h_table.shape[1]).bit_length()
    queries = tuple(getattr(dpk, q + "_bases") for q in _POD_QUERIES)  # a (narrow, wide) pair each, (x, y, wire) a class
    w_planes, h_planes, done = _exchange_pod_fn(mesh, split, n_h, h_window)(
        tuple(tuple(cls[2] for cls in pair) for pair in queries), w_std, h_std)
    _enqueued(watch, "exchange", done, mesh=on, bytes=exchange_bytes(mesh, n_proofs, n_wires, n_h))
    del w_std, h_std  # the MSMs read the planes alone

    def msm(name, curve, classes, **attrs):
        """One pod MSM, enqueued, over `classes`: (bases, planes, lanes)
        each; a class the key has no base in takes no part."""
        bases, planes, lanes = zip(*(cls for cls in classes if cls[0][0].shape[0]))
        return _msm_enqueued(watch, name, msm_pod_batched(
            curve, bases, planes, mesh, dcn_axis="batch", ici_axis="shard", lanes=lanes, window=MSM_WINDOW,
        ), mesh=on, digits="signed", **attrs)

    def query(q, pair, planes):
        """A witness MSM: the key's narrow class at its low planes and
        wide steps, the wide class at every plane, one sum a chip."""
        n_narrow, n_wide = (cls[0].shape[0] for cls in pair)
        return msm("msm_" + q, G2J if q == "b2" else G1J, (
            (pair[0][:2], planes[0], pod_narrow_lanes(n_narrow, n_ici, a_group, _narrow_cap(q))),
            (pair[1][:2], planes[1], pod_lanes(n_wide, n_ici, a_group)),
        ), narrow=n_narrow // n_ici, wide=n_wide // n_ici)

    def msm_h():
        """The h stage, enqueued: each chip against the table of its
        shard (a select and one `add_mixed` a plane a step: `add` says
        `mixed`); where no table fits, the multiples in the scan, as a
        wide class."""
        if h_table is None:
            return msm("msm_h", G1J, ((dpk.h_bases, h_planes, pod_lanes(n_h, n_ici, a_group)),), window=MSM_WINDOW, table="scan")
        return _msm_enqueued(
            watch, "msm_h", msm_pod_resident(G1J, h_table, h_planes, mesh, dcn_axis="batch", ici_axis="shard"),
            mesh=on, digits="signed", window=h_window, table="resident", add="mixed")

    return tuple(query(*args) for args in zip(_POD_QUERIES, queries, w_planes)) + (msm_h(),)


# What the device waited for between two batches, by what the thread that
# feeds it was doing (`tpu/prove_batch/device_idle`): each cause, in the order
# its span is written, with the spans of that thread it sums (their last path
# elements, as `utils.trace.thread_tally` keys them).  `other` is the rest.
IDLE_CAUSES: Dict[str, Tuple[str, ...]] = {
    "finish": ("finish",),  # tpu/prove_batch/finish
    "verify": ("verify",),  # service/verify
    "emit": ("emit",),  # service/emit
    "handover": ("handover", "sweep"),  # service/handover, and service/sweep's own time
    "poll": ("poll",),  # service/poll
    "starved": ("starved",),  # service/starved
    "prep": ("prep",),  # tpu/prove_batch/prep
}
# the causes that are work, not a wait the thread chose: what of them was not
# on a CPU is `device_idle/offcpu`
_IDLE_WORK = ("finish", "verify", "emit", "prep", "handover")
# a key's placement -> (the thread that fed it last, when that batch's last
# stage was ready, that thread's tally and CPU clock as it learned so)
_fed_last: Dict[Any, Tuple[threading.Thread, float, Dict[str, Tuple[float, float]], float]] = {}


def _idle_since_fed(placement, t_fed: float):
    """The gap a batch closes as its `device` span opens at `t_fed`: since
    this thread's last batch on `placement` left the device empty.  None for
    a placement's first batch and for one another thread fed last (a warm-up
    batch before a service's first): no one thread's spans account for that."""
    from ..utils.trace import thread_tally

    last = _fed_last.pop(placement, None)
    if last is None or last[0] is not threading.current_thread():
        return None
    _, t_ready, tally0, cpu0 = last
    return t_ready, t_fed, tally0, thread_tally(), (time.thread_time() - cpu0) * 1e3


def _record_idle(gap, n: int) -> None:
    """One `device_idle` span from the gap `_idle_since_fed` read, `n` the
    batch it precedes, and under it one span a cause, zero included (a mean
    over spans is a mean over batches), laid end to end from the gap's start
    in `IDLE_CAUSES`' order then `other`: an account of the gap, not when
    each happened.  `ms` and `cpu_ms` are the feeding thread's self time in
    that cause's spans inside the gap; `other` is what is left of each (time
    in no span, and in spans that are no cause or still open), so the eight
    partition the gap.  `offcpu`, beside them and no part of the sum, is
    what the thread spent in `_IDLE_WORK`'s spans off a CPU."""
    from ..utils.trace import record

    t_ready, t_fed, tally0, tally1, cpu_ms = gap
    none = (0.0, 0.0)
    parts = {
        cause: tuple(sum(tally1.get(nm, none)[i] - tally0.get(nm, none)[i] for nm in names) for i in (0, 1))
        for cause, names in IDLE_CAUSES.items()
    }
    parts["other"] = ((t_fed - t_ready) * 1e3 - sum(ms for ms, _ in parts.values()),
                      cpu_ms - sum(cpu for _, cpu in parts.values()))
    account = {"tally": False, "tid": None}  # the device's time, and sums: no interval of this thread
    idle = record("device_idle", t_ready, t_fed, cpu_ms=round(cpu_ms, 3), n=n, **account)
    t = t_ready
    for cause, (ms, cpu) in parts.items():
        record("device_idle/" + cause, t, t + ms / 1e3, cpu_ms=round(cpu, 3), parent=idle, **account)
        t += ms / 1e3
    offcpu = sum(parts[c][0] - parts[c][1] for c in _IDLE_WORK)
    record("device_idle/offcpu", t_ready, t_ready + offcpu / 1e3, parent=idle, **account)


def _batch_chunk_size(log_m: Optional[int] = None, device=None, mesh=None) -> int:
    """Sub-batch size for prove_tpu_batch; 0 = whole batch in one vmap.

    "auto" chunks only on a real TPU, and there the chunk is a function
    of the key's size and the device's memory (`batch_chunk_for`): the
    batched pipeline's peak HBM is linear in the vmapped batch and in
    the domain — 1,253 B a domain point a proof and 877 B a point of key
    at the 499k venmo shape (PERF_LEDGER.jsonl, PR 24), so a chunk of
    four peaks at 3.1 GB at 2^19 and would plan 29 GB at 2^22 against
    the v5e's 15.75 G.  Four up to 2^20, two at 2^21, one from 2^22 on a
    16 GB chip; every chunk reuses ONE compiled executable.  Without a
    key (`log_m` None: preflight arms the gate before any key is loaded)
    the answer is the largest chunk.  For a batch on the mesh road
    (`mesh`) the same rule plans the fullest chip's share of the key as
    `place_key` lays it on that mesh and of the chunk (four at 2^22 on
    1x4, one at 2^23), and a key that fits no chunk raises
    `KeyDoesNotFit`.  ZKP2P_BATCH_CHUNK, when a number, overrides the
    rule."""
    auto = 0
    if _on_tpu():
        shape = (1, 1) if mesh is None else (mesh.shape["batch"], mesh.shape["shard"])
        auto = BATCH_CHUNK_MAX if log_m is None else batch_chunk_for(log_m, _hbm_bytes_limit(device), *shape)
    if BATCH_CHUNK == "auto":
        v = auto
    else:
        try:
            v = max(0, int(BATCH_CHUNK))
        except ValueError:
            # a malformed knob must not silently select the unchunked
            # (OOM-prone) behavior the knob exists to prevent — keep the
            # auto rule
            v = auto
    _record_arm("batch_chunk", str(v))
    return v


def prove_tpu_batch(
    dpk: DeviceProvingKey,
    witnesses: Sequence[Sequence[int]],
    rs: Optional[Sequence[int]] = None,
    ss: Optional[Sequence[int]] = None,
) -> List[Proof]:
    """vmap the full device pipeline over a batch of witnesses (the
    batch=64 configuration in BASELINE.json).  `rs`/`ss` pin the
    per-proof blinding scalars (same signature as prove_native_batch):
    with them the batch emits byte for byte what prove_native /
    prove_host emit for the same (witness, r, s).

    A witness that is, or carries, its standard-form (n, 4) u64 rows
    (`_witness_rows`: the `u64` both builders of `snark.r1cs` attach,
    which the service's self-check has just held to the constraints) is
    proved AS those rows; any other sequence a wire at a time through
    `int(w) % R`.  The `prep` span's `witness_form` says which.

    Large batches run as shape-stable sub-chunks (see _batch_chunk_size;
    the last chunk pads by repeating its final witness) so device memory
    is bounded by the chunk, not the batch, and every chunk reuses the
    same compiled executable.

    With ZKP2P_TPU_SHARD=on (and a satisfiable ZKP2P_TPU_MESH) each
    chunk runs the pod-mesh programs instead (_prove_batch_sharded),
    over the key as `place_key` lays it on the mesh (done by the first
    such call of a key, unless the key came placed): batch data-parallel
    over the mesh's "batch" axis, MSM bucket partial sums allreduced
    over "shard".  The arm is decided ONCE per call —
    a chunk size indivisible by the mesh's batch width, or a chunk whose
    proofs a group's chips would share (`_h_shard_fn`) over a domain
    those chips do not divide, records the `tpu_shard` arm as "fallback"
    and the whole call takes the vmap path, so every chunk of a call
    shares one executable either way.

    What the device waited before this batch is written as a span of this
    one (`device_idle`, `_record_idle`), per key placement and feeding
    thread.  A batch of several chunks that waits one chunk out before it
    enqueues the next (`chunk < BATCH_CHUNK_MAX`) leaves the device empty
    between them too: that wait is inside `device`, and `chunk_wait`
    is its span (one a chunk after the first, `chunk` on it): from the
    instant the last stage of the chunk before was ready to this chunk's
    first stage being enqueued, so the wait's residue, the upload and
    `to_mont`'s dispatch.  `zkp2p_prove_chunks_total` counts the chunks
    proved, one padded by repeats included."""
    from ..utils.audit import sample_device_memory
    from ..utils.metrics import REGISTRY
    from ..utils.trace import thread_tally, trace

    # The road, decided once a call; the mesh road reads the key as
    # `place_key` lays it on the mesh, which the first batch of a key
    # does here, before the batch's own span (`tpu/place_key`).
    n = len(witnesses)
    key_dev = key_device(dpk)

    def in_chunks(chunk: int):
        if chunk <= 0 or n <= chunk:
            return [list(witnesses)]
        spans = [list(witnesses[i : i + chunk]) for i in range(0, n, chunk)]
        spans[-1] += [spans[-1][-1]] * (chunk - len(spans[-1]))
        return spans

    mesh = _shard_mesh()
    chunk = _batch_chunk_size(dpk.log_m, key_dev, mesh)
    spans = in_chunks(chunk)
    if mesh is not None and not _pod_takes(mesh, len(spans[0]), dpk.log_m):
        _record_arm("tpu_shard", "fallback")
        mesh = None
        chunk = _batch_chunk_size(dpk.log_m, key_dev)
        spans = in_chunks(chunk)
    if mesh is not None:
        dpk = _key_on_mesh(dpk, mesh)
    # Spans (utils.trace): `prep`, `device` and `finish` partition
    # `tpu/prove_batch`.  `device` runs from the batch's first enqueue to
    # the instant its last stage's result is ready; `dispatch` and one
    # span per device stage (per chunk, _StageWatch) lie inside it, and
    # one `upload` a chunk.  `device_idle`, written under `device` once the
    # batch is enqueued, is the time BEFORE it: from the last stage of this
    # thread's last batch on the same placement to this `device`'s start.
    placement = mesh_name(mesh) if mesh is not None else key_dev
    with trace("tpu/prove_batch", n=len(witnesses), log_m=dpk.log_m) as batch_span:
        with trace("prep") as prep:
            sample_device_memory("tpu/prove_batch")  # entry watermark
            # which form each witness arrives in, observed (`_witness_rows`);
            # one that is in the batch again (padding) is looked at once
            forms: Dict[str, int] = {}
            for wit in {id(w): w for w in witnesses}.values():
                rows = _witness_rows(wit)
                _check_inferred_widths(dpk, wit, w_std=rows)
                form = "ints" if rows is None else "rows"
                forms[form] = forms.get(form, 0) + 1
            prep.update(witness_form="mixed" if len(forms) > 1 else "".join(forms))
            for form, seen in forms.items():
                REGISTRY.counter("zkp2p_prove_witness_form_total", {"form": form}).inc(seen)
            # the size chosen (0: the whole batch as one) and how many ran
            batch_span.update(chunk=chunk, n_chunks=len(spans))
            limbs = _chunk_limbs(spans[0])
        with trace("device", leaf=True) as device:
            gap = _idle_since_fed(placement, device["t0"])
            _h_table(dpk)  # the first batch of a key builds it, on either road: one `tpu/prove_batch/h_table` span
            watch = _StageWatch(device["t0"])
            try:
                with trace("dispatch"):
                    parts = []
                    for i, span in enumerate(spans):
                        if i:
                            limbs = _chunk_limbs(span)
                        wait = contextlib.nullcontext()
                        if i and chunk < BATCH_CHUNK_MAX:
                            # fewer than four at a time: the device's memory is the
                            # ceiling, and a chunk enqueued behind another has its
                            # buffers planned beside it — wait the last one out
                            jax.block_until_ready(parts[-1])
                            wait = trace("chunk_wait", t0=watch.settled(), chunk=i)
                        watch.chunk = i
                        with wait:
                            if mesh is None:
                                # one batched to_mont per chunk (not one device dispatch per
                                # witness); the h_planes stage includes it; beside a pinned key
                                w = FR.to_mont(watch.uploaded(
                                    jnp.asarray(limbs) if key_dev is None else jax.device_put(limbs, key_dev), limbs.nbytes))
                        if mesh is not None:  # the mesh road puts the chunk to its chips itself
                            parts.append(_prove_batch_sharded(dpk, limbs, mesh, watch))
                        else:
                            parts.append(_prove_device(dpk, w, watch=watch))
                        # sub-chunk HBM watermark: the batched pipeline's peak is
                        # linear in the vmapped chunk (r5: 15.75 G OOM at batch=16
                        # with no telemetry) — sample per chunk so the staircase is
                        # on record BEFORE the allocator walks off the top
                        sample_device_memory("tpu/prove_batch_chunk")
                    accs = (
                        parts[0]
                        if len(parts) == 1
                        else jax.tree_util.tree_map(lambda *xs: jnp.concatenate(xs, axis=0), *parts)
                    )
                if gap is not None:
                    _record_idle(gap, n)  # here, with the batch enqueued: the device waits for none of it
                # these go to the host while the device is on a later stage
                a, b1, c = (g1_jac_to_host(accs[i]) for i in (0, 1, 3))
            finally:
                watch.close()  # the last stage's result is ready: the device has nothing left
            if watch.t_ready is not None:
                _fed_last[placement] = (threading.current_thread(), watch.t_ready, thread_tally(), time.thread_time())
        with trace("finish", assemble=native_assemble.path_for()):
            hq = g1_jac_to_host(accs[4])
            b2 = g2_jac_to_host(accs[2])
            proofs = [
                _assemble(
                    dpk, (a[i], b1[i], b2[i], c[i], hq[i]),
                    rs[i] if rs is not None else 1 + secrets.randbelow(R - 1),
                    ss[i] if ss is not None else 1 + secrets.randbelow(R - 1),
                )
                for i in range(len(witnesses))
            ]
            sample_device_memory("tpu/prove_batch")  # exit watermark: batch HBM peak
    REGISTRY.counter("zkp2p_proves_total", {"prover": "tpu"}).inc(len(witnesses))
    REGISTRY.counter("zkp2p_prove_chunks_total").inc(len(spans))
    return proofs
