"""The TPU Groth16 prover: witness limbs in, proof points out.

This is the `prover=tpu` backend the build exists for (BASELINE.json
north star) — the drop-in for snarkjs `groth16 prove` /
rapidsnark (`dizkus-scripts/5_gen_proof.sh`, `6_gen_proof_rapidsnark.sh`):
same zkey material + witness in, same proof out, verified by the same
pairing equation (`contracts/Verifier.sol:340-380`).

Dataflow (one jitted program, SURVEY.md §7 step 6):

  witness w (mont limbs, n_wires x 16)
    ├─ Az/Bz/Cz: gather coeffs -> Montgomery mul -> modular segment-sum
    │  over rows (the sparse matvec; zero scatter)
    ├─ H: iNTT -> coset shift -> NTT -> (a·b - c)·Z⁻¹ -> iNTT -> unshift
    └─ 4 G1 MSMs + 1 G2 MSM over bit planes (ops.msm)
  host: the ~10 scalar ops that blind with (r, s) and assemble (A, B, C)

Determinism contract: given the same (witness, r, s) this emits the exact
proof `snark.groth16.prove_host` does — the two provers are diffed
point-by-point in tests, the same way the reference pins a known-good
proof vector in `test/ramp.test.js:193-196`.

Batching: `prove_tpu_batch` vmaps the whole pipeline over independent
witnesses sharing one key — the reference has no analog (browser proves
one email at a time); this is the TPU data-parallel axis.
"""

from __future__ import annotations

import queue
import secrets
import threading
import time
from dataclasses import dataclass
from functools import lru_cache
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..curve.host import G1Point, G2Point, g1_add, g1_mul, g1_neg, g2_add, g2_mul
from ..curve.jcurve import (
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
    digit_planes_from_limbs,
    glv_extend_bases,
    glv_sel,
    glv_signed_planes_from_limbs,
    msm_resident,
    msm_windowed,
    msm_windowed_signed,
    resident_table,
    signed_digit_planes_from_limbs,
)
from ..ops.ntt import LADDER as NTT_LADDER, coset_shift, intt, ntt

# All tier knobs resolve through the ONE typed config (utils.config:
# default -> env, with provenance); the module constants
# below are its import-time snapshot — jit identities depend on them,
# so they are process-lifetime like the config itself.
#
# MSM_WINDOW: 4-bit digits -> ~78 point-adds per base instead of the 256
#   of the bit-plane formulation; w=8 halves accumulate work at the
#   price of a 254-add per-chunk table, worth it vmapped.
# MSM_SIGNED: signed digit recoding (default on) — the per-chunk
#   multiples table halves because a negative digit is (x, -y) for free.
# MSM_UNIFIED ("auto" = on for a real TPU backend): pad the a/b1/c/h
#   MSM inputs to one common base count so all four share ONE compiled
#   executable (each cold TPU MSM compile measured ~2 min).
# MSM_AFFINE: batch-affine accumulate tier (ops.msm_affine) — hardware-
#   gated until the on-chip A/B proves it.
# MSM_H: "windowed" or "bucket" (ops.msm_bucket sorted-prefix
#   Pippenger) — hardware-gated like MSM_AFFINE.
from ..utils.jaxcfg import on_tpu as _on_tpu
from ..utils.audit import record_arm as _record_arm
from ..utils.config import load_config as _load_config

_CFG = _load_config()
MSM_WINDOW = _CFG.msm_window
MSM_SIGNED = _CFG.msm_signed
MSM_UNIFIED = _CFG.msm_unified
MSM_AFFINE = _CFG.msm_affine
MSM_H = _CFG.msm_h
MSM_GLV = _CFG.msm_glv
BATCH_CHUNK = _CFG.batch_chunk
H_BUCKET_WINDOW = 16

from ..snark.groth16 import Proof, ProvingKey, coset_gen, domain_size_for, qap_rows
from ..snark.r1cs import ConstraintSystem
def _unified() -> bool:
    return _record_arm("msm_unified", MSM_UNIFIED == "1" or (MSM_UNIFIED == "auto" and _on_tpu()))


def _affine() -> bool:
    return _record_arm("msm_affine", MSM_AFFINE == "1" or (MSM_AFFINE == "auto" and _on_tpu()))


def _h_bucket() -> bool:
    v = MSM_SIGNED and (MSM_H == "bucket" or (MSM_H == "auto" and _on_tpu()))
    _record_arm("msm_h", "bucket" if v else "windowed")
    return v


def _glv() -> bool:
    """GLV endomorphism decomposition for the G1 MSMs (ZKP2P_MSM_GLV).
    Rides the signed-digit machinery, so MSM_SIGNED off disables it —
    the unsigned path stays the byte-stable fallback."""
    return _record_arm("msm_glv", MSM_GLV and MSM_SIGNED)


# The h MSM's window multiples live in a table resident with the key
# (ops.msm.resident_table) where the device can hold it: h_bases depend
# on the key alone, so the table is built once and the window is a
# function of what the key's size lets the chip hold — w=8 (32 planes,
# 128 multiples a base), else w=4 (64 planes, 8 multiples), else the
# in-scan table of `_msm_g1`.  The batch chunk is a function of the same
# two things: as many proofs at a time, up to four, as the device holds
# beside the key.  Both rules plan `work_bytes_a_point(chunk)` a domain
# point — the key and `chunk` proofs' working set — under
# HBM_PLAN_FRACTION of the device's memory.
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


def work_bytes_a_point(chunk: int) -> int:
    """Device bytes a domain point that the key and `chunk` proofs in
    flight are planned to take."""
    return KEY_BYTES_A_POINT + chunk * PROOF_BYTES_A_POINT


def batch_chunk_for(log_m: int, bytes_limit: int) -> int:
    """Proofs a chunk of `prove_tpu_batch` for a key of 2^log_m domain
    points on a device of `bytes_limit`: BATCH_CHUNK_MAX where that many
    working sets fit beside the key, half as many while they do not, and
    never fewer than one."""
    chunk = BATCH_CHUNK_MAX
    while chunk > 1 and work_bytes_a_point(chunk) << log_m > HBM_PLAN_FRACTION * bytes_limit:
        chunk //= 2
    return chunk


def h_table_window(log_m: int, entry_bytes: int, bytes_limit: int, chunk: int = BATCH_CHUNK_MAX) -> Optional[int]:
    """The widest signed window whose multiples table (2^(w-1) entries
    of `entry_bytes` a base, 2^log_m bases) fits a device of
    `bytes_limit` beside the key and a chunk of `chunk` proofs; None:
    neither does."""
    for window in (8, 4):
        if ((entry_bytes << (window - 1)) + work_bytes_a_point(chunk)) << log_m <= HBM_PLAN_FRACTION * bytes_limit:
            return window
    return None


@lru_cache(maxsize=None)
def _hbm_bytes_limit() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("bytes_limit") or NOMINAL_HBM_BYTES)


def _h_table_window(log_m: int) -> Optional[int]:
    """The window at which this process keeps a resident h table for a
    key of 2^log_m domain points; None: the h MSM takes today's road
    (the table does not fit, or an arm gives the h planes another
    layout).  Static under jit: `_recode` and `_prove_device` agree."""
    if not MSM_SIGNED or _h_bucket() or _glv():
        return None
    limit = _hbm_bytes_limit()
    # off a TPU `auto` does not chunk (0): plan the chunk a TPU would take
    chunk = _batch_chunk_size(log_m) or batch_chunk_for(log_m, limit)
    return h_table_window(log_m, RESIDENT_ENTRY_BYTES, limit, chunk)


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
    _record_arm("tpu_shard", f"{b}x{s}")
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


def _rows_to_arrays(rows: Sequence[dict], m: int) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Sparse QAP rows -> (coeff mont limbs, wire ids, row ids).  The
    coefficient conversion is the vectorized bytes->limbs path — at
    venmo-scale nnz counts a per-element limb loop costs minutes."""
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
        jnp.asarray(FR.array_to_mont_host_fast(vals)),
        jnp.asarray(np.array(wires, dtype=np.int32)),
        jnp.asarray(np.array(row_ids, dtype=np.int32)),
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


def _is_u64_witness(witness) -> bool:
    """The (n, 4) uint64 standard-form limb layout (the .bench_cache
    witness format, prove_native's view) — the only ndarray form the
    vectorized paths and _check_inferred_widths' w_std view accept."""
    return (
        isinstance(witness, np.ndarray)
        and witness.dtype == np.uint64
        and witness.ndim == 2
        and witness.shape[-1] == 4
    )


_R_U64 = np.frombuffer(R.to_bytes(32, "little"), dtype="<u8").copy()


def _check_u64_reduced(rows: np.ndarray) -> None:
    """Reject (n, 4)-u64 witness rows >= R.  The fast path trusts its
    input to already be reduced (the .bench_cache contract) — an
    unreduced row would silently emit a wrong Montgomery form and an
    unverifiable proof, so the boundary asserts it (8 vectorized
    compares; negligible next to to_mont)."""
    ge = np.zeros(rows.shape[0], dtype=bool)
    eq = np.ones(rows.shape[0], dtype=bool)
    for j in range(3, -1, -1):
        col = rows[:, j]
        ge |= eq & (col > _R_U64[j])
        eq &= col == _R_U64[j]
    ge |= eq  # exactly R is unreduced too
    if ge.any():
        i = int(np.flatnonzero(ge)[0])
        raise ValueError(
            f"witness row {i} is not reduced below the Fr modulus: the "
            f"(n, 4)-u64 fast path requires canonical scalars (< R); "
            f"reduce mod R before witness_to_device"
        )


def _witness_std_limbs(witness) -> np.ndarray:
    """Host witness (int sequence or (n, 4) u64 limb rows) -> (n, 16)
    u32 standard-form 16-bit limbs, fully vectorized (one C-speed bytes
    pack + a numpy view; never a per-wire Python bigint loop)."""
    from ..native.lib import _scalars_to_u64, _u64_to_limbs16

    if not _is_u64_witness(witness):
        witness = _scalars_to_u64([int(w) % R for w in witness])
    else:
        _check_u64_reduced(witness)
    return _u64_to_limbs16(witness)


def witness_to_device(witness) -> jnp.ndarray:
    """Host witness -> Montgomery limb matrix (n_wires, 16): the
    vectorized standard-form limbs plus ONE device to_mont mul."""
    return FR.to_mont(jnp.asarray(_witness_std_limbs(witness)))


def _matvec(coeff, wire, row, w_mont, m):
    vals = FR.mul(coeff, w_mont[wire])
    return lazy_segment_sum_mod(FR, vals, row, m)


def abc_evals(dpk: DeviceProvingKey, w_mont: jnp.ndarray):
    """Az/Bz/Cz evaluations on the domain: the sparse-matvec stage shared
    by the single-chip and sharded H ladders (and vmapped over the batch
    axis by the dryrun's data-parallel step)."""
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


def _h_and_planes(dpk: DeviceProvingKey, w_mont: jnp.ndarray):
    h = h_evals(dpk, w_mont)
    with jax.named_scope("recode"):
        return _recode(dpk, w_mont, h)


def _recode(dpk: DeviceProvingKey, w_mont: jnp.ndarray, h: jnp.ndarray):
    if MSM_SIGNED:
        w_std = FR.from_mont(w_mont)
        h_window = H_BUCKET_WINDOW if _h_bucket() else (_h_table_window(dpk.log_m) or MSM_WINDOW)
        if _glv():
            # G1 planes in the GLV-doubled column layout (k1 digits for
            # P_i, k2 digits for phi(P_i)): HALF the digit planes over
            # twice the columns.  The G2 MSM has no cheap endomorphism
            # here, so it keeps full-width signed planes — but ONLY for
            # the b_sel wires it can consume (recoding all n_wires just
            # for b2 would materialize ~65 planes x n_wires per proof);
            # its columns are therefore b_sel POSITIONS, not wire ids.
            w_mags, w_negs = glv_signed_planes_from_limbs(w_std, MSM_WINDOW)
            g2_planes = signed_digit_planes_from_limbs(
                jnp.take(w_std, dpk.b_sel, axis=-2), MSM_WINDOW
            )
            h_mags, h_negs = glv_signed_planes_from_limbs(FR.from_mont(h), h_window)
            if int(dpk.a_nsel.shape[0]) > 0:
                n4_mags, n4_negs = signed_digit_planes_from_limbs(w_std, 4)
                narrow = (n4_mags[-NARROW_PLANES:], n4_negs[-NARROW_PLANES:])
            else:
                narrow = ()
            return ((w_mags, w_negs), narrow, g2_planes), (h_mags, h_negs)
        w_mags, w_negs = signed_digit_planes_from_limbs(w_std, MSM_WINDOW)
        h_mags, h_negs = signed_digit_planes_from_limbs(FR.from_mont(h), h_window)
        # Narrow-class planes: witness wires with width bounds <= 2^11
        # only populate the last NARROW_PLANES signed w=4 digits — the
        # upper 61 planes are provably zero and never reach an MSM.
        # Keys with no narrow class (zkey import) skip the w=4 recode
        # entirely — shapes are static under jit, so this prunes at
        # trace time.
        if int(dpk.a_nsel.shape[0]) > 0:
            n4_mags, n4_negs = signed_digit_planes_from_limbs(w_std, 4)
            narrow = (n4_mags[-NARROW_PLANES:], n4_negs[-NARROW_PLANES:])
        else:
            narrow = ()
        return ((w_mags, w_negs), narrow), (h_mags, h_negs)
    return (
        digit_planes_from_limbs(FR.from_mont(w_mont), MSM_WINDOW),
        digit_planes_from_limbs(FR.from_mont(h), MSM_WINDOW),
    )


def _msm_g1(bases, planes):
    # lanes from the static base count: wide steps keep the VPU batch
    # large (TPU ops are latency-bound at small batches — see
    # ops.msm.default_lanes).
    lanes = default_lanes(bases[0].shape[0])
    if MSM_SIGNED:
        return _signed_windowed(G1J, bases, planes, lanes, MSM_WINDOW)
    return msm_windowed(G1J, bases, planes, lanes=lanes, window=MSM_WINDOW)


def _signed_windowed(curve, bases, planes, lanes, window):
    """Signed windowed MSM with the accumulate-tier selector: batch
    affine (ops.msm_affine) when armed, Jacobian otherwise."""
    mags, negs = planes
    if _affine():
        from ..ops.msm_affine import msm_windowed_affine

        return msm_windowed_affine(curve, bases, mags, negs, lanes=lanes, window=window)
    return msm_windowed_signed(curve, bases, mags, negs, lanes=lanes, window=window)


def _msm_g1_narrow(bases, planes):
    # 3-plane signed w=4 MSM for width-bounded wires: ~3.5 adds/pt at
    # batch=16 vs ~40 on the wide path.  Wider lanes keep the per-step
    # batch (NARROW_PLANES x lanes) off the latency floor.
    return _signed_windowed(
        G1J, bases, planes, default_lanes(bases[0].shape[0], cap=16384), 4
    )


def _msm_g2_narrow(bases, planes):
    return _signed_windowed(
        G2J, bases, planes, default_lanes(bases[0].shape[0], cap=4096), 4
    )


def _msm_g2(bases, planes):
    lanes = default_lanes(bases[0].shape[0], cap=2048)
    if MSM_SIGNED:
        return _signed_windowed(G2J, bases, planes, lanes, MSM_WINDOW)
    return msm_windowed(G2J, bases, planes, lanes=lanes, window=MSM_WINDOW)


def _msm_h(bases, planes):
    """The h MSM: full-width coset-quotient scalars, the dominant prover
    cost — routed to the sorted-prefix bucket formulation when armed."""
    if _h_bucket():
        from ..ops.msm_bucket import msm_bucket_affine

        mags, negs = planes
        return msm_bucket_affine(G1J, bases, mags, negs, window=H_BUCKET_WINDOW)
    return _msm_g1(bases, planes)


def _h_table_fn(bases, window: int):
    return resident_table(G1J, bases, window, default_lanes(bases[0].shape[0]))


def _msm_h_resident(table, planes):
    """The h MSM against the key's resident multiples table."""
    return msm_resident(G1J, table, *planes)


# Stage-wise jits, NOT one fused program: XLA compile time scales with
# traced-graph size, so the pipeline is a handful of small executables
# with intermediates staying on device between stages.  Since b/c
# pruning the G1 MSMs run at three different lane counts (a: all wires,
# b1: |b_sel|, c: |c_sel|), so jit re-specializes _msm_g1 per shape —
# the ~50% runtime cut on b1/b2/c outweighs the extra first-proof
# compiles (and the persistent cache amortises them across processes).
_jit_h_planes = jax.jit(_h_and_planes)
_jit_msm_g1 = jax.jit(_msm_g1)
_jit_msm_g2 = jax.jit(_msm_g2)
_jit_msm_h = jax.jit(_msm_h)
_jit_msm_g1_narrow = jax.jit(_msm_g1_narrow)
_jit_msm_g2_narrow = jax.jit(_msm_g2_narrow)
_jit_h_table = jax.jit(_h_table_fn, static_argnames="window")
_jit_msm_h_resident = jax.jit(_msm_h_resident)
_jit_h_planes_batch = jax.jit(jax.vmap(_h_and_planes, in_axes=(None, 0)))
_jit_msm_g1_batch = jax.jit(jax.vmap(_msm_g1, in_axes=(None, 0)))
_jit_msm_g2_batch = jax.jit(jax.vmap(_msm_g2, in_axes=(None, 0)))
_jit_msm_h_batch = jax.jit(jax.vmap(_msm_h, in_axes=(None, 0)))
_jit_msm_h_resident_batch = jax.jit(jax.vmap(_msm_h_resident, in_axes=(None, 0)))
_jit_msm_g1_narrow_batch = jax.jit(jax.vmap(_msm_g1_narrow, in_axes=(None, 0)))
_jit_msm_g2_narrow_batch = jax.jit(jax.vmap(_msm_g2_narrow, in_axes=(None, 0)))


def _take_planes(planes, sel):
    # signed planes are a (mags, negs) pair; both gather on wires
    if isinstance(planes, tuple):
        return tuple(jnp.take(p, sel, axis=-1) for p in planes)
    return jnp.take(planes, sel, axis=-1)


def _glv_key_bases(dpk: DeviceProvingKey, name: str, bases: AffPoint) -> AffPoint:
    """GLV-doubled base set [P, phi(P)] for one query, memoised on the
    key instance (one batched Fq mul per query per key — witness-
    independent, like _split_cache)."""
    cache = getattr(dpk, "_glv_cache", None)
    if cache is None:
        cache = {}
        setattr(dpk, "_glv_cache", cache)
    got = cache.get(name)
    if got is None:
        got = glv_extend_bases(bases)
        cache[name] = got
    return got


def _h_table(dpk: DeviceProvingKey) -> Optional[jnp.ndarray]:
    """The key's resident h table, built by the first prove that needs
    it and memoised on the instance like `_split_cache` (not a pytree
    field: its bytes never ride into a jitted stage as part of the key);
    None where `_h_table_window` says the h MSM builds its multiples in
    the scan.  `zkp2p_msm_h_table_bytes` says which."""
    from ..utils.metrics import REGISTRY
    from ..utils.trace import trace

    window, table = _h_table_window(dpk.log_m), None
    if window is not None:
        table = getattr(dpk, "_h_table_cache", None)
        if table is None:
            with trace("h_table", window=window) as span:
                table = jax.block_until_ready(_jit_h_table(dpk.h_bases, window=window))
                span["bytes"] = int(table.nbytes)
            setattr(dpk, "_h_table_cache", table)
    REGISTRY.gauge("zkp2p_msm_h_table_bytes").set(0 if table is None else table.nbytes)
    return table


def _take_bases(bases, pos):
    return tuple(jnp.take(c, pos, axis=0) for c in bases)


def _pad_msm(bases, planes, n_to: int):
    """Pad an MSM's inputs to `n_to` bases: the (0, 0) infinity sentinel
    and zero digit planes contribute nothing, and equal shapes let MSMs
    share one compiled executable."""
    n = bases[0].shape[0]
    if n_to and n < n_to:
        bases = tuple(jnp.pad(c, [(0, n_to - n)] + [(0, 0)] * (c.ndim - 1)) for c in bases)
        if isinstance(planes, tuple):
            planes = tuple(jnp.pad(p, [(0, 0)] * (p.ndim - 1) + [(0, n_to - n)]) for p in planes)
        else:
            planes = jnp.pad(planes, [(0, 0)] * (planes.ndim - 1) + [(0, n_to - n)])
    return bases, planes


STAGES = ("h_planes", "msm_a", "msm_b1", "msm_b2", "msm_c", "msm_h")


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
    their ends would all read as dispatch's."""

    def __init__(self, t0: float):
        from ..utils.trace import adopt_context, adopt_stack, current_context, current_stack

        self.chunk = 0
        self._t = t0
        self._q: "queue.SimpleQueue" = queue.SimpleQueue()
        stack, ctx = current_stack(), current_context()

        def run():
            from ..utils.trace import record

            adopt_stack(stack)  # the spans nest under `device`, open on the proving thread
            adopt_context(ctx)
            t_ready = t0
            for name, chunk, t_enqueue, value, attrs in iter(self._q.get, None):
                try:
                    jax.block_until_ready(value)
                except Exception:  # noqa: BLE001 — the proving thread meets it where it reads the accumulator
                    return
                t_start, t_ready = max(t_ready, t_enqueue), time.time()
                record("stage/" + name, t_start, t_ready, chunk=chunk, **attrs)

        self._thread = threading.Thread(target=run, name="zkp2p-stage-watch", daemon=True)
        self._thread.start()

    def enqueued(self, name: str, value, **attrs) -> None:
        self._q.put((name, self.chunk, self._t, value, attrs))
        self._t = time.time()

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


def _prove_device(dpk: DeviceProvingKey, w_mont: jnp.ndarray, batched: bool = False,
                  watch: Optional[_StageWatch] = None):
    """The five big MSMs; everything else about the proof is host-cheap.
    The b/c MSMs run only over their pruned non-infinity lanes (plane
    columns gathered through b_sel/c_sel), and with width metadata each
    witness MSM splits into a narrow class (3 signed w=4 planes — the
    ~90% of wires that are constraint-bounded bits/bytes) and a wide
    class (full planes); the two partial sums combine with one Jacobian
    add per query."""
    classed = MSM_SIGNED and int(dpk.a_nsel.shape[0]) > 0
    jh, m1, m2 = (
        (_jit_h_planes_batch, _jit_msm_g1_batch, _jit_msm_g2_batch)
        if batched
        else (_jit_h_planes, _jit_msm_g1, _jit_msm_g2)
    )
    mh = _jit_msm_h_batch if batched else _jit_msm_h
    m1n, m2n = (
        (_jit_msm_g1_narrow_batch, _jit_msm_g2_narrow_batch)
        if batched
        else (_jit_msm_g1_narrow, _jit_msm_g2_narrow)
    )
    h_table = _h_table(dpk)
    w_all, h_planes = jh(dpk, w_mont)
    if watch is not None:
        # a few bytes that are ready when the stage is, cut from a plane by a
        # program of their own: the stage's program stays as it is, and no
        # plane is kept alive to wait on
        watch.enqueued("h_planes", _first_element(jax.tree_util.tree_leaves(h_planes)[0]),
                       ntt=NTT_LADDER)
    if _glv():
        # GLV layout: G1 planes carry 2*n_wires columns (k1 digits for
        # the P half, k2 for the phi(P) half); the G2 MSM keeps its own
        # full-width planes.  G1 bases and column selectors lift to the
        # doubled layout; everything downstream is shape-generic.
        w_planes, w_narrow, g2_planes = w_all
        g1_bases = lambda name, b: _glv_key_bases(dpk, name, b)  # noqa: E731
        g1_cols = lambda sel: glv_sel(sel, dpk.n_wires)  # noqa: E731
    else:
        if MSM_SIGNED:
            w_planes, w_narrow = w_all
        else:
            w_planes, w_narrow = w_all, None
        g2_planes = w_planes
        g1_bases = lambda name, b: b  # noqa: E731
        g1_cols = lambda sel: sel  # noqa: E731

    def msm_h(scan):
        """The h stage, enqueued: against the resident table where the
        key has one, else `scan()` — today's road."""
        if h_table is not None:
            mhr = _jit_msm_h_resident_batch if batched else _jit_msm_h_resident
            return _enqueued(watch, "msm_h", mhr(h_table, h_planes),
                             window=int(h_table.shape[1]).bit_length(), table="resident")
        return _enqueued(watch, "msm_h", scan(),
                         window=H_BUCKET_WINDOW if _h_bucket() else MSM_WINDOW, table="scan")

    if not classed:
        a_b = g1_bases("a", dpk.a_bases)
        b1_b = g1_bases("b1", dpk.b1_bases)
        c_b = g1_bases("c", dpk.c_bases)
        h_b = g1_bases("h", dpk.h_bases)
        # bucket-h mode, or a resident h table: h no longer shares the
        # unified executable, so padding a/b1/c up to the (domain-sized)
        # h base count would be pure waste — unify the three query MSMs
        # among themselves only.
        h_apart = _h_bucket() or h_table is not None
        g1_n = 0 if not _unified() else max(
            a_b[0].shape[0], b1_b[0].shape[0], c_b[0].shape[0],
            *(() if h_apart else (h_b[0].shape[0],)),
        )
        b_planes = _take_planes(w_planes, g1_cols(dpk.b_sel))
        c_planes = _take_planes(w_planes, g1_cols(dpk.c_sel))
        # GLV g2_planes are already gathered to the b_sel columns
        b2_planes = g2_planes if _glv() else b_planes
        # windowed mode keeps the m1 wrapper so the compiled-executable
        # identity (and its persistent-cache entry) is unchanged
        h_acc = msm_h(lambda: (
            mh(h_b, h_planes)
            if _h_bucket()
            else m1(*_pad_msm(h_b, h_planes, g1_n))
        ))
        return (
            _enqueued(watch, "msm_a", m1(*_pad_msm(a_b, w_planes, g1_n))),
            _enqueued(watch, "msm_b1", m1(*_pad_msm(b1_b, b_planes, g1_n))),
            _enqueued(watch, "msm_b2", m2(dpk.b2_bases, b2_planes)),
            _enqueued(watch, "msm_c", m1(*_pad_msm(c_b, c_planes, g1_n))),
            h_acc,
        )

    # Unify shapes WITHIN each class (a/b1/c wide together, narrows
    # together) but NOT with the h MSM: the wide query classes are ~6%
    # of wires while h spans the full domain — padding them to h's size
    # would burn ~16x the work the classing just removed.  Three G1
    # executables total (narrow, query-wide, h).
    g1_wide_n = g1_narrow_n = 0
    if _unified():
        g1_wide_n = max(dpk.a_wsel.shape[0], dpk.b_wsel.shape[0], dpk.c_wsel.shape[0])
        g1_narrow_n = max(dpk.a_nsel.shape[0], dpk.b_nsel.shape[0], dpk.c_nsel.shape[0])
        if _glv():
            g1_wide_n *= 2  # wide-class MSMs run over the doubled base axis

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
        wires_of maps base positions to wire ids (None = identity).
        Under GLV only the WIDE class decomposes — narrow wires are
        width-bounded below 2^11, where a 2-term split has nothing to
        halve — so the narrow executable is byte-identical either way."""
        accs = []
        if int(nsel.shape[0]):
            nb, nw = key_split(name + ".n", bases, nsel, wires_of)
            accs.append(m1n(*_pad_msm(nb, _take_planes(w_narrow, nw), g1_narrow_n)))
        if int(wsel.shape[0]):
            wb, ww = key_split(name + ".w", bases, wsel, wires_of)
            wb = g1_bases(name + ".w", wb)
            accs.append(m1(*_pad_msm(wb, _take_planes(w_planes, g1_cols(ww)), g1_wide_n)))
        return accs[0] if len(accs) == 1 else G1J.add(accs[0], accs[1])

    def query_g2(name, bases, nsel, wsel, wires_of):
        accs = []
        if int(nsel.shape[0]):
            nb, nw = key_split(name + ".n", bases, nsel, wires_of)
            accs.append(m2n(nb, _take_planes(w_narrow, nw)))
        if int(wsel.shape[0]):
            wb, ww = key_split(name + ".w", bases, wsel, wires_of)
            # GLV g2_planes carry b_sel POSITIONS (wsel indexes those);
            # the plain path's full-wire planes gather by wire id
            cols = wsel if _glv() else ww
            accs.append(m2(wb, _take_planes(g2_planes, cols)))
        return accs[0] if len(accs) == 1 else G2J.add(accs[0], accs[1])

    return (
        _enqueued(watch, "msm_a", query("a", dpk.a_bases, dpk.a_nsel, dpk.a_wsel, None)),
        _enqueued(watch, "msm_b1", query("b1", dpk.b1_bases, dpk.b_nsel, dpk.b_wsel, dpk.b_sel)),
        _enqueued(watch, "msm_b2", query_g2("b2", dpk.b2_bases, dpk.b_nsel, dpk.b_wsel, dpk.b_sel)),
        _enqueued(watch, "msm_c", query("c", dpk.c_bases, dpk.c_nsel, dpk.c_wsel, dpk.c_sel)),
        msm_h(lambda: (mh if _h_bucket() else m1)(g1_bases("h", dpk.h_bases), h_planes)),
    )


def _assemble(dpk: DeviceProvingKey, acc, r: int, s: int) -> Proof:
    a_acc, b1_acc, b2_acc, c_acc, h_acc = acc
    pi_a = g1_add(g1_add(dpk.alpha_1, a_acc), g1_mul(dpk.delta_1, r))
    pi_b = g2_add(g2_add(dpk.beta_2, b2_acc), g2_mul(dpk.delta_2, s))
    pi_b1 = g1_add(g1_add(dpk.beta_1, b1_acc), g1_mul(dpk.delta_1, s))
    pi_c = g1_add(c_acc, h_acc)
    pi_c = g1_add(pi_c, g1_mul(pi_a, s))
    pi_c = g1_add(pi_c, g1_mul(pi_b1, r))
    pi_c = g1_add(pi_c, g1_neg(g1_mul(dpk.delta_1, r * s % R)))
    return Proof(a=pi_a, b=pi_b, c=pi_c)


def prove_tpu(
    dpk: DeviceProvingKey,
    witness: Sequence[int],
    r: Optional[int] = None,
    s: Optional[int] = None,
) -> Proof:
    from ..utils.audit import sample_device_memory
    from ..utils.metrics import REGISTRY
    from ..utils.trace import trace

    if r is None:
        r = 1 + secrets.randbelow(R - 1)
    if s is None:
        s = 1 + secrets.randbelow(R - 1)
    with trace("tpu/prove"):
        sample_device_memory("tpu/prove")  # entry watermark (flight recorder)
        _check_inferred_widths(dpk, witness, w_std=witness if _is_u64_witness(witness) else None)
        acc = _prove_device(dpk, witness_to_device(witness))
        a, b1, c, hq = (g1_jac_to_host(p)[0] for p in (acc[0], acc[1], acc[3], acc[4]))
        b2 = g2_jac_to_host(acc[2])[0]
        proof = _assemble(dpk, (a, b1, b2, c, hq), r, s)
        sample_device_memory("tpu/prove")  # exit watermark: per-prove HBM peak
    REGISTRY.counter("zkp2p_proves_total", {"prover": "tpu"}).inc()
    return proof


def h_evals_sharded(dpk: DeviceProvingKey, w_mont: jnp.ndarray, mesh, axis: str = "shard") -> jnp.ndarray:
    """`h_evals` with the six domain transforms sharded over `mesh`:
    the production multi-chip path (SURVEY.md §2.7 NTT parallelism).

    The sparse matvec stays replicated (it is ~1% of prove FLOPs and its
    segment-sum does not shard cleanly); each (m, 16) vector is then laid
    out shard-major and run through the four-step `ntt_sharded` with its
    three ICI all-to-alls.  Requires both Bailey factors of the domain to
    be divisible by the mesh width: m >= (mesh size)^2."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..parallel.ntt import ntt_sharded

    g = coset_gen(dpk.log_m)
    a_ev, b_ev, c_ev = abc_evals(dpk, w_mont)
    shard = NamedSharding(mesh, P(axis, None))

    def ladder(v):
        v = jax.device_put(v, shard)
        v = ntt_sharded(v, dpk.log_m, mesh, axis=axis, inverse=True)
        v = coset_shift(v, g, dpk.log_m)
        return ntt_sharded(v, dpk.log_m, mesh, axis=axis)

    a_cos, b_cos, c_cos = ladder(a_ev), ladder(b_ev), ladder(c_ev)
    return FR.sub(FR.mul(a_cos, b_cos), c_cos)


def prove_tpu_sharded(
    dpk: DeviceProvingKey,
    witness: Sequence[int],
    mesh,
    r: Optional[int] = None,
    s: Optional[int] = None,
    axis: str = "shard",
    lanes: int = 64,
    unified: bool = False,
    progress=None,
) -> Proof:
    """`prove_tpu` with the MSM base axis AND the NTT domain sharded over
    `mesh` — the same dataflow a v5e slice runs, exercised by the driver's
    `dryrun_multichip` on virtual CPU devices.  Emits the exact proof
    `prove_host`/`prove_tpu` produce for the same (witness, r, s).

    unified=True pads every G1 MSM (a/b1/c/h) to one common base count so
    all four share a single compiled executable — the dryrun/cold-start
    configuration, where XLA compile time on the driver host dwarfs the
    masked-lane runtime waste.  Production keeps per-shape sizing.
    progress, when given, is called with a short string after each
    device stage (the dryrun's per-stage timestamps)."""
    from ..parallel.mesh import msm_sharded, pad_to_multiple
    from ..utils.trace import trace

    if r is None:
        r = 1 + secrets.randbelow(R - 1)
    if s is None:
        s = 1 + secrets.randbelow(R - 1)

    def note(arr, msg: str) -> None:
        # Sync + report only when a progress callback asked for stage
        # boundaries (the dryrun); production dispatch stays fully async.
        if progress is not None:
            arr.block_until_ready()
            progress(msg)

    # Stage spans feed the same trace/metrics rails as the single-chip
    # provers, so a MULTICHIP dryrun dumped to a sink is diffable with
    # trace_report like any bench run.  With a progress callback each
    # span brackets block_until_ready (true stage time); without one
    # dispatch is async and spans measure enqueue latency only.
    n_dev = mesh.shape[axis]
    with trace("sharded/witness"):
        w_mont = witness_to_device(witness)
    with trace("sharded/h_evals"):
        h = h_evals_sharded(dpk, w_mont, mesh, axis)
        note(h, "h_evals_sharded")
    with trace("sharded/planes"):
        w_planes = digit_planes_from_limbs(FR.from_mont(w_mont), MSM_WINDOW)
        h_planes = digit_planes_from_limbs(FR.from_mont(h), MSM_WINDOW)
    if unified:
        # One executable for ALL FOUR G1 MSMs needs identical input
        # LAYOUTS, not just shapes: h_planes inherits the NTT's shard-axis
        # sharding while w_planes is replicated, and jit keys compiled
        # programs on input shardings — without this the h MSM recompiles
        # the whole G1 program (~250 s of the dryrun's cold budget).
        # Replicating h_planes is dryrun-sized traffic only; production
        # (unified=False) keeps the sharded layout.
        from jax.sharding import NamedSharding, PartitionSpec as P

        rep = NamedSharding(mesh, P())
        w_planes = jax.device_put(w_planes, rep)
        h_planes = jax.device_put(h_planes, rep)

    base_chunk = n_dev * lanes
    g1_chunk = base_chunk
    if unified:
        n_max = max(
            dpk.a_bases[0].shape[0], dpk.b1_bases[0].shape[0],
            dpk.c_bases[0].shape[0], dpk.h_bases[0].shape[0],
        )
        g1_chunk = ((n_max + base_chunk - 1) // base_chunk) * base_chunk

    def msm(curve, bases, planes, tag):
        # Per-MSM padding: the b/c queries are pruned to their
        # non-infinity lanes, so each MSM runs at its own (smaller) size
        # rather than a unified shape (runtime beats executable reuse on
        # the production path); unified=True pads the four G1 MSMs to one
        # shared shape.  G2 compiles its own executable either way (other
        # curve type), so it always keeps its minimal padded size — its
        # per-point cost is ~3x G1's.
        chunk = g1_chunk if curve is G1J else base_chunk
        with trace(f"sharded/msm_{tag}"):
            b, p = pad_to_multiple(bases, planes, chunk)
            acc = msm_sharded(curve, b, p, mesh, axis=axis, lanes=lanes, window=MSM_WINDOW)
            note(acc[0], f"msm {tag} ({b[0].shape[0]} bases)")
        return acc

    b_planes = jnp.take(w_planes, dpk.b_sel, axis=-1)
    a_acc = msm(G1J, dpk.a_bases, w_planes, "a")
    b1_acc = msm(G1J, dpk.b1_bases, b_planes, "b1")
    b2_acc = msm(G2J, dpk.b2_bases, b_planes, "b2")
    c_acc = msm(G1J, dpk.c_bases, jnp.take(w_planes, dpk.c_sel, axis=-1), "c")
    h_acc = msm(G1J, dpk.h_bases, h_planes, "h")
    a, b1, c, hq = (g1_jac_to_host(p)[0] for p in (a_acc, b1_acc, c_acc, h_acc))
    b2 = g2_jac_to_host(b2_acc)[0]
    return _assemble(dpk, (a, b1, b2, c, hq), r, s)


# Batched sharded-arm h stage: h_evals vmapped over this batch group's
# share of the witness chunk, and the UNSIGNED digit-plane recode per
# witness ((B, n_planes, n) — the layout msm_pod_batched's shard_map
# consumes), as ONE shard_map over the pod mesh.  It must be a
# shard_map, not a jit over mesh-sharded inputs: on a real mesh JAX
# refuses to partition a Mosaic kernel automatically ("wrap the call in
# a shard_map" — found on the four-chip host, PERF.md PR 21), and every
# field product here is one.  The sharded MSMs use the unsigned
# formulation like prove_tpu_sharded: group arithmetic is exact, so the
# proof bytes match the signed vmap arm regardless.
@lru_cache(maxsize=None)
def _h_planes_pod_fn(mesh):
    from jax.sharding import PartitionSpec as P

    def local(dpk, w_mont):  # w_mont: (B_local, n_wires, 16)
        planes = jax.vmap(lambda x: digit_planes_from_limbs(FR.from_mont(x), MSM_WINDOW))
        h = jax.vmap(h_evals, in_axes=(None, 0))(dpk, w_mont)
        with jax.named_scope("recode"):
            # the last is `done`: one limb of h a witness, ready when the stage is (_StageWatch waits on it)
            return planes(w_mont), planes(h), h[:, 0, 0]

    return jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(P(), P("batch")),
        out_specs=(P("batch"), P("batch"), P("batch")), check_vma=False,
    ))


def _prove_batch_sharded(dpk: DeviceProvingKey, w_mont: jnp.ndarray, mesh, watch: Optional[_StageWatch] = None):
    """One prove_tpu_batch chunk on a ("batch", "shard") pod mesh: the
    (B, n_wires, 16) witness chunk is placed batch-sharded
    (`NamedSharding(mesh, P("batch"))` — each batch group proves its
    share of the chunk), and every MSM runs base-axis-sharded over the
    inner "shard" axis with per-device bucket partial sums combined by
    ONE group-op allreduce (all_gather + Jacobian fold — ICI on real
    hardware, host rings on the virtual CPU mesh; parallel.mesh.
    msm_pod_batched).  Returns the same five (B,)-batched accumulators
    `_prove_device(batched=True)` emits, so chunks from either arm
    concatenate identically downstream."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..parallel.mesh import msm_pod_batched, pad_to_multiple

    n_ici = mesh.shape["shard"]
    w_mont = jax.device_put(w_mont, NamedSharding(mesh, P("batch")))
    w_planes, h_planes, done = _h_planes_pod_fn(mesh)(dpk, w_mont)
    _enqueued(watch, "h_planes", done, ntt=NTT_LADDER)

    def msm(name, curve, bases, planes):
        # lanes sized to the per-device slice (tiny CI circuits stay at
        # lanes ~ n/S instead of padding 16x to a 64-lane step); the pad
        # rule matches prove_tpu_sharded — bases to a multiple of
        # S * lanes so every device sees whole steps.
        n = bases[0].shape[0]
        lanes = max(1, min(64, -(-n // n_ici)))
        b, p = pad_to_multiple(bases, planes, n_ici * lanes)
        return _enqueued(watch, name, msm_pod_batched(
            curve, b, p, mesh,
            dcn_axis="batch", ici_axis="shard", lanes=lanes, window=MSM_WINDOW,
        ))

    b_planes = jnp.take(w_planes, dpk.b_sel, axis=-1)
    return (
        msm("msm_a", G1J, dpk.a_bases, w_planes),
        msm("msm_b1", G1J, dpk.b1_bases, b_planes),
        msm("msm_b2", G2J, dpk.b2_bases, b_planes),
        msm("msm_c", G1J, dpk.c_bases, jnp.take(w_planes, dpk.c_sel, axis=-1)),
        msm("msm_h", G1J, dpk.h_bases, h_planes),
    )


def _batch_chunk_size(log_m: Optional[int] = None) -> int:
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
    the answer is the largest chunk.  ZKP2P_BATCH_CHUNK, when a number,
    overrides the rule."""
    auto = 0
    if _on_tpu():
        auto = BATCH_CHUNK_MAX if log_m is None else batch_chunk_for(log_m, _hbm_bytes_limit())
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

    Large batches run as shape-stable sub-chunks (see _batch_chunk_size;
    the last chunk pads by repeating its final witness) so device memory
    is bounded by the chunk, not the batch, and every chunk reuses the
    same compiled executable.

    With ZKP2P_TPU_SHARD=on (and a satisfiable ZKP2P_TPU_MESH) each
    chunk runs the pod-mesh program instead (_prove_batch_sharded):
    batch data-parallel over the mesh's "batch" axis, MSM bucket partial
    sums allreduced over "shard".  The arm is decided ONCE per call —
    a chunk size indivisible by the mesh's batch width records the
    `tpu_shard` arm as "fallback" and the whole call takes the vmap
    path, so every chunk of a call shares one executable either way."""
    from ..utils.audit import sample_device_memory
    from ..utils.metrics import REGISTRY
    from ..utils.trace import trace

    # Spans (utils.trace): `prep`, `device` and `finish` partition
    # `tpu/prove_batch`.  `device` runs from the batch's first enqueue to
    # the instant its last stage's result is ready; `dispatch` and one
    # span per device stage (per chunk, _StageWatch) lie inside it.
    with trace("tpu/prove_batch", n=len(witnesses), log_m=dpk.log_m) as batch_span:
        with trace("prep"):
            sample_device_memory("tpu/prove_batch")  # entry watermark
            for wit in witnesses:
                _check_inferred_widths(dpk, wit, w_std=wit if _is_u64_witness(wit) else None)
            n = len(witnesses)
            chunk = _batch_chunk_size(dpk.log_m)
            if chunk <= 0 or n <= chunk:
                spans = [list(witnesses)]
            else:
                spans = [list(witnesses[i : i + chunk]) for i in range(0, n, chunk)]
                spans[-1] += [spans[-1][-1]] * (chunk - len(spans[-1]))
            # the size chosen (0: the whole batch as one) and how many ran
            batch_span.update(chunk=chunk, n_chunks=len(spans))
            REGISTRY.gauge("zkp2p_prove_chunk").set(chunk)
            mesh = _shard_mesh()
            if mesh is not None and len(spans[0]) % mesh.shape["batch"]:
                _record_arm("tpu_shard", "fallback")
                mesh = None
            limbs = np.stack([_witness_std_limbs(wit) for wit in spans[0]])
        with trace("device", leaf=True) as device:
            if mesh is None:
                _h_table(dpk)  # the first batch of a key builds it: one `tpu/prove_batch/h_table` span
            watch = _StageWatch(device["t0"])
            try:
                with trace("dispatch"):
                    parts = []
                    for i, span in enumerate(spans):
                        if i:
                            limbs = np.stack([_witness_std_limbs(wit) for wit in span])
                        if i and chunk < BATCH_CHUNK_MAX:
                            # fewer than four at a time: the device's memory is the
                            # ceiling, and a chunk enqueued behind another has its
                            # buffers planned beside it — wait the last one out
                            jax.block_until_ready(parts[-1])
                        watch.chunk = i
                        # one batched to_mont per chunk (not one device dispatch per
                        # witness); the h_planes stage includes it
                        w = FR.to_mont(jnp.asarray(limbs))
                        parts.append(
                            _prove_batch_sharded(dpk, w, mesh, watch)
                            if mesh is not None
                            else _prove_device(dpk, w, batched=True, watch=watch)
                        )
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
                # these go to the host while the device is on a later stage
                a, b1, c = (g1_jac_to_host(accs[i]) for i in (0, 1, 3))
            finally:
                watch.close()  # the last stage's result is ready: the device has nothing left
        with trace("finish"):
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
    return proofs
