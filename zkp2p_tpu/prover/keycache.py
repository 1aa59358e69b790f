"""Data-only persistence for device proving keys (.npz).

The interop format stays snarkjs `.zkey` (formats.zkey); this cache is
the fast *internal* form — the DeviceProvingKey's numpy limb arrays
written as-is, so bench/service restarts skip both setup AND the
points->ints->limbs conversions.  Pure array data (numpy .npz), never
pickle (round-1 advisor finding)."""

from __future__ import annotations

from typing import Tuple

import numpy as np

from ..curve.host import G1Point, G2Point
from ..field.tower import Fq2
from ..snark.groth16 import VerifyingKey
from .groth16_tpu import _DPK_ARRAY_FIELDS, DeviceProvingKey, key_arrays_home

# Bump whenever _DPK_ARRAY_FIELDS / the npz layout changes: a cache written
# by an older schema must fail fast here (triggering re-setup upstream),
# not materialize empty arrays that crash deep inside jit (r3 advisor).
SCHEMA_VERSION = 3  # v3: width-classed MSM position arrays (a/b/c x narrow/wide)


class KeyCacheSchemaError(RuntimeError):
    """Cache file does not match the current DeviceProvingKey schema."""


def circuit_digest(cs) -> str:
    """Sampled structural digest of a ConstraintSystem: wire/constraint
    counts plus ~1k evenly-sampled constraint rows.  Catches the silent
    killer the (n_wires, domain) guard cannot: a gadget change that
    REORDERS wires or constraints without changing their counts — a key
    cached for the old order would prove garbage (caught only at
    verify).  Sampling keeps it O(1k) at the 4.9M-constraint flagship."""
    import hashlib

    n = len(cs.constraints)
    h = hashlib.sha256(f"{cs.num_wires}|{cs.num_public}|{n}".encode())
    step = max(1, n // 997)
    for i in range(0, n, step):
        c = cs.constraints[i]
        h.update(repr((i, sorted(c.a.items()), sorted(c.b.items()), sorted(c.c.items()))).encode())
    # The v3 cache stores narrow/wide classification arrays derived from
    # wire_width and the NARROW_WIDTH rule — a width-tag or rule change
    # with unchanged constraints must invalidate the cache, or the prover
    # would silently drop nonzero digit planes (caught only at verify).
    from .groth16_tpu import NARROW_PLANES, NARROW_WIDTH

    h.update(f"|nw{NARROW_WIDTH}|np{NARROW_PLANES}|".encode())
    widths = getattr(cs, "wire_width", {})
    h.update(hashlib.sha256(repr(sorted(widths.items())).encode()).digest())
    return h.hexdigest()[:16]


def _g1_arr(pt: G1Point) -> np.ndarray:
    if pt is None:
        return np.zeros((2, 32), dtype=np.uint8)
    return np.stack([
        np.frombuffer(pt[0].to_bytes(32, "little"), dtype=np.uint8),
        np.frombuffer(pt[1].to_bytes(32, "little"), dtype=np.uint8),
    ])


def _g1_from(arr: np.ndarray) -> G1Point:
    x = int.from_bytes(arr[0].tobytes(), "little")
    y = int.from_bytes(arr[1].tobytes(), "little")
    return None if x == 0 and y == 0 else (x, y)


def _g2_arr(pt: G2Point) -> np.ndarray:
    if pt is None:
        return np.zeros((4, 32), dtype=np.uint8)
    x, y = pt
    return np.stack([
        np.frombuffer(v.to_bytes(32, "little"), dtype=np.uint8)
        for v in (x.c0, x.c1, y.c0, y.c1)
    ])


def _g2_from(arr: np.ndarray) -> G2Point:
    vals = [int.from_bytes(arr[i].tobytes(), "little") for i in range(4)]
    if not any(vals):
        return None
    return (Fq2(vals[0], vals[1]), Fq2(vals[2], vals[3]))


def save_dpk(
    path: str, dpk: DeviceProvingKey, vk: VerifyingKey, digest: str = ""
) -> None:
    """`digest`, when given, pins the cache to circuit_digest(cs) — load
    callers passing a digest reject a key for a reordered circuit."""
    data = {}
    if digest:
        data["circuit_digest"] = np.frombuffer(digest.encode(), dtype=np.uint8)
    for f in _DPK_ARRAY_FIELDS:
        v = getattr(dpk, f)
        if isinstance(v, tuple):
            for i, c in enumerate(v):
                data[f"{f}.{i}"] = np.asarray(c)
        else:
            data[f] = np.asarray(v)
    data["meta"] = np.array([dpk.n_public, dpk.n_wires, dpk.log_m], dtype=np.int64)
    data["schema_version"] = np.array([SCHEMA_VERSION], dtype=np.int64)
    for name in ("alpha_1", "beta_1", "delta_1"):
        data[name] = _g1_arr(getattr(dpk, name))
    for name in ("beta_2", "delta_2"):
        data[name] = _g2_arr(getattr(dpk, name))
    data["vk_gamma_2"] = _g2_arr(vk.gamma_2)
    data["vk_ic"] = np.stack([_g1_arr(p) for p in vk.ic])
    # stored, not deflated: curve points hardly compress, and deflating took
    # 40 s for the 434 MB key of a 2^19 domain (2.1 GB at 2^22), in every
    # checkout's first start
    np.savez(path, **data)


def load_dpk(path: str, digest: str = "") -> Tuple[DeviceProvingKey, VerifyingKey]:
    z = np.load(path)
    found = int(z["schema_version"][0]) if "schema_version" in z else 0
    if found != SCHEMA_VERSION:
        raise KeyCacheSchemaError(
            f"{path}: key cache schema {found} != current {SCHEMA_VERSION}; re-run setup"
        )
    if digest:
        had = bytes(z["circuit_digest"]).decode() if "circuit_digest" in z else "<none>"
        if had != digest:
            raise KeyCacheSchemaError(
                f"{path}: circuit digest {had} != rebuilt circuit {digest} "
                f"(wire/constraint order changed); re-run setup"
            )
    n_public, n_wires, log_m = (int(v) for v in z["meta"])
    home = key_arrays_home(log_m)  # the default device, or the host for a key only a mesh can take
    arrays = {}
    for f in _DPK_ARRAY_FIELDS:
        if f in z:
            arrays[f] = home(z[f])
        else:
            parts = []
            i = 0
            while f"{f}.{i}" in z:
                parts.append(home(z[f"{f}.{i}"]))
                i += 1
            if not parts:
                raise KeyCacheSchemaError(f"{path}: missing field {f!r}; re-run setup")
            arrays[f] = tuple(parts)
    dpk = DeviceProvingKey(
        n_public=n_public,
        n_wires=n_wires,
        log_m=log_m,
        alpha_1=_g1_from(z["alpha_1"]),
        beta_1=_g1_from(z["beta_1"]),
        beta_2=_g2_from(z["beta_2"]),
        delta_1=_g1_from(z["delta_1"]),
        delta_2=_g2_from(z["delta_2"]),
        **arrays,
    )
    vk = VerifyingKey(
        n_public=n_public,
        alpha_1=dpk.alpha_1,
        beta_2=dpk.beta_2,
        gamma_2=_g2_from(z["vk_gamma_2"]),
        delta_2=dpk.delta_2,
        ic=[_g1_from(z["vk_ic"][i]) for i in range(z["vk_ic"].shape[0])],
    )
    return dpk, vk
