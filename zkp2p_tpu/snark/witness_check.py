"""The witness self-check without the interpreter.

`ConstraintSystem.check_witness` is a Python loop over every constraint
and every width tag: 1.3-1.6 s a witness at 499k constraints, on the
thread that feeds the prover.  This module runs the SAME check — every
constraint `<A,w>·<B,w> = <C,w>` and every tag `w[wire] < 2^bits`, exact
in Fr, nothing sampled — as three sparse products of the native library
over the witness's standard-form `u64` rows (both witness builders emit
them), a pointwise product, and numpy compares on the limbs.

The plan (A, B and C as the coefficient / wire / row-segment arrays
`fr_matvec_seg` takes, the tags as wire / bits arrays) is the constraint
system's, built once a circuit and memoised on it: C is in no proving key
(the prover assumes `Cz = Az∘Bz`, which is what this check establishes).
The segmented product on the library's pool, and not the serial scatter
`fr_matvec` on three threads, by a reading on the chip machine's host:
15.8 against 50.6 ms for the three at 499k constraints (PERF.md §6, PR 31).

`ConstraintSystem.check_witness` stays the oracle and the voice: when the
products find a failing row or tag, the Python loop runs on that witness
and ITS AssertionError is what the caller sees.  Which path runs is
observed, not set: the native library is loaded and the witness carries
its rows -> the plan; anything else -> the Python loop.

Not imported by `snark.r1cs`: `snark/` stays importable without the
native library.
"""

from __future__ import annotations

import ctypes
import itertools
import threading
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..field.bn254 import R

_u64p = ctypes.POINTER(ctypes.c_uint64)
_u32p = ctypes.POINTER(ctypes.c_uint32)
_i64p = ctypes.POINTER(ctypes.c_longlong)

R_U64 = np.frombuffer(R.to_bytes(32, "little"), dtype="<u8").copy()
_U64_MAX = np.uint64(0xFFFFFFFFFFFFFFFF)

_NONE = np.zeros(0, dtype=np.int64)

_build_lock = threading.Lock()


def unreduced_rows(rows: np.ndarray) -> np.ndarray:
    """Indices of the (n, 4)-u64 little-endian rows that are >= R (exactly
    R included): the standard form is canonical or it is not the value
    `int(w) % R` hands the prover."""
    cand = np.flatnonzero(rows[:, 3] >= R_U64[3])  # nearly every wire is far below
    top = rows[cand]
    ge = np.zeros(cand.shape[0], dtype=bool)
    eq = np.ones(cand.shape[0], dtype=bool)
    for j in range(3, -1, -1):
        ge |= eq & (top[:, j] > R_U64[j])
        eq &= top[:, j] == R_U64[j]
    return cand[ge | eq]


@dataclass(frozen=True)
class Matrix:
    """One of A, B, C as `fr_matvec_seg` takes it: the nonzeros in
    constraint order, and one segment a constraint that has a term
    (nonzeros `seg_starts[s]:seg_starts[s + 1]` are row `seg_rows[s]`),
    which the library's pool splits with no two workers on one row."""

    coeff: np.ndarray  # (nnz, 4) u64, the coefficient mod R, Montgomery
    wire: np.ndarray  # (nnz,) u32
    seg_starts: np.ndarray  # (nseg + 1,) i64
    seg_rows: np.ndarray  # (nseg,) u32, rising
    coeff52: Optional[np.ndarray]  # the 8-lane pack of `coeff`, where the CPU has the vector tier

    def product(self, lib, w_mont: np.ndarray, threads: int, out: np.ndarray) -> None:
        lib.fr_matvec_seg(
            _p(self.coeff52) if self.coeff52 is not None else None, _p(self.coeff), _p32(self.wire),
            self.seg_starts.ctypes.data_as(_i64p), _p32(self.seg_rows), self.seg_rows.shape[0],
            _p(w_mont), out.shape[0], threads, _p(out),
        )


@dataclass(frozen=True)
class CheckPlan:
    n_constraints: int
    n_wires: int
    a: Matrix
    b: Matrix
    c: Matrix
    width_wire: np.ndarray  # (tags,) u32
    width_bits: np.ndarray  # (tags,) i64
    # the largest value each limb of a tagged wire may hold: what
    # `w[wire] < 2^bits` reads on four u64 limbs (tags reach 130 bits)
    width_limb_max: np.ndarray  # (tags, 4) u64
    # buffers of a check that has finished, for the next one: a fresh
    # 16-68 MB array is paid for in page faults every time, and a sweep's
    # producer thread does not live to keep its own
    _spare: List[Tuple[np.ndarray, ...]] = field(default_factory=list, repr=False, compare=False)

    def faults(self, lib, rows: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """(indices of the constraints `rows` leaves unsatisfied, indices
        into the tag arrays of the width bounds it exceeds)."""
        from ..prover.native_prove import _n_threads

        n = self.n_constraints
        try:
            bufs = self._spare.pop()
        except IndexError:
            bufs = (np.empty_like(rows),) + tuple(np.empty((n, 4), dtype=np.uint64) for _ in range(3))
        w_mont, az, bz, cz = bufs
        threads = _n_threads()
        lib.fr_to_mont_batch(_p(rows), _p(w_mont), rows.shape[0])
        for mx, out in ((self.a, az), (self.b, bz), (self.c, cz)):
            mx.product(lib, w_mont, threads, out)
        lib.fr_mul_batch(_p(az), _p(bz), _p(az), n)
        # the whole-array compares first: a witness that passes, as nearly
        # every one does, is never asked where it fails
        bad_rows = _NONE if np.array_equal(az, cz) else np.flatnonzero((az != cz).any(axis=1))
        over = rows[self.width_wire] > self.width_limb_max
        bad_tags = np.flatnonzero(over.any(axis=1)) if over.any() else _NONE
        self._spare.append(bufs)
        return bad_rows, bad_tags


def _p(a: np.ndarray):
    return a.ctypes.data_as(_u64p)


def _p32(a: np.ndarray):
    return a.ctypes.data_as(_u32p)


def _matrix(lib, terms: Sequence[Dict[int, int]], n_wires: int) -> Matrix:
    """The constraints' `a` (or `b`, `c`) dicts as one sparse matrix.
    Nothing here runs a Python statement a nonzero (a loop is 7 s at 2.8 M
    of them): the dicts' keys and values are collected by `chain`, a
    circuit's few thousand distinct coefficients are converted once each,
    the rest is numpy and the library."""
    from ..prover.matvec_plan import _pack52
    from ..prover.native_prove import _witness_std_u64

    counts = np.fromiter(map(len, terms), dtype=np.int64, count=len(terms))
    nnz = int(counts.sum())
    wire = np.fromiter(itertools.chain.from_iterable(terms), dtype=np.uint32, count=nnz)
    if nnz and int(wire.max()) >= n_wires:
        raise ValueError(f"a constraint names wire {int(wire.max())} of {n_wires}")
    values = list(itertools.chain.from_iterable(map(dict.values, terms)))
    distinct = list(dict.fromkeys(values))
    index = dict(zip(distinct, range(len(distinct))))
    which = np.fromiter(map(index.__getitem__, values), dtype=np.int64, count=nnz)
    mont = _witness_std_u64(lib, distinct)  # the values mod R, whatever integers the dicts hold
    lib.fr_to_mont_batch(_p(mont), _p(mont), mont.shape[0])
    coeff = np.ascontiguousarray(mont[which]) if nnz else np.zeros((0, 4), dtype=np.uint64)
    used = np.flatnonzero(counts)
    return Matrix(
        coeff=coeff,
        wire=wire,
        seg_starts=np.concatenate([[0], np.cumsum(counts[used])]).astype(np.int64),
        seg_rows=used.astype(np.uint32),
        coeff52=_pack52(lib, coeff),
    )


def build_plan(lib, cs) -> CheckPlan:
    cons = cs.constraints
    n_tags = len(cs.wire_width)
    width_wire = np.fromiter(cs.wire_width.keys(), dtype=np.uint32, count=n_tags)
    width_bits = np.fromiter(cs.wire_width.values(), dtype=np.int64, count=n_tags)
    if n_tags and int(width_wire.max()) >= cs.num_wires:
        raise ValueError(f"a width tag names wire {int(width_wire.max())} of {cs.num_wires}")
    # limb j of a value under 2^bits holds at most 2^(bits - 64 j) - 1:
    # nothing where bits <= 64 j, anything where bits >= 64 (j + 1)
    over = np.clip(width_bits[:, None] - 64 * np.arange(4)[None, :], 0, 64)
    width_limb_max = np.where(
        over >= 64, _U64_MAX, (np.uint64(1) << np.minimum(over, 63).astype(np.uint64)) - np.uint64(1)
    )
    return CheckPlan(
        n_constraints=len(cons),
        n_wires=cs.num_wires,
        a=_matrix(lib, [c.a for c in cons], cs.num_wires),
        b=_matrix(lib, [c.b for c in cons], cs.num_wires),
        c=_matrix(lib, [c.c for c in cons], cs.num_wires),
        width_wire=width_wire,
        width_bits=width_bits,
        width_limb_max=np.ascontiguousarray(width_limb_max),
    )


def _native():
    """The native library with the prover's entry points, or None."""
    from ..prover.native_prove import _lib

    return _lib()


def plan_for(cs) -> Optional[CheckPlan]:
    """The constraint system's plan, built on first use and kept on it;
    `enforce`, `set_width` and a new wire drop it.  None without the
    native library."""
    lib = _native()
    if lib is None:
        return None
    with _build_lock:  # four replicas share one constraint system
        plan = cs._check_plan
        # a wire allocated since (no constraint names it yet) makes the
        # witness's rows longer than the buffers the plan keeps
        if plan is None or plan.n_wires != cs.num_wires:
            plan = cs._check_plan = build_plan(lib, cs)
    return plan


def rows_of(w, rows) -> Optional[np.ndarray]:
    """`rows` where they are `w`'s standard form in the one layout every
    reader of them takes (the library, this check, the device prover):
    an ndarray of one (4,) u64 row a wire of `w`; else None.  THE guard:
    what a reader observes of its input, and all it observes."""
    if isinstance(rows, np.ndarray) and rows.dtype == np.uint64 and rows.shape == (len(w), 4):
        return np.ascontiguousarray(rows)
    return None


def witness_rows(cs, w) -> Optional[np.ndarray]:
    """The standard-form rows a builder attached to `w`, if they are in
    the layout the library reads (one (4,) u64 row a wire of `cs`)."""
    rows = rows_of(w, getattr(w, "u64", None))
    return rows if rows is not None and len(rows) == cs.num_wires else None


def path_for(cs, ws: Sequence) -> str:
    """Which check `ws` get: "native" where the library is loaded and
    every witness carries its rows, else "python"."""
    if all(witness_rows(cs, w) is not None for w in ws) and plan_for(cs) is not None:
        return "native"
    return "python"


def check_witness(cs, w, path: str) -> None:
    """`cs.check_witness(w)` — every constraint, every width tag, values
    mod R — by the path `path_for` chose.  A witness that fails raises the
    Python loop's own AssertionError; rows that are not canonical raise
    ValueError (the loop reduces mod R and has no word for them)."""
    if path == "python":
        cs.check_witness(w)
        return
    rows, plan = witness_rows(cs, w), plan_for(cs)
    unreduced = unreduced_rows(rows)
    if unreduced.size:
        i = int(unreduced[0])
        raise ValueError(
            f"witness row {i} ({cs.wire_desc(i)}) is not reduced below the Fr modulus: "
            "the u64 rows a witness carries are its values mod R, canonical"
        )
    bad_rows, bad_tags = plan.faults(_native(), rows)
    if bad_rows.size or bad_tags.size:
        cs.check_witness(w)  # the voice of every rejection: raises
        raise RuntimeError(
            f"the native witness check found {bad_rows.size} unsatisfied constraints (first: "
            f"{bad_rows[:3].tolist()}) and {bad_tags.size} exceeded width tags (first wires: "
            f"{plan.width_wire[bad_tags[:3]].tolist()}) in a witness ConstraintSystem.check_witness "
            "accepts: the two disagree, and the witness is not passed on"
        )
