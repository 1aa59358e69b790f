"""Sequence/context parallelism for per-byte recurrences (the CP axis).

The framework's "long context" is the email byte axis: every hot witness
recurrence — DFA regex scans, SHA-256 block chaining — is a left fold
over bytes (SURVEY.md §5 long-context).  The reference scales this by
moving a hashed prefix OUT of the circuit (`Sha256Partial` +
`generate_input.ts:110-124`); the TPU-native generalisation is a
blockwise scan over a sharded byte axis — the same shape as ring
attention / Ulysses for transformers, specialised to monoid folds:

  1. each device folds ITS byte block into a composed transition
     function (DFA: a state->state map; SHA: a midstate),
  2. one collective exchanges the per-device functions and every device
     composes the prefix of the devices before it (the "handoff" —
     exactly the Sha256Partial midstate trick, generalised), and
  3. each device re-scans its block from its entry state, emitting the
     per-byte states.

DFA transition functions compose by GATHER (f∘g = g[f]), so the whole
pipeline is int32 vector ops — no matmuls, no field arithmetic.
Differentially tested against the host DFA simulation in
tests/test_seqscan.py.
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P


def dfa_tables(dfa) -> np.ndarray:
    """(256, S+1) int32: next-state per (byte, state); the extra state S
    is the absorbing dead state (-1 entries map to it)."""
    S = dfa.n_states
    t = np.full((256, S + 1), S, dtype=np.int32)
    nxt = np.asarray(dfa.next)  # (S, 256)
    t[:, :S] = np.where(nxt.T >= 0, nxt.T, S)
    return t


@lru_cache(maxsize=None)
def _dfa_scan_fn(mesh: Mesh, axis: str, S: int, block: int):
    """Cached jitted shard_map executable per (mesh, dfa size, block)."""
    n_dev = mesh.shape[axis]
    dead = S  # absorbing

    def local(bytes_blk: jnp.ndarray, table: jnp.ndarray) -> jnp.ndarray:
        # bytes_blk: (block,) uint8 — this device's slice; table: (256, S+1)

        # 1. fold the block into one composed transition fn (S+1,)
        def compose(f, b):
            return table[b][f], None

        ident = jnp.arange(S + 1, dtype=jnp.int32)
        f_blk, _ = jax.lax.scan(compose, ident, bytes_blk)

        # 2. handoff: gather every device's function, compose the strict
        # prefix of this device (the midstate-handoff collective)
        fns = jax.lax.all_gather(f_blk, axis)  # (n_dev, S+1)
        idx = jax.lax.axis_index(axis)

        def prefix_step(carry, i):
            f = fns[i]
            nxt = jnp.where(i < idx, f[carry], carry)
            return nxt, None

        entry, _ = jax.lax.scan(prefix_step, jnp.int32(0), jnp.arange(n_dev))

        # 3. re-scan the block from the entry state, emitting states
        def step(s, b):
            ns = table[b][s]
            return ns, ns

        _, states = jax.lax.scan(step, entry, bytes_blk)
        return states  # (block,) state AFTER each byte

    return jax.jit(
        jax.shard_map(
            local,
            mesh=mesh,
            in_specs=(P(axis), P(None, None)),
            out_specs=P(axis),
            check_vma=False,
        )
    )


def dfa_scan_sharded(data: jnp.ndarray, dfa, mesh: Mesh, axis: str = "shard") -> jnp.ndarray:
    """Run `dfa` over a byte vector sharded on `mesh`'s `axis`.

    data: (n,) uint8, n divisible by the mesh size.  Returns (n,) int32 —
    the DFA state after each byte (dead state = dfa.n_states), sharded
    like the input.  Exactly equals the sequential host simulation."""
    n_dev = mesh.shape[axis]
    n = data.shape[0]
    assert n % n_dev == 0, "pad the byte axis to the mesh size first"
    table = jnp.asarray(dfa_tables(dfa))
    fn = _dfa_scan_fn(mesh, axis, dfa.n_states, n // n_dev)
    return fn(jnp.asarray(data), table)


def dfa_scan_host(data, dfa) -> np.ndarray:
    """Sequential oracle (same dead-state convention)."""
    S = dfa.n_states
    t = dfa_tables(dfa)
    s = 0
    out = np.empty(len(data), dtype=np.int32)
    for i, b in enumerate(bytes(data)):
        s = int(t[b][s]) if s != S else S
        out[i] = s
    return out
