"""One transform over the chips of a mesh group: each chip a share of the vector.

Where one proof's h stage does not fit a chip (the onramp circuit at its
published size: 2^23 points, 512 MB a vector) the prover runs it over the
S chips of the proof's group (`prover.groth16_tpu._h_shard_fn`), every
2^log_m-point vector in S shares of L = m / S points.  A transform is then
the four-step decomposition m = S x L with ONE exchange over ICI: its
length-L part is `ops.ntt`'s constant-geometry ladder, unchanged (the
`butterfly` kernel where the field's product is one), on each chip's own
share, and its length-S part is a DFT across chips: an `all_gather` of the
shares and S - 1 products by constants.

Two layouts, so that a transform needs no transpose of its own:

- **block**: chip c holds points [c*L, (c+1)*L).  Evaluations live here:
  the rows of the QAP matrices a chip's matvec sums, and the columns of h
  whose bases `place_key` gave the chip.
- **strided**: chip c holds the indices congruent to c mod S, index
  c + S*j at local position j.  Coefficients live here.

`intt_block_to_strided` is decimation in frequency (the DFT across chips
first), `ntt_strided_to_block` decimation in time (the DFT last): an
inverse transform, the coset shift and a forward transform cross the
chips twice and come back to the block layout.  With j = j2 + L*j1 and
i = k1 + S*k2,

    c[k1 + S*k2] = sum_j2 wL^-(j2*k2) * w^-(j2*k1) * [sum_j1 wS^-(j1*k1) * x[j2 + L*j1]]

and the forward direction is its mirror image.  Field arithmetic is exact
and every product, sum and difference is canonical, so the result is
bit-equal to `ops.ntt.intt` / `coset_shift` / `ntt` on one device
(tests/test_h_sharded.py, against both ladders).

Everything here runs INSIDE a `shard_map` over the pod mesh's "shard" axis
(`AXIS`: the one the key's bases are split over), but `shard_tables`,
which builds what the programs take as arguments: at 2^23 a table is
128 MB a chip, too much to close over as a constant of a program.
"""

from __future__ import annotations

from functools import lru_cache
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..field.bn254 import R, fr_domain_root, fr_inv
from ..field.jfield import FR
from ..ops.ntt import _powers_by_doubling, _transform, domain
from ..snark.groth16 import coset_gen

AXIS = "shard"


@lru_cache(maxsize=None)
def _cross_constants(n_ici: int, inverse: bool) -> np.ndarray:
    """D[k][j] = wS^(+-j*k), (S, S, 16) Montgomery limbs: row k is what
    chip k multiplies the gathered shares by."""
    w = fr_domain_root(n_ici.bit_length() - 1)
    if inverse:
        w = fr_inv(w)
    return np.stack([np.stack([FR.to_mont_host(pow(w, j * k % n_ici, R)) for j in range(n_ici)]) for k in range(n_ici)])


def _across_chips(x: jnp.ndarray, n_ici: int, inverse: bool) -> jnp.ndarray:
    """The length-S DFT across the chips of a group, one for every local
    position: out on chip k = sum_j wS^(+-j*k) * (x on chip j)."""
    shares = jax.lax.all_gather(x, AXIS, axis=0)
    row = jnp.asarray(_cross_constants(n_ici, inverse))[jax.lax.axis_index(AXIS)]
    acc = shares[0]  # wS^0
    for j in range(1, n_ici):
        acc = FR.add(acc, FR.mul(shares[j], row[j]))
    return acc


def intt_block_to_strided(x: jnp.ndarray, tables: Dict[str, jnp.ndarray], n_ici: int) -> jnp.ndarray:
    """(..., L, 16) evaluations in the block layout -> the coefficients
    in the strided layout, ALREADY on the coset: `tables["coset"]` holds
    m^-1 * g^i for each coefficient index i the chip holds, so `intt`'s
    scaling and `coset_shift` are one product."""
    u = FR.mul(_across_chips(x, n_ici, inverse=True), tables["pre"])
    return FR.mul(_transform(u, tables["tw_inv"]), tables["coset"])


def ntt_strided_to_block(y: jnp.ndarray, tables: Dict[str, jnp.ndarray], n_ici: int) -> jnp.ndarray:
    """(..., L, 16) coefficients in the strided layout -> evaluations in
    the block layout."""
    v = FR.mul(_transform(y, tables["tw"]), tables["post"])
    return _across_chips(v, n_ici, inverse=False)


def ici_bytes_a_transform(log_m: int, n_ici: int) -> int:
    """What one transform of one vector moves between chips, summed over
    the chips that receive it: each takes the other S - 1 shares, 64 B a
    point."""
    return (n_ici - 1) * (64 << log_m)


TABLE_SPECS = {"pre": P(AXIS), "coset": P(AXIS), "post": P(AXIS), "tw": P(), "tw_inv": P()}


@lru_cache(maxsize=None)
def shard_tables(mesh, log_m: int) -> Dict[str, jnp.ndarray]:
    """What the sharded transforms of a 2^log_m domain take as arguments
    on `mesh`, built once a mesh and a size and kept on it:

    - `pre`, `coset`, `post`: (S * L, 16), a chip its L rows
      (`TABLE_SPECS`): w^-(c*j), m^-1 * g^(c + S*j) and w^(c*j) for chip
      c and local position j, each chip growing its own by doubling
      (`ops.ntt._powers_by_doubling`): no table crosses the host;
    - `tw`, `tw_inv`: the length-L ladder's twiddles (`ops.ntt.domain`),
      the same on every chip."""
    n_ici, m = mesh.shape[AXIS], 1 << log_m
    n_loc = m // n_ici
    w, g = fr_domain_root(log_m), coset_gen(log_m)
    n_rounds = max(1, (n_loc - 1).bit_length())

    def doubling(base: int) -> np.ndarray:
        return np.stack([FR.to_mont_host(pow(base, 1 << i, R)) for i in range(n_rounds)])

    ones = [1] * n_ici
    kinds = {  # name -> (the base whose powers chip c holds, the constant it multiplies them by)
        "pre": ([pow(fr_inv(w), c, R) for c in range(n_ici)], ones),
        "coset": ([pow(g, n_ici, R)] * n_ici, [fr_inv(m) * pow(g, c, R) % R for c in range(n_ici)]),
        "post": ([pow(w, c, R) for c in range(n_ici)], ones),
    }
    factors = np.stack([np.stack([doubling(b) for b in bases]) for bases, _ in kinds.values()], axis=1)  # (S, 3, rounds, 16)
    scales = np.stack([np.stack([FR.to_mont_host(s) for s in scale]) for _, scale in kinds.values()], axis=1)  # (S, 3, 16)

    def local(f, s):
        return tuple(FR.mul(_powers_by_doubling(f[0, i], n_loc), s[0, i]) for i in range(len(kinds)))

    built = jax.jit(jax.shard_map(local, mesh=mesh, in_specs=(P(AXIS), P(AXIS)), out_specs=(P(AXIS),) * len(kinds),
                                  check_vma=False))(factors, scales)
    tables = dict(zip(kinds, built))
    whole = NamedSharding(mesh, P())
    ladder = domain(n_loc.bit_length() - 1)
    tables["tw"], tables["tw_inv"] = jax.device_put(ladder["tw"], whole), jax.device_put(ladder["tw_inv"], whole)
    return tables
