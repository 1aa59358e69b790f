"""Array-path trusted setup: ConstraintSystem -> DeviceProvingKey directly.

`snark.groth16.setup` materialises every query point as a Python tuple —
fine at gadget-test scale, hopeless at the flagship circuit's 6.4M wires
(the reference pays 782 s on a 48-core EC2 box for the same step,
`zkp-mooc-hackathon-submission.md:98`).  This path keeps everything in
numpy limb arrays end to end:

  tau-evaluation loops   : Python ints over sparse rows (linear, cheap)
  fixed-base G1/G2 muls  : csrc batch kernels, Montgomery-limb output,
                           batch-inverted normalization (native.lib)
  QAP coeff arrays       : vectorized bytes->u16 limb decode

The emitted DeviceProvingKey is bit-identical to
`device_pk(setup(cs, seed))` for the same seed — pinned by
tests/test_setup_device.py — and the matching VerifyingKey is a host
object usable by `snark.groth16.verify` and the Solidity export.

The same key feeds both device roads unchanged: the one-chip batch and
the pod-mesh batch (ZKP2P_TPU_SHARD=on, docs/TPU.md).  The pruned b/c query lanes emitted here are NOT padded
to any mesh width — `groth16_tpu.place_key` pads the bases with
infinity lanes once a key and a mesh, as it lays the key on that mesh,
so one key serves every mesh shape.
"""

from __future__ import annotations

from typing import List, Tuple

import numpy as np

from ..curve.host import G1_GENERATOR, G2_GENERATOR, g1_gen_mul, g2_gen_mul
from ..field.bn254 import R, fr_domain_root, fr_inv
from ..native.lib import g1_fixed_base_batch_mont_limbs, g2_fixed_base_batch_mont_limbs
from ..snark.groth16 import VerifyingKey, _batch_inv, _seeded_scalars, coset_gen, domain_size_for, qap_rows
from ..snark.r1cs import ConstraintSystem
from .groth16_tpu import DeviceProvingKey, _rows_to_arrays, key_arrays_home


def setup_device(cs: ConstraintSystem, seed: str = "zkp2p-tpu-dev") -> Tuple[DeviceProvingKey, VerifyingKey]:
    """Development setup straight to device arrays (same key material as
    `setup(cs, seed)`).  Requires the native library (use `setup` +
    `device_pk` for small circuits without a toolchain)."""
    tau, alpha, beta, gamma, delta = _seeded_scalars(seed, 5)
    rows = qap_rows(cs)
    m = domain_size_for(cs)
    n_wires = cs.num_wires

    w = fr_domain_root(m.bit_length() - 1)
    z_tau = (pow(tau, m, R) - 1) % R
    minv = fr_inv(m)
    wjs: List[int] = []
    wj = 1
    for _ in range(m):
        wjs.append(wj)
        wj = wj * w % R
    denom_inv = _batch_inv([(tau - wj) % R for wj in wjs])
    lag = [z_tau * wj % R * minv % R * di % R for wj, di in zip(wjs, denom_inv)]

    a_tau = [0] * n_wires
    b_tau = [0] * n_wires
    c_tau = [0] * n_wires
    for j, (ra, rb, rc) in enumerate(rows):
        lj = lag[j]
        for wi, coeff in ra.items():
            a_tau[wi] = (a_tau[wi] + coeff * lj) % R
        for wi, coeff in rb.items():
            b_tau[wi] = (b_tau[wi] + coeff * lj) % R
        for wi, coeff in rc.items():
            c_tau[wi] = (c_tau[wi] + coeff * lj) % R

    delta_inv = fr_inv(delta)
    gamma_inv = fr_inv(gamma)
    vals = [(beta * a_tau[i] + alpha * b_tau[i] + c_tau[i]) % R for i in range(n_wires)]
    scaled = [
        v * (gamma_inv if i <= cs.num_public else delta_inv) % R for i, v in enumerate(vals)
    ]

    g = coset_gen(m.bit_length() - 1)
    tau_p = tau * fr_inv(g) % R
    z_tau_p = (pow(tau_p, m, R) - 1) % R
    z_coset = (pow(g, m, R) - 1) % R
    scale = z_tau_p * minv % R * z_tau % R * fr_inv(delta * z_coset % R) % R
    hden_inv = _batch_inv([(tau_p - wj) % R for wj in wjs])
    h_scalars = [scale * wj % R * di % R for wj, di in zip(wjs, hden_inv)]

    # Prune the b/c queries to their non-infinity lanes (device_pk does
    # the same from the point lists): b_tau is zero for every wire absent
    # from B (half the circuit, measured), so both the setup-time
    # fixed-base muls AND the prove-time b1/b2/c MSMs halve.
    from .groth16_tpu import _prune_sel

    b_flags = [v % R != 0 for v in b_tau]
    c_flags = [i > cs.num_public and scaled[i] % R != 0 for i in range(n_wires)]
    b_sel = _prune_sel(b_flags)
    c_sel = _prune_sel(c_flags)
    # Degenerate fallback lanes ([0] when nothing survives pruning) must
    # be INFINITY bases: index 0 is wire one, whose gamma-scaled C point
    # is NOT infinity — mapping the scalar to 0 here keeps the MSM a
    # no-op for any witness.  (b_tau[0] is already 0 whenever the b
    # fallback triggers, but map it too for uniformity.)
    b_scalars = [b_tau[i] if b_flags[i] else 0 for i in b_sel]
    c_scalars = [scaled[i] if c_flags[i] else 0 for i in c_sel]
    a_bases = g1_fixed_base_batch_mont_limbs(G1_GENERATOR, a_tau)
    b1_bases = g1_fixed_base_batch_mont_limbs(G1_GENERATOR, b_scalars)
    b2_bases = g2_fixed_base_batch_mont_limbs(G2_GENERATOR, b_scalars)
    cq_bases = g1_fixed_base_batch_mont_limbs(G1_GENERATOR, c_scalars)
    h_bases = g1_fixed_base_batch_mont_limbs(G1_GENERATOR, h_scalars)
    if a_bases is None or b2_bases is None:
        raise RuntimeError("native library unavailable; use snark.groth16.setup for small circuits")

    # IC points (host form, few) for the verifier.
    from ..curve.host import g1_gen_mul_batch

    ic = g1_gen_mul_batch(scaled[: cs.num_public + 1])

    home = key_arrays_home(m.bit_length() - 1)  # the default device, or the host for a key only a mesh can take
    a_arr = _rows_to_arrays([t[0] for t in rows], m, home)
    b_arr = _rows_to_arrays([t[1] for t in rows], m, home)

    # Width-classed MSM split — THE shared rule from groth16_tpu
    # (class_sels), so this dev-setup path and the pk-import path can
    # never drift.  The degenerate [0] fallback lanes are infinity
    # bases, harmless in either class.
    from .groth16_tpu import class_sels, widths_array

    widths = widths_array(cs)
    a_nsel, a_wsel = class_sels(widths, np.arange(n_wires, dtype=np.int32))
    b_nsel, b_wsel = class_sels(widths, np.asarray(b_sel))
    c_nsel, c_wsel = class_sels(widths, np.asarray(c_sel))
    dpk = DeviceProvingKey(
        n_public=cs.num_public,
        n_wires=n_wires,
        log_m=m.bit_length() - 1,
        a_coeff=a_arr[0], a_wire=a_arr[1], a_row=a_arr[2],
        b_coeff=b_arr[0], b_wire=b_arr[1], b_row=b_arr[2],
        a_bases=tuple(home(x) for x in a_bases),
        b1_bases=tuple(home(x) for x in b1_bases),
        b2_bases=tuple(home(x) for x in b2_bases),
        c_bases=tuple(home(x) for x in cq_bases),
        h_bases=tuple(home(x) for x in h_bases),
        b_sel=home(b_sel),
        c_sel=home(c_sel),
        a_nsel=home(a_nsel), a_wsel=home(a_wsel),
        b_nsel=home(b_nsel), b_wsel=home(b_wsel),
        c_nsel=home(c_nsel), c_wsel=home(c_wsel),
        alpha_1=g1_gen_mul(alpha),
        beta_1=g1_gen_mul(beta),
        beta_2=g2_gen_mul(beta),
        delta_1=g1_gen_mul(delta),
        delta_2=g2_gen_mul(delta),
    )
    vk = VerifyingKey(
        n_public=cs.num_public,
        alpha_1=dpk.alpha_1,
        beta_2=dpk.beta_2,
        gamma_2=g2_gen_mul(gamma),
        delta_2=dpk.delta_2,
        ic=ic,
    )
    return dpk, vk
