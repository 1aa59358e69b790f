"""Circuit registry: named builders + declared public layouts, with the
static soundness audit (snark.analysis) as the ADMISSION PRECONDITION.

ROADMAP item 1 wants the service to serve many circuits; ISSUE 15's
point is that every circuit must pass an automated soundness gate
before it is served — a hand review per minted regex circuit does not
scale.  `audited()` is that gate: build -> audit (cached by structural
digest under .bench_cache) -> REFUSE on any unwaived finding.  The CLI
`setup` path and `zkp2p-tpu lint --circuits` / `make circuit-audit`
both route through here, and each in-process audit lands in
run_manifest (utils.metrics) beside the knob/gate arms.

Each spec declares its on-chain public-signal count (`n_public`) — the
audit's public-layout rule closes the docs/EVM_PARITY.md loop per
circuit: the venmo layout is the contract's uint[26]
(`Verifier.sol:360` / `Ramp.sol:253-293`), and a circuit whose built
n_public drifts from its declaration is refused before any key is cut.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Tuple

from ..snark.analysis import audit_circuit, require_clean


@dataclass(frozen=True)
class CircuitSpec:
    name: str
    build: Callable[[], object]  # -> ConstraintSystem, inputs marked
    n_public: int  # declared on-chain signal layout (public-layout rule)
    description: str
    flagship: bool = False  # multi-minute build: slow tier only


def _build_venmo_mini():
    from .venmo import VenmoParams, build_venmo_circuit

    cs, _ = build_venmo_circuit(VenmoParams(max_header_bytes=256, max_body_bytes=192))
    return cs


def _build_venmo_full():
    from .venmo import VenmoParams, build_venmo_circuit

    cs, _ = build_venmo_circuit(VenmoParams())  # 1024/6400: the 4.9M flagship
    return cs


def _build_email_mini():
    from .email_verify import EmailVerifyParams, build_email_verify

    cs, _ = build_email_verify(
        EmailVerifyParams(max_header_bytes=256, max_body_bytes=128)
    )
    return cs


def _build_email_full():
    from .email_verify import EmailVerifyParams, build_email_verify

    cs, _ = build_email_verify(EmailVerifyParams())  # 1024/1536: the published size, 2.14M constraints
    return cs


def _build_amount_demo():
    from .amount_demo import amount_circuit

    cs, _, _ = amount_circuit()
    return cs


def _build_dryrun_vid():
    from .amount_demo import dryrun_circuit

    cs, _, _ = dryrun_circuit()
    return cs


def build_sha2b() -> Tuple[object, List[int]]:
    """Two-block fixed SHA-256 over 128 padded private bytes (the
    flagship's dominant gadget family at a 2^16 domain): the circuit of
    the benchmark's sha2b cells.  Returns (cs, digest bit wires); no
    publics (a caller compares the witness digest against hashlib)."""
    from ..gadgets import core, sha256
    from ..snark.r1cs import ConstraintSystem

    cs = ConstraintSystem("sharded-scale-sha2b")
    msg = cs.new_wires(128, "msg")
    cs.mark_input(msg)
    bits = core.assert_bytes(cs, msg, "msg")
    out = sha256.sha256_blocks(cs, bits, None)
    return cs, out


def build_sha256_preimage(message_bytes: int) -> Tuple[object, List[int]]:
    """SHA-256 of a private preimage of exactly `message_bytes` bytes
    with the digest public: the circuit of public Groth16 prover
    comparisons (celer-network/zk-benchmark, "The Pantheon of Zero
    Knowledge Proof Development Frameworks"), and of the benchmark's
    sha256-4k cell.  Where `build_sha2b` takes pre-padded bytes, so the
    padding is the prover's and the digest is of nothing in particular,
    this wires the padding as constants.  Public signals: [0] the first
    16 digest bytes, [1] the last 16, each as a big-endian 128-bit
    integer.  Returns (cs, message byte wires)."""
    from ..gadgets import core, sha256
    from ..snark.r1cs import LC, ConstraintSystem

    cs = ConstraintSystem(f"sha256-preimage-{message_bytes}")
    halves = [cs.new_public("digest_hi"), cs.new_public("digest_lo")]
    msg = cs.new_wires(message_bytes, "msg")
    cs.mark_input(msg)
    bits = core.assert_bytes(cs, msg, "msg") + sha256.padding_byte_bits(cs, message_bytes)
    out = sha256.sha256_blocks(cs, bits, None)
    # `out` is h0..h7, bit i of a word at weight 2^i; the digest is the words big-endian, h0 first
    for half, pub in enumerate(halves):
        terms = {out[32 * (4 * half + w) + i]: 1 << (i + 32 * (3 - w)) for w in range(4) for i in range(32)}
        cs.enforce_eq(LC(terms), LC.of(pub), "digest/pack")
    return cs, msg


def sha256_preimage_inputs(msg_wires: List[int], payload: Dict) -> Tuple[List[int], Dict[int, int]]:
    """A request of `build_sha256_preimage`'s circuit -> (public signals,
    private inputs).  The message comes as {"msg": [one int 0-255 a
    byte]} or as {"msg_hex": "two hex digits a byte"} (a third of the
    bytes on the wire, and written and parsed as one string).  A payload
    of another length, with a value outside a byte or with digits that
    are not hex raises."""
    import hashlib

    msg = bytes.fromhex(payload["msg_hex"]) if "msg_hex" in payload else bytes(payload["msg"])  # both refuse what is no byte
    if len(msg) != len(msg_wires):
        raise ValueError(f"the circuit hashes {len(msg_wires)} bytes, the request carries {len(msg)}")
    digest = hashlib.sha256(msg).digest()
    return [int.from_bytes(digest[:16], "big"), int.from_bytes(digest[16:], "big")], dict(zip(msg_wires, msg))


def _build_regex_actor():
    """Minted from regexc (the reference's regex_to_circom L0 layer):
    see regexc.compiler.reveal_circuit."""
    from ..regexc.compiler import VENMO_ACTOR_ID, reveal_circuit

    cs, _ = reveal_circuit(
        VENMO_ACTOR_ID, n_bytes=48, reveal_len=14, name="regex_actor"
    )
    return cs


SPECS: Dict[str, CircuitSpec] = {
    s.name: s
    for s in (
        CircuitSpec(
            "venmo", _build_venmo_mini, 26,
            "P2POnrampVerify at the CI shape (256/192 header/body)",
        ),
        CircuitSpec(
            "venmo-full", _build_venmo_full, 26,
            "the 4.94M-constraint production flagship (1024/6400)",
            flagship=True,
        ),
        CircuitSpec(
            "email_verify", _build_email_mini, 20,
            "generic DKIM EmailVerify at the CI shape (256/128)",
        ),
        CircuitSpec(
            "email_verify-full", _build_email_full, 20,
            "EmailVerify(1024, 1536, 121, 17) as email.circom:222 instantiates it (2^22 domain)",
            flagship=True,
        ),
        CircuitSpec(
            "amount_demo", _build_amount_demo, 3,
            "Venmo amount block over a 32-byte subject slice",
        ),
        CircuitSpec(
            "dryrun_vid", _build_dryrun_vid, 1,
            "venmo-id packing + Poseidon (the multichip dryrun shape)",
        ),
        CircuitSpec(
            "sha2b", lambda: build_sha2b()[0], 0,
            "two-block SHA-256, the benchmark's sha2b shape",
        ),
        CircuitSpec(
            "sha256-64", lambda: build_sha256_preimage(64)[0], 2,
            "SHA-256 of a 64-byte preimage, digest public, at the CI shape (two blocks)",
        ),
        CircuitSpec(
            "sha256-4k", lambda: build_sha256_preimage(4096)[0], 2,
            "SHA-256 of a 4,096-byte preimage, digest public: the benchmark's sha256-4k (65 blocks, 2^21 domain)",
            flagship=True,
        ),
        CircuitSpec(
            "regex_actor", _build_regex_actor, 2,
            "regexc-minted actor_id reveal circuit (the L0 minting path)",
        ),
    )
}


def circuit_ids(include_flagship: bool = False) -> List[str]:
    return [
        n for n, s in SPECS.items() if include_flagship or not s.flagship
    ]


def build(name: str):
    spec = SPECS.get(name)
    if spec is None:
        raise KeyError(
            f"unknown circuit {name!r}; registered: {', '.join(sorted(SPECS))}"
        )
    return spec.build()


def audited(name: str, use_cache: bool = True, cache_dir: Optional[str] = None):
    """The admission gate: build the named circuit, audit it (report
    cached by circuit digest), and REFUSE — CircuitAuditError — on any
    unwaived soundness finding.  Returns (cs, report)."""
    spec = SPECS.get(name)
    if spec is None:
        raise KeyError(
            f"unknown circuit {name!r}; registered: {', '.join(sorted(SPECS))}"
        )
    cs = spec.build()
    report = audit_circuit(
        cs,
        name=name,
        declared_n_public=spec.n_public,
        use_cache=use_cache,
        cache_dir=cache_dir,
    )
    require_clean(report)
    return cs, report
