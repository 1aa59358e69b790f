"""The comparison that decides `correct`.

(a) every proof the service produced satisfies the pairing against its own
    public signals under the run's verifying key — checked here with the
    benchmark's copy of the verifier (benchmarks/reference), after the
    drain, not taken on trust from the service's own sample verify — and
    those signals are the ones its own request asks for, worked out from
    the request by benchmarks/reference/public_signals.py;
(b) the pinned-(r, s) warm-up batch is pairing-valid, tied to its requests
    in the same way, and byte-equal to the independent C++ prover, on
    every proof of the batch.

Field arithmetic is exact: every limit is 0.
"""

from __future__ import annotations

import concurrent.futures
import json
import multiprocessing
import os
from typing import Callable, Dict, List, Optional, Sequence

from benchmarks.reference.groth16_verify import verify_json
from benchmarks.reference.public_signals import differing


def _g2_ints(pt) -> List[int]:
    return [int(pt[0].c0), int(pt[0].c1), int(pt[1].c0), int(pt[1].c1)]


def vk_to_ints(vk) -> Dict:
    """The program's VerifyingKey, stripped to integers for the reference."""
    return {"alpha_1": [int(c) for c in vk.alpha_1], "beta_2": _g2_ints(vk.beta_2),
            "gamma_2": _g2_ints(vk.gamma_2), "delta_2": _g2_ints(vk.delta_2),
            "ic": [[int(c) for c in p] for p in vk.ic]}


def proof_bytes(proof_json: Dict) -> bytes:
    """The 256 bytes of a proof (A, B, C coordinates, 32 bytes each)."""
    a, b, c = proof_json["pi_a"], proof_json["pi_b"], proof_json["pi_c"]
    coords = [a[0], a[1], b[0][0], b[0][1], b[1][0], b[1][1], c[0], c[1]]
    return b"".join(int(x).to_bytes(32, "big") for x in coords)


def bytes_differing(p: Dict, q: Dict) -> int:
    return sum(x != y for x, y in zip(proof_bytes(p), proof_bytes(q)))


def _verify_files(vk_ints: Dict, proof_path: str, public_path: str) -> bool:
    try:
        with open(proof_path) as f:
            proof = json.load(f)
        with open(public_path) as f:
            public = json.load(f)
    except (OSError, ValueError):
        return False
    return verify_json(vk_ints, proof, public)


def verify_many(vk_ints: Dict, items: Sequence, workers: int) -> List[bool]:
    """items: (proof_json, public_json) pairs or (proof_path, public_path)
    pairs.  About 0.4 s of Python integers a proof, so a window's worth
    goes to worker processes; they import no JAX and hold no chip."""
    fn = _verify_files if items and isinstance(items[0][0], str) else verify_json
    if workers <= 1 or len(items) <= 2:
        return [fn(vk_ints, a, b) for a, b in items]
    ctx = multiprocessing.get_context("spawn")
    with concurrent.futures.ProcessPoolExecutor(max_workers=min(workers, len(items)), mp_context=ctx) as pool:
        return list(pool.map(fn, [vk_ints] * len(items), [a for a, _ in items], [b for _, b in items]))


def check_window(vk_ints: Dict, spool: str, requests: List[Dict], workers: int,
                 expected_public: Optional[Callable[[Dict], Dict[int, int]]] = None) -> int:
    """Sets `valid` on every request that ended `done`: its proof passes the
    pairing AND its public signals are its own request's.  Returns how many
    proofs passed the pairing with signals that are not."""
    done = [r for r in requests if r.get("state") == "done"]
    paths = [(os.path.join(spool, r["rid"] + ".proof.json"), os.path.join(spool, r["rid"] + ".public.json"))
             for r in done]
    untied = 0
    for r, (_, public_path), ok in zip(done, paths, verify_many(vk_ints, paths, workers)):
        tied = True
        if ok and expected_public is not None:
            with open(public_path) as f:
                tied = differing(expected_public(r["payload"]), json.load(f)) == 0
        untied += ok and not tied
        r["valid"] = ok and tied
    return untied


def check_pinned(vk_ints: Dict, pinned_json: List[Dict], publics_json: List[List], natives_json: List[Dict],
                 workers: int, expected: Sequence[Dict[int, int]] = ()) -> Dict:
    """The warm-up batch against the oracle: numbers compared, each with limit 0."""
    valid = verify_many(vk_ints, list(zip(pinned_json, publics_json)), workers)
    return {"pinned_pairing_failures": valid.count(False),
            "pinned_signals_not_the_requests": sum(differing(e, p) for e, p in zip(expected, publics_json)),
            "pinned_bytes_differing_from_native": sum(bytes_differing(p, n) for p, n in zip(pinned_json, natives_json))}
