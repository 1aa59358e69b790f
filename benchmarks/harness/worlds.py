"""Payload generators and witness adapters.  A configuration file names
each as `module:function` (`payload`, `adapter`, and `public_tie` for what
ties a proof to its request), so a new world arrives as files.

A payload generator turns (rng, i) into one request's JSON; the rng is
seeded from --seed and the request's index, so a seed fixes every request
whatever order clients send them in.  An adapter builds the circuit and
wires a ProvingService to it the way the program's own entry points do
(`ProvingService.for_venmo`; `chip_smoke.sha2b_world`).
"""

from __future__ import annotations

import dataclasses
import importlib
import random
from typing import Callable, Dict, List, Optional


@dataclasses.dataclass
class World:
    cs: object
    payload: Callable[[random.Random, int], Dict]
    make_service: Callable[..., object]  # (dpk, vk, **ProvingService keywords)
    public_tie: Optional[Callable[[Dict], Dict[int, int]]]  # payload -> {signal index: value}; None: no public signals


def _resolve(path: str):
    module, _, attr = path.partition(":")
    return getattr(importlib.import_module(module), attr)


# ------------------------------------------------------------------ payloads


def venmo_receipt(config: Dict, cs) -> Callable[[random.Random, int], Dict]:
    """The synthetic-demo request shape of `ProvingService.for_venmo`: a
    19-digit venmo id, a whole-dollar amount, the order and claim ids."""
    def payload(rng: random.Random, i: int) -> Dict:
        return {"raw_id": "".join(rng.choice("0123456789") for _ in range(19)),
                "amount": str(rng.randrange(10, 1000)), "order_id": i + 1, "claim_id": i}
    return payload


def input_bytes(config: Dict, cs) -> Callable[[random.Random, int], Dict]:
    """One byte per input wire of the circuit (sha2b: the 128 message bytes)."""
    n = len(cs.input_wires)

    def payload(rng: random.Random, i: int) -> Dict:
        return {"msg": [rng.randrange(256) for _ in range(n)]}
    return payload


# ------------------------------------------------------------------ adapters


def venmo(config: Dict):
    from zkp2p_tpu.models.venmo import VenmoParams, build_venmo_circuit
    from zkp2p_tpu.pipeline.service import ProvingService

    params = VenmoParams(**{k: config[k] for k in ("max_header_bytes", "max_body_bytes", "n", "k")})
    cs, lay = build_venmo_circuit(params)

    def make_service(dpk, vk, **kw):
        return ProvingService.for_venmo(cs, lay, params, dpk, vk, prover_fn=None, **kw)
    return cs, make_service


def input_wires(config: Dict):
    """A circuit whose inputs are private wires marked with `mark_input`,
    built by `config["builder"]` ("module:function" -> cs or (cs, ...))."""
    from zkp2p_tpu.pipeline.service import ProvingService

    built = _resolve(config["builder"])()
    cs = built[0] if isinstance(built, tuple) else built
    wires = sorted(cs.input_wires)

    def make_service(dpk, vk, **kw):
        return ProvingService(
            cs, dpk, vk,
            witness_fn=lambda p: cs.witness([], dict(zip(wires, p["msg"]))),
            public_fn=lambda w: list(w[1 : cs.num_public + 1]),
            prover_fn=None, **kw)
    return cs, make_service


def build_world(config: Dict) -> World:
    cs, make_service = _resolve(config["adapter"])(config)
    tie = config.get("public_tie")
    return World(cs=cs, payload=_resolve(config["payload"])(config, cs), make_service=make_service,
                 public_tie=_resolve(tie) if tie else None)


def payload_for(world: World, seed: int, i: int) -> Dict:
    return world.payload(random.Random(f"payload-{seed}-{i}"), i)


def witnesses(svc, payloads: List[Dict]) -> list:
    """The witnesses of a batch, by the tier the service itself would use."""
    if svc.inputs_fn is not None:
        return list(svc.cs.witness_batch([svc.inputs_fn(p) for p in payloads]))
    return [svc.witness_fn(p) for p in payloads]
