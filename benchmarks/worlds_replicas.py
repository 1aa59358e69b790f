"""The world of the configuration sha2b-replica4: the circuit and wiring
of a one-service adapter (`replica_of`, `harness/worlds.py`'s
`input_wires` unless the configuration names another), served by a
replica set (`zkp2p_tpu/pipeline/replicas.py::ReplicaSet`: `replicas`
one-chip services of this process on the one spool, each built as that
adapter builds its one) instead of by one service.  Named by the configuration
file as `module:function`, like `worlds_email.py`'s.  The cell takes
`chips` = `replicas`, and `arms.replicas` holds a run to the count.

When the set has drained, its spool is held to the deployment's own
guarantee by `reference/exactly_once.py` (every request ended once, under
one replica): each number is printed beside its limit of 0, and a spool
that fails ends the run with no result line.
"""

from __future__ import annotations

from typing import Dict


def replica_set(config: Dict):
    try:
        from zkp2p_tpu.pipeline.replicas import ReplicaSet
    except ImportError:
        # before the circuit and the key are built: a program that predates the
        # configuration fails in seconds, not after minutes of set-up
        raise SystemExit("benchmarks: this program has no zkp2p_tpu.pipeline.replicas.ReplicaSet "
                         "(it predates the configuration sha2b-replica4) — nothing measured") from None
    from benchmarks.harness.worlds import _resolve
    from benchmarks.reference import exactly_once

    # the one-service adapter this world multiplies: a replica is built as that adapter builds its one
    cs, make_one = _resolve(config.get("replica_of", "benchmarks.harness.worlds:input_wires"))(config)
    n = int(config["replicas"])

    class CheckedSet(ReplicaSet):
        def run(self, spool: str, poll_s: float = 1.0, **kw) -> str:
            why = super().run(spool, poll_s=poll_s, **kw)
            res = exactly_once.check(spool)
            for name, value in res["numbers"].items():
                print(f"[bench] check: {name} = {value} (limit 0)", flush=True)
            print(f"[bench] replicas served: {res['served']} of {res['ended']} ended requests", flush=True)
            if not res["ok"]:
                raise SystemExit(f"benchmarks: the spool fails exactly-once over {n} replicas: {res}")
            return why

    def make_service(dpk, vk, **kw):
        return CheckedSet(lambda key: make_one(key, vk, **kw), dpk, n=n)
    return cs, make_service
