"""A replica set: n one-chip services of ONE process on one spool.

The four-chip deployment that is not a mesh.  `pipeline/fleet.py` runs N
worker processes on one spool, arbitrated by O_EXCL claim files; where
one process holds all of a host's chips (JAX's default on a four-chip
host, and the only way a caller that has already touched JAX can have
them), the same topology is a `ReplicaSet`: the key read once and copied
device to device, one `ProvingService` a device with a thread of its
own, every replica proving where its key lives (`prover/groth16_tpu.py::
key_device`).  The claim files arbitrate between the replicas exactly as
between processes, so a set and a fleet can share a spool.

What one process costs is the interpreter: witness, verify, `prep` and
`finish` are Python, and four replicas take turns at it (PERF.md).  At
the batched witness tier they take turns in earnest: one member at a time
inside `cs.witness_batch` (`ProvingService._in_witness_turn`), because
four inside it at once take nine times one, not four.

A set presents what a service presents: `run(spool, poll_s)`,
`request_drain()`, `draining`, `cs`, `public_fn`, `inputs_fn`,
`witness_fn`, `batch_size`.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Callable, List, Optional

from ..snark.r1cs import Witness, _std_u64
from ..utils.audit import install_compile_listener, record_arm, stamp_preflight
from ..utils.metrics import REGISTRY, run_id
from ..utils.trace import drain as drain_trace, record, set_context, thread_tally, trace

# A replica's loop that has not come up after this long is a fault, not a slow
# start.  The clock starts when the loops' threads do, AFTER `warm()`: it covers n
# preflights, not the set's bring-up, which a big key makes long and this does not
# bound.  Four replicas of a 2^19 circuit (venmo 256/192: a 460 MB key and a 4.29 GB
# h table a chip) on the chip: each of the three copies lands in 11-13 ms
# (`replicas/place`); `warm()` takes 26 s with the programs in the compile cache
# (replica 0's batch alone 7.9 s, then the three others' together 17.6-18.7 s,
# `table_ms` 3.8 s of it) and 86-89 s in a checkout's first start (the three others'
# 77.7-80.8 s: each device's compiles, `table_ms` 5.6-7.3 s); every loop is up within
# a second of it (PERF.md, PR 41).
LOOP_UP_TIMEOUT_S = 120.0


def replicas_arm(n: Optional[int] = None) -> str:
    """record_arm the `replicas` gate: the number of replicas that have
    a key on a device and a running loop, "off" for a solo service."""
    return record_arm("replicas", "off" if n is None else str(n))


class ReplicaSet:
    def __init__(self, make_service: Callable[[object], object], dpk, n: Optional[int] = None):
        """make_service: key -> ProvingService, constructed as a solo
        service is (`ProvingService.for_venmo`, ...): called once a
        replica with that replica's key.
        dpk: the loaded key, wherever it lives.  Replica i serves from
        local device i, its key pinned there (`place_key`): the loaded
        key itself where it already lives there (its resident table
        and class splits with it), else a device-to-device copy.
        n: replicas, one a local device; None = every local device."""
        import jax

        from ..prover.groth16_tpu import place_key

        devices = jax.local_devices()
        n = len(devices) if n is None else int(n)
        if not 1 <= n <= len(devices):
            raise ValueError(f"a replica set of {n} needs {n} local devices; this process has {len(devices)}")
        self.devices = devices[:n]
        self.replicas: List = []
        # one sink a path for the whole set: four JsonlSink instances on one
        # file would rotate against each other
        sinks, sinks_lock = {}, threading.Lock()
        self._up: set = set()
        self._up_lock = threading.Lock()
        witness_turn = threading.Lock()  # one member at a time in the batched witness tier (`_in_witness_turn`)
        for i, dev in enumerate(self.devices):
            # what the copy costs a start: `bytes` 0 for the replica that serves from the loaded key itself
            with trace("replicas/place", replica=i) as span:
                key = place_key(dpk, dev)
                copied = () if key is dpk else jax.tree_util.tree_leaves(jax.block_until_ready(key))
                span["bytes"] = sum(x.nbytes for x in copied)
            svc = make_service(key)
            svc.join_set(i, self.live, sinks, sinks_lock, self._loop_up, witness_turn)
            self.replicas.append(svc)
        first = self.replicas[0]
        self.cs, self.vk, self.batch_size = first.cs, first.vk, first.batch_size
        self.witness_fn, self.public_fn, self.inputs_fn = first.witness_fn, first.public_fn, first.inputs_fn
        self._preflight_stamp: Optional[dict] = None

    # ------------------------------------------------------------ as a service

    def request_drain(self) -> None:
        for svc in self.replicas:
            svc.request_drain()

    @property
    def draining(self) -> bool:
        return all(svc.draining for svc in self.replicas)

    def live(self) -> int:
        """Replicas whose loop is up (a peer's scheduler counts them)."""
        with self._up_lock:
            return len(self._up)

    def _loop_up(self, replica: int, stamp: dict) -> None:
        with self._up_lock:
            self._up.add(replica)
            self._preflight_stamp = stamp
            REGISTRY.gauge("zkp2p_replicas_live").set(len(self._up))

    def _loop_down(self, replica: int) -> None:
        with self._up_lock:
            self._up.discard(replica)
            REGISTRY.gauge("zkp2p_replicas_live").set(len(self._up))

    # ------------------------------------------------------------------ warm-up

    def warm(self) -> None:
        """One batch of the set's shape on every replica's device, so that
        no replica lowers or compiles while it serves.  The first replica
        alone (whatever the process has not lowered yet is lowered once,
        on one thread), then the others together: a second device takes
        the first's lowering and only compiles (PERF.md, PR 30).  Nothing
        to warm under a stand-in `prover_fn`."""
        from ..prover.groth16_tpu import prove_tpu_batch

        def in_h_table() -> float:
            """This thread's milliseconds so far in `tpu/prove_batch/h_table`, the build of a key's
            resident h table: what a warm batch spends there is its `table_ms` (0: the key came with one)."""
            return thread_tally().get("h_table", (0.0, 0.0))[0]

        def one(svc) -> None:
            # the shapes are what is warmed, not the values; carrying its rows as a
            # builder's witness does, so the warm batch takes the host path a served one takes
            witness = Witness([1] + [0] * (svc.dpk.n_wires - 1))
            witness.u64 = _std_u64(witness)
            set_context(replica=svc.replica)  # the warm batch's spans are that replica's too
            try:
                t0, table0 = time.time(), in_h_table()
                prove_tpu_batch(svc.dpk, [witness] * svc.batch_size)
                record("replicas/warm", t0, time.time(), n=svc.batch_size, table_ms=round(in_h_table() - table0, 3))
            finally:
                set_context(replica=None)

        todo = [svc for svc in self.replicas if svc.prover_fn is None]
        if not todo:
            return
        one(todo[0])
        errors: List[BaseException] = []

        def guarded(svc) -> None:
            try:
                one(svc)
            except BaseException as e:  # noqa: BLE001 — raised on the caller's thread below
                errors.append(e)

        threads = [threading.Thread(target=guarded, args=(svc,), name=f"zkp2p-warm-{svc.replica}") for svc in todo[1:]]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        if errors:
            raise errors[0]

    # ---------------------------------------------------------------------- run

    def run(self, spool: str, poll_s: float = 1.0, **kw) -> str:
        """Warm every replica, then one `ProvingService.run` a replica,
        each on a thread of its own, until all have ended; returns why
        ("drained" when every loop drained, else the first other
        reason).  `last_preflight` is stamped once every loop is up."""
        install_compile_listener()
        REGISTRY.counter("zkp2p_service_claim_lost_total")  # 0 is a reading
        self.warm()
        self._flush_spans(spool)  # the bring-up (`replicas/place`, `replicas/warm`) is in the sink before any loop claims
        whys: List[Optional[str]] = [None] * len(self.replicas)
        errors: List[BaseException] = []

        def serve(i: int, svc) -> None:
            try:
                whys[i] = svc.run(spool, poll_s=poll_s, **kw)
            except BaseException as e:  # noqa: BLE001 — raised on the caller's thread below
                errors.append(e)
                # A set with a dead replica is not the deployment: the peers finish what they
                # have claimed and stop, and the caller hears of it.  At 2^19 a replacement
                # would be a table's build (3.8 s warm) and a warm batch (7.9 s) on a chip whose
                # memory the dead loop's key and table (4.75 GB) may still hold: the restart is
                # the operator's (or the fleet supervisor's), with a process to reclaim it.
                self.request_drain()
            finally:
                self._loop_down(i)

        threads = [threading.Thread(target=serve, args=(i, svc), name=f"zkp2p-replica-{i}", daemon=True)
                   for i, svc in enumerate(self.replicas)]
        for th in threads:
            th.start()
        t_give_up = time.time() + LOOP_UP_TIMEOUT_S
        while self.live() < len(self.replicas) and not errors and time.time() < t_give_up \
                and all(th.is_alive() for th in threads):
            time.sleep(0.01)
        n_up = self.live()
        replicas_arm(n_up)
        if n_up == len(self.replicas) and self._preflight_stamp is not None:
            stamp_preflight(self._preflight_stamp)  # what a caller waits on: every loop is up
            print(f"[replicas] {n_up} replicas up on {[str(d) for d in self.devices]}", flush=True)
        elif not errors:
            errors.append(RuntimeError(f"only {n_up} of {len(self.replicas)} replica loops came up"))
            self.request_drain()
        for th in threads:
            th.join()
        self._idle_spans(spool)
        if errors:
            raise errors[0]
        return next((w for w in whys if w != "drained"), "drained") or "drained"

    def _idle_spans(self, spool: str) -> None:
        """One `replicas/idle` span a replica: of the wall time between
        the set's first claim and its last terminal, the part in which
        that replica had no batch in its prover (`ms`), and the proofs it
        served (`n`).  Flushed with whatever spans the replicas' last
        flushes left."""
        firsts = [s.t_first_claim for s in self.replicas if s.t_first_claim is not None]
        lasts = [s.t_last_terminal for s in self.replicas if s.t_last_terminal is not None]
        if firsts and lasts:
            t0, t1 = min(firsts), max(lasts)
            for svc in self.replicas:
                idle_s = max(0.0, (t1 - t0) - svc.busy_s)
                record("replicas/idle", t0, t0 + idle_s, replica=svc.replica, n=svc.n_done,
                       span_s=round(t1 - t0, 6), batches=svc.n_batches)
        self._flush_spans(spool)

    def _flush_spans(self, spool: str) -> None:
        """The spans in the process's ring, to the set's sink."""
        rid, pid = run_id(), os.getpid()
        try:
            self.replicas[0]._sink(spool).write_many(
                [{"type": "stage", "run_id": rid, "pid": pid, **r} for r in drain_trace()])
        except Exception:  # noqa: BLE001 — observation only
            pass
