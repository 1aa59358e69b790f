"""A two-constraint circuit with four private input bytes and no public
signal: the shape of sha2b (inputs marked with `mark_input`), small enough
for the CPU."""

from zkp2p_tpu.field.bn254 import R
from zkp2p_tpu.snark.r1cs import LC, ConstraintSystem


def build_toy():
    cs = ConstraintSystem("bench-toy")
    msg = cs.new_wires(4, "msg")
    cs.mark_input(msg)
    u, v = cs.new_wire("u"), cs.new_wire("v")
    cs.enforce(LC.of(msg[0]), LC.of(msg[1]), LC.of(u), "mul0")
    cs.enforce(LC.of(msg[2]), LC.of(msg[3]), LC.of(v), "mul1")
    cs.compute(u, lambda a, b: a * b % R, [msg[0], msg[1]])
    cs.compute(v, lambda a, b: a * b % R, [msg[2], msg[3]])
    return cs


def build_toy_signal():
    """The toy with one public signal, the product of its first two bytes:
    the shape of venmo, whose signals say which request a proof answers."""
    cs = ConstraintSystem("bench-toy-signal")
    prod = cs.new_public("prod")
    msg = cs.new_wires(4, "msg")
    cs.mark_input(msg)
    v = cs.new_wire("v")
    cs.enforce(LC.of(msg[0]), LC.of(msg[1]), LC.of(prod), "mul0")
    cs.enforce(LC.of(msg[2]), LC.of(msg[3]), LC.of(v), "mul1")
    cs.compute(v, lambda a, b: a * b % R, [msg[2], msg[3]])
    return cs


MESSAGE_OF = {"fn": lambda payload: payload["msg"]}  # a test breaks the path from request to witness here


def signal_adapter(config):
    """`adapter` of the fixture configuration toy-signal."""
    from zkp2p_tpu.pipeline.service import ProvingService

    cs = build_toy_signal()
    wires = sorted(cs.input_wires)

    def witness(payload):
        msg = MESSAGE_OF["fn"](payload)
        return cs.witness([msg[0] * msg[1]], dict(zip(wires, msg)))

    def make_service(dpk, vk, **kw):
        return ProvingService(cs, dpk, vk, witness_fn=witness,
                              public_fn=lambda w: list(w[1 : cs.num_public + 1]), prover_fn=None, **kw)
    return cs, make_service


def signal_of(payload):
    """`public_tie` of toy-signal: the one signal, from the request alone."""
    return {0: payload["msg"][0] * payload["msg"][1]}
