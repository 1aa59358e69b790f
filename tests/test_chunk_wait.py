"""The chunk loop of `prove_tpu_batch` where a batch is more than the
device holds (the benchmark's sha256-4k: 2^21, two at a time): a batch of
several chunks below `BATCH_CHUNK_MAX` waits each chunk out before it feeds
the next, and that wait is a span, `chunk_wait`, one a chunk after the
first; `zkp2p_prove_chunks_total` counts the chunks.  On the toy circuit of
`test_msm_resident` (the real h stage, table and h MSM; the witness MSMs
answered by the host curve), proof for proof against the same batch
unchunked and against the C++ prover."""

import pytest

from test_msm_resident import _toy_world

from zkp2p_tpu.prover import groth16_tpu as G
from zkp2p_tpu.utils import trace as tr
from zkp2p_tpu.utils.metrics import REGISTRY

RS, SS = [31, 32, 33, 34, 35, 36, 37, 38], [41, 42, 43, 44, 45, 46, 47, 48]


def _by_end(end):
    return sorted((r for r in tr.records() if r["stage"].endswith("/" + end)), key=lambda r: r["t0"])


def _chunks_counted():
    return REGISTRY.counter("zkp2p_prove_chunks_total").value


@pytest.mark.parametrize("knob,n,n_chunks,waits", [("2", 4, 2, 1), ("2", 3, 2, 1), ("2", 2, 1, 0), ("4", 8, 2, 0)],
                         ids=["four_as_two_chunks_of_two", "three_pads_its_second_chunk", "one_chunk", "chunks_of_four_queue"])
def test_a_chunked_batch_gives_the_unchunked_proofs_and_a_span_a_wait(monkeypatch, knob, n, n_chunks, waits):
    from zkp2p_tpu.native.lib import get_lib
    from zkp2p_tpu.prover.native_prove import prove_native

    if get_lib() is None:
        pytest.skip("native library unavailable")
    _cs, _pk, dpk, four = _toy_world(monkeypatch)
    wits, rs, ss = (four * 2)[:n], RS[:n], SS[:n]
    # `_toy_world` sets the knob to "0": the batch as one chunk (the one case: each batch shape compiles for a minute)
    whole = G.prove_tpu_batch(dpk, wits, rs=rs, ss=ss) if (knob, n) == ("2", 4) else None
    monkeypatch.setattr(G, "BATCH_CHUNK", knob)
    tr.reset()
    counted = _chunks_counted()
    proofs = G.prove_tpu_batch(dpk, wits, rs=rs, ss=ss)
    assert len(proofs) == n and whole in (None, proofs)
    assert proofs == [prove_native(dpk, w, r, s) for w, r, s in zip(wits, rs, ss)]
    (batch,) = [r for r in tr.records() if r["stage"] == "tpu/prove_batch"]
    assert (batch["n"], batch["chunk"], batch["n_chunks"]) == (n, int(knob), n_chunks)
    assert _chunks_counted() - counted == n_chunks  # a chunk padded by repeats is a chunk proved

    spans = _by_end("chunk_wait")
    assert [r["chunk"] for r in spans] == list(range(1, n_chunks))[:waits]
    for wait in spans:
        (device,) = _by_end("prove_batch/device")
        (dispatch,) = _by_end("prove_batch/dispatch")
        assert wait["stage"] == "tpu/prove_batch/dispatch/chunk_wait" and wait["parent"] == dispatch["id"]
        before = [r for r in tr.records() if "/stage/" in r["stage"] and r["chunk"] == wait["chunk"] - 1]
        after = [r for r in tr.records() if "/stage/" in r["stage"] and r["chunk"] == wait["chunk"]]
        assert len(before) == len(after) == len(G.STAGES)
        # from the instant the last stage of the chunk before was ready ...
        assert wait["t0"] == pytest.approx(max(r["t0"] + r["ms"] / 1e3 for r in before), abs=2e-6)
        # ... to this chunk's first stage being enqueued: its upload was put inside the wait, and the
        # wait is inside `device`, which the stages still partition (the next `h_planes` starts where it starts)
        (upload,) = [r for r in _by_end("upload") if r["chunk"] == wait["chunk"]]
        t_end = wait["t0"] + wait["ms"] / 1e3
        assert wait["t0"] <= upload["t0"] + upload["ms"] / 1e3 and wait["ms"] > 0 and wait["cpu_ms"] >= 0
        assert min(r["t0"] for r in after) == pytest.approx(wait["t0"], abs=2e-6)
        assert device["t0"] <= wait["t0"] and t_end <= device["t0"] + device["ms"] / 1e3 + 1e-3
    tr.reset()
