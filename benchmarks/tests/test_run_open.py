"""Open loops through the whole command at the toy circuit: a deadline the
service honestly misses is counted in `failed` and leaves the run correct;
a refusal before the deadline, and a backlog, read as what they are."""

import json
import time

import pytest

from benchmarks import run as bench_run
from benchmarks.tests.conftest import StubChip


def _run(capsys, root, argv):
    rc = bench_run.main(argv, chip=StubChip(), root=root)
    cap = capsys.readouterr()
    out = cap.out.strip().splitlines()
    return rc, json.loads(out[-1]), out, cap.err.strip().splitlines()


@pytest.fixture
def slow_prover(host_backed_device_prover):
    """A batch takes 0.3 s more than the C++ prover needs, on any machine: the
    toy's capacity is under 13 proofs a second whatever runs the tests."""
    def slow(proofs, pinned):
        if not pinned:
            time.sleep(0.3)
        return proofs

    host_backed_device_prover["tamper"] = slow
    return host_backed_device_prover


def test_a_deadline_the_service_honestly_misses_is_failed_and_not_incorrect(capsys, toy_root, slow_prover):
    """16 at once and a second to answer them in: the first batch makes it, the
    second comes out late (a late proof is a proof), the rest are refused."""
    rc, res, out, err = _run(capsys, toy_root, ["--workload", "toy.open-deadline", "--seed", str(2**31 + 40), "--seconds", "3", "--trace", "0"])
    assert rc == 0 and res["correct"] is True
    refused = res["checks"]["requests_refused_at_their_deadline"]
    assert refused["limit"] is None and 0 < refused["value"] == res["failed"] < res["attempted"]
    assert res["checks"]["requests_refused_before_their_deadline"] == {"value": 0, "limit": 0}
    assert res["checks"]["requests_not_done_or_pairing_invalid_or_passed_over"] == {"value": 0, "limit": 0}
    assert list(res)[-1] == "checks" and all(c["value"] == 0 for n, c in res["checks"].items() if c["limit"] == 0)
    # the rate and the latencies are the valid proofs' alone
    assert set(res["metrics"]) == {"proofs_per_s", "latency_p50_s", "latency_p90_s", "setup_s"}
    assert any(f"requests_refused_at_their_deadline = {refused['value']} (no limit" in line for line in out)
    # each number compared, beside its limit, as the last lines of standard error
    assert err[-len(res["checks"]):] == [f"check: {n} = {c['value']} (limit {c['limit']})" for n, c in res["checks"].items()]


def test_the_program_counts_the_refusals_the_spool_shows(capsys, toy_root, slow_prover):
    rc, res, out, _ = _run(capsys, toy_root, ["--workload", "toy.open-deadline", "--seed", "41", "--seconds", "3", "--trace", "1"])
    assert rc == 0 and res["correct"] is True and res["failed"] > 0
    assert res["metrics"]["deadline_refusals_in_window"] == {"value": float(res["failed"]), "unit": "count"}
    assert res["metrics"]["generator_late_p95_ms"]["value"] >= 0 and "backlog_latency_p90_s" not in res["metrics"]


def test_a_refusal_before_the_deadline_reads_not_correct(capsys, toy_root, host_backed_device_prover, monkeypatch):
    """A service that takes one request's deadline for passed when it has 30 s left."""
    from zkp2p_tpu.pipeline.service import ProvingService

    real = ProvingService._deadline_of

    def hasty(self, req):
        return req.t_submit if req.rid.endswith("-000001") else real(self, req)

    monkeypatch.setattr(ProvingService, "_deadline_of", hasty)
    rc, res, out, _ = _run(capsys, toy_root, ["--workload", "toy.open-poisson", "--seed", "42", "--seconds", "2", "--trace", "0"])
    assert rc == 0 and res["correct"] is False and res["failed"] == 1
    assert res["checks"]["requests_refused_before_their_deadline"] == {"value": 1, "limit": 0}
    assert res["checks"]["requests_refused_at_their_deadline"]["value"] == 0


def test_a_deadline_refusal_where_no_deadline_was_asked_for_reads_not_correct(capsys, toy_root, host_backed_device_prover, monkeypatch):
    """Without `deadline_s` in the traffic file nothing new is compared: any error state fails the old number."""
    from zkp2p_tpu.pipeline.service import ProvingService

    monkeypatch.setattr(ProvingService, "_deadline_of", lambda self, req: req.t_submit if req.rid.endswith("-000001") else None)
    rc, res, out, _ = _run(capsys, toy_root, ["--workload", "toy.open-burst", "--seed", "43", "--seconds", "1", "--trace", "0"])
    assert rc == 0 and res["correct"] is False and res["failed"] == 1
    assert res["checks"]["requests_not_done_or_pairing_invalid_or_passed_over"]["value"] == 1
    assert "requests_refused_at_their_deadline" not in res["checks"]


def test_a_backlog_is_left_unclaimed_not_passed_over(capsys, toy_root, slow_prover):
    rc, res, out, _ = _run(capsys, toy_root, ["--workload", "toy.open-burst", "--seed", str(2**31 + 44), "--seconds", "2", "--trace", "1"])
    assert rc == 0 and res["correct"] is True and res["failed"] == 0 and 0 < res["attempted"] < 32
    assert res["metrics"]["unclaimed_at_close"]["value"] == 32 - res["attempted"] > 0
    assert res["metrics"]["backlog_latency_p90_s"]["value"] > 0 and "deadline_refusals_in_window" not in res["metrics"]
    assert any("0 failed" in line and f"{32 - res['attempted']} it had not claimed" in line for line in out)
    assert res["metrics"]["batch_fill"]["value"] == 100.0  # sixteen at a time: every batch is full


def test_an_answer_altered_where_it_is_produced_reads_not_correct_under_an_open_loop(capsys, toy_root, host_backed_device_prover):
    """The control of the open cells: the second proof of every served batch altered (the service's
    sample verify checks the first; a short batch's last may be padding, which is dropped)."""
    import dataclasses

    def tamper(proofs, pinned):
        if not pinned and len(proofs) > 1:
            proofs[1] = dataclasses.replace(proofs[1], c=(proofs[1].c[0] ^ 1, proofs[1].c[1]))
        return proofs

    host_backed_device_prover["tamper"] = tamper
    rc, res, out, _ = _run(capsys, toy_root, ["--workload", "toy.open-burst", "--seed", "45", "--seconds", "1", "--trace", "0"])
    assert rc == 0 and res["correct"] is False and res["failed"] > 0
    assert res["checks"]["requests_not_done_or_pairing_invalid_or_passed_over"]["value"] == res["failed"]
