"""The knee sweep's control flow at the toy circuit: one set-up, a window a rate."""

import json

from benchmarks.harness import device
from benchmarks.tests import knee_sweep
from benchmarks.tests.conftest import StubChip


def test_a_sweep_of_two_rates_prints_a_row_a_rate(capsys, toy_root, host_backed_device_prover, monkeypatch):
    monkeypatch.setattr(device, "Chip", StubChip)
    monkeypatch.chdir(toy_root)
    assert knee_sweep.main(["--workload", "toy.closed8", "--seconds", "1.5", "--rates", "4,12@burst4", "--deadline-s", "30", "--seed", str(2**31 + 5)]) == 0
    rows = [json.loads(line)["knee_sweep"] for line in capsys.readouterr().out.splitlines() if line.startswith('{"knee_sweep"')]
    assert [(r["rate_per_s"], r["arrival"]) for r in rows] == [(4.0, "poisson"), (12.0, "burst4")] and [r["submitted"] >= n - 1 for r, n in zip(rows, (6, 18))] == [True, True]
    assert all(r["correct"] and r["failed"] == 0 and r["passed_over"] == 0 and r["refused_at_deadline"] == 0 for r in rows)
    assert all(r["first_half"]["n"] + r["second_half"]["n"] == r["attempted"] for r in rows)
