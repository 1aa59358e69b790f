"""proofs_per_s, percentile, lateness and arrival arithmetic on fixed timestamps."""

import pytest

from benchmarks.harness import loadgen, score
from benchmarks.harness.cell import BenchmarkFileError, load_traffic
from benchmarks.harness.peaks import peaks_for


def test_percentile_is_an_observed_sample():
    lat = [5.0, 1.0, 3.0, 2.0, 4.0]
    assert score.percentile(lat, 0.5) == 3.0 and score.percentile(lat, 0.9) == 5.0 and score.percentile(lat, 0.0) == 1.0
    assert score.percentile(list(range(1, 101)), 0.9) == 90
    with pytest.raises(ValueError):
        score.percentile([], 0.5)


def test_proofs_per_s_runs_to_the_last_artifact_not_the_window():
    # one wave of four at 32 s and a second at 63.9 s: the rate does not step
    assert score.proofs_per_s(100.0, [132.0] * 4) == pytest.approx(4 / 32)
    assert score.proofs_per_s(100.0, [132.0] * 4 + [163.9] * 4) == pytest.approx(8 / 63.9)
    assert score.proofs_per_s(100.0, []) is None


def test_score_window_counts_everything_the_service_took_on_through_the_drain():
    reqs = [
        {"t_ref": 0.0, "t_terminal": 2.0, "state": "done", "valid": True},
        {"t_ref": 0.0, "t_terminal": 4.0, "state": "done", "valid": True},
        {"t_ref": 1.0, "t_terminal": 5.0, "state": "done", "valid": False},      # pairing failed, or another request's signals
        {"t_ref": 1.0, "t_terminal": 6.0, "state": "error-shed"},                 # refused
        {"t_ref": 2.0},                                                            # passed over: a later one was served
        {"t_ref": 2.0, "t_terminal": 11.0, "state": "done", "valid": True},      # claimed in the window, finished in the drain
        {"t_ref": 3.0},                                                            # not claimed when the window closed
        {"t_ref": 3.5},
    ]
    sc = score.score_window(reqs, t_first=0.0)
    assert (sc["submitted"], sc["attempted"], sc["failed"], sc["passed_over"], sc["unclaimed_at_end"]) == (8, 6, 3, 1, 2)
    assert sc["proofs_per_s"] == pytest.approx(3 / 11.0) and sc["latency_samples"] == 3
    assert sc["latency_p50_s"] == 4.0 and sc["latency_p90_s"] == 9.0


def test_a_refusal_at_the_deadline_is_a_miss_one_before_it_is_not_and_a_late_proof_is_a_proof():
    def req(t_sent, **kw):
        return dict({"t_ref": t_sent, "t_sent": t_sent + 0.01, "payload": {"msg": [1], "deadline_s": 10.0}}, **kw)

    reqs = [
        req(0.0, t_terminal=3.0, state="done", valid=True),
        req(1.0, t_terminal=12.5, state="done", valid=True),                  # done after its deadline: late, valid, in the tail
        req(2.0, t_terminal=12.6, state="error-deadline-exceeded"),           # refused 10.59 s after it was sent: a miss
        req(3.0, t_terminal=12.97, state="error-deadline-exceeded"),          # 9.96 s: inside the two clocks' 50 ms
        req(4.0, t_terminal=12.9, state="error-deadline-exceeded"),           # 8.89 s: the deadline had not passed
        req(5.0, t_terminal=13.0, state="error-shed"),                        # any other error state: as before
        req(6.0),                                                             # not claimed when the window closed
    ]
    sc = score.score_window(reqs, t_first=0.0)
    assert (sc["submitted"], sc["attempted"], sc["failed"], sc["unclaimed_at_end"]) == (7, 6, 4, 1)
    assert (sc["refused_at_deadline"], sc["refused_before_deadline"]) == (2, 1)
    assert sc["latency_samples"] == 2 and sc["latency_p90_s"] == 11.5 and sc["proofs_per_s"] == pytest.approx(2 / 12.5)
    # a refusal of a request that carried no deadline is no miss: it fails, and is counted in neither
    bare = [{"t_ref": 0.0, "t_sent": 0.0, "payload": {"msg": [1]}, "t_terminal": 1.0, "state": "error-deadline-exceeded"}]
    sc = score.score_window(bare, 0.0)
    assert (sc["failed"], sc["refused_at_deadline"], sc["refused_before_deadline"]) == (1, 0, 0)


def test_a_stall_behind_the_last_completion_lowers_the_rate():
    """Two batches of four; the second, claimed before the window closed, ends late."""
    first = [{"t_ref": 0.0, "t_terminal": 30.0, "state": "done", "valid": True}] * 4
    on_time = first + [{"t_ref": 0.0, "t_terminal": 60.0, "state": "done", "valid": True}] * 4
    stalled = first + [{"t_ref": 0.0, "t_terminal": 75.0, "state": "done", "valid": True}] * 4
    assert score.score_window(on_time, 0.0)["proofs_per_s"] == pytest.approx(8 / 60)
    assert score.score_window(stalled, 0.0)["proofs_per_s"] == pytest.approx(8 / 75)
    empty = score.score_window([{"t_ref": 0.0}], 0.0)
    assert empty["attempted"] == 0 and empty["proofs_per_s"] is None and "latency_p50_s" not in empty


def test_lateness_is_sent_minus_due():
    late = score.lateness([0.0, 1.0, 2.0, 3.0], [0.001, 1.0, 2.5, 2.9])
    assert late["n"] == 4 and late["max_s"] == pytest.approx(0.5) and late["mean_s"] == pytest.approx(0.501 / 4)


@pytest.mark.parametrize("arrival", ["poisson", "uniform", "burst"])
def test_every_seed_offers_the_same_arrivals_in_another_order(arrival):
    t = {"rate_per_s": 3.0, "arrival": arrival, "burst_size": 4}
    a, b = loadgen.arrival_offsets(t, 1, 20.0), loadgen.arrival_offsets(t, 2**31 + 9, 20.0)
    assert len(a) == len(b) == 60 and a[0] == 0.0 and max(a) < 20.0 and a == sorted(a)
    gaps = lambda xs: sorted(round(y - x, 9) for x, y in zip(xs, xs[1:] + [20.0]))  # noqa: E731 — the last gap runs to the window's end
    assert gaps(a) == gaps(b)
    if arrival == "poisson":
        assert a != b
    if arrival == "burst":
        assert a[:5] == [0.0, 0.0, 0.0, 0.0, pytest.approx(4 / 3)]


def test_traffic_vocabulary_is_closed(tmp_path):
    p = tmp_path / "t.json"
    p.write_text('{"loop": "closed", "clients": 2, "ramp": [1, 2]}')
    with pytest.raises(BenchmarkFileError, match="vocabulary"):
        load_traffic(str(p))
    p.write_text('{"loop": "open"}')
    with pytest.raises(BenchmarkFileError, match="rate_per_s"):
        load_traffic(str(p))
    p.write_text('{"loop": "closed", "circuits": [{"config": "a", "weight": 1}, {"config": "b", "weight": 2}]}')
    with pytest.raises(BenchmarkFileError, match="one circuit"):
        load_traffic(str(p))


def test_an_unknown_device_has_no_peaks():
    assert peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    for kind in ("TPU v9", "cpu", "_source"):
        with pytest.raises(KeyError):
            peaks_for(kind)
