"""trace_reduce's arithmetic on a hand-made slice with known answers, its
reader on a small recorded profile, and (once cut from a chip run) on a
slice of a real v5e trace."""

import glob
import json
import os

import pytest

from benchmarks.harness import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURES = os.path.join(os.path.dirname(HERE), "fixtures")

# one device, times in ns; the anchor puts trace time 0 at wall 1000 s
SYNTH = {
    "anchor": {"start_ns": 0.0, "wall_ns": 1_000_000_000_000},
    "device": {
        "/device:TPU:0": {
            "XLA Ops": [
                ["while.1", 100.0, 400.0],          # encloses the two below: self 400 - 300 = 100
                ["fusion.a", 150.0, 100.0],
                ["mosaic.msm", 300.0, 200.0],
                ["fusion.a", 700.0, 100.0],
                ["copy.2", 1500.0, 250.0],
            ],
            "XLA Modules": [["jit_prove", 100.0, 1650.0]],   # not operations: must not count as busy
            "Steps": [["0", 0.0, 2000.0]],
        },
    },
}
HOST = [
    {"label": "service/prove", "t0_wall_s": 1000.0 + 0e-9, "ms": 900e-6, "rank": 0},    # trace 0..900
    {"label": "service/verify", "t0_wall_s": 1000.0 + 900e-9, "ms": 300e-6, "rank": 0},  # 900..1200
    {"label": "service/witness", "t0_wall_s": 1000.0 + 1000e-9, "ms": 400e-6, "rank": 1},  # 1000..1400, other thread
]


def test_interval_arithmetic():
    assert tr.merge([(5, 6), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 6)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5), (7, 10)]
    assert tr.clip([(0, 4), (5, 9)], 3, 6) == [(3, 4), (5, 6)]
    assert tr.overlap_s([(0, 4), (6, 8)], [(3, 7)]) == 2


def test_busy_union_idle_top_operations_and_gap_attribution():
    r = tr.reduce_events(SYNTH, HOST, wall_start_ns=1_000_000_000_000, wall_stop_ns=1_000_000_002_000)
    # busy: [100,500) + [700,800) + [1500,1750) = 750 ns of a 2000 ns slice
    assert r["window_s"] == pytest.approx(2000e-9) and r["busy_s"] == pytest.approx(750e-9) and r["devices"] == 1
    ops = dict(r["device_ops"])
    assert ops == {"copy.2": pytest.approx(250e-9), "fusion.a": pytest.approx(200e-9),
                   "mosaic.msm": pytest.approx(200e-9), "while.1": pytest.approx(100e-9)}
    assert sum(ops.values()) == pytest.approx(r["busy_s"])
    # idle: [0,100) [500,700) [800,1500) [1750,2000) = 1250 ns.
    # prove (0..900) takes 100+200+100; verify (900..1200) 300; witness, the other
    # thread, only what they left of 1000..1400: 1200..1400 = 200; the rest 350
    gaps = dict(r["idle_gaps"])
    assert gaps == {"service/prove": pytest.approx(400e-9), "service/verify": pytest.approx(300e-9),
                    "service/witness": pytest.approx(200e-9), "unattributed": pytest.approx(350e-9)}
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    assert r["device_ops"][0][0] == "copy.2" and r["idle_gaps"][0][0] == "service/prove"


def test_a_gap_goes_to_the_innermost_span_of_a_rank_only():
    """The stage spans nest (sweep > prove > prove_batch > finish) and sit beside the per-request
    ones: an idle instant is credited once, to the span that began last, and the sums stay."""
    nested = HOST + [
        {"label": "service/sweep", "t0_wall_s": 1000.0 + 50e-9, "ms": 1350e-6, "rank": 0},            # 50..1400, encloses most
        {"label": "tpu/prove_batch/finish", "t0_wall_s": 1000.0 + 600e-9, "ms": 300e-6, "rank": 0},    # 600..900, inside prove
        {"label": "service/witness_check", "t0_wall_s": 1000.0 + 1250e-9, "ms": 100e-6, "rank": 1},    # 1250..1350, inside witness
        {"label": "service/poll", "t0_wall_s": 1000.0 + 1800e-9, "ms": 200e-6, "rank": 0},             # 1800..2000
    ]
    r = tr.reduce_events(SYNTH, nested, wall_start_ns=1_000_000_000_000, wall_stop_ns=1_000_000_002_000)
    flat = tr.reduce_events(SYNTH, HOST, wall_start_ns=1_000_000_000_000, wall_stop_ns=1_000_000_002_000)
    assert (r["busy_s"], r["window_s"], r["device_ops"]) == (flat["busy_s"], flat["window_s"], flat["device_ops"])
    # idle: [0,100) [500,700) [800,1500) [1750,2000).  prove began at 0, sweep at 50: prove is outside it and keeps
    # [0,50); sweep, begun later, takes [50,100) [500,600); finish [600,700) [800,900); verify 300; what sweep still
    # covers of 1200..1400 is its own (the witness thread comes second); poll [1800,2000); the rest 150
    gaps = dict(r["idle_gaps"])
    assert gaps == {"service/prove": pytest.approx(50e-9), "service/sweep": pytest.approx(350e-9),
                    "tpu/prove_batch/finish": pytest.approx(200e-9), "service/verify": pytest.approx(300e-9),
                    "service/poll": pytest.approx(200e-9), "unattributed": pytest.approx(150e-9)}
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    # the witness thread's spans, once rank 0 is out of the way: the check inside the witness, once
    r = tr.reduce_events(SYNTH, [sp for sp in nested if sp["rank"] == 1], 1_000_000_000_000, 1_000_000_002_000)
    assert dict(r["idle_gaps"]) == {"service/witness": pytest.approx(300e-9), "service/witness_check": pytest.approx(100e-9),
                                    "unattributed": pytest.approx(850e-9)}


def test_a_replicas_plane_is_reduced_against_that_replicas_spans():
    two = json.loads(json.dumps(SYNTH))
    two["device"]["/device:TPU:1"] = {"XLA Ops": [["mosaic.msm", 0.0, 1000.0]]}   # idle 1000..2000
    spans = [{"label": "service/verify", "t0_wall_s": 1000.0 + 1000e-9, "ms": 500e-6, "rank": 0, "replica": 0},   # 1000..1500
             {"label": "service/starved", "t0_wall_s": 1000.0 + 1000e-9, "ms": 1000e-6, "rank": 0, "replica": 1}]  # 1000..2000
    r = tr.reduce_events(two, spans, 1_000_000_000_000, 1_000_000_002_000)
    # plane 0 idles 500 ns of 1000..1500 under ITS verify; plane 1 all of 1000..2000 under ITS wait; summed over
    # the planes and averaged like busy_s, never one replica's span against another's device
    assert dict(r["idle_gaps"]) == {"service/verify": pytest.approx(500e-9 / 2), "service/starved": pytest.approx(1000e-9 / 2),
                                    "unattributed": pytest.approx(750e-9 / 2)}
    solo = [dict(sp, replica=None) for sp in spans]   # a solo service (and a mesh): every plane against the one set of spans
    r = tr.reduce_events(two, solo, 1_000_000_000_000, 1_000_000_002_000)
    assert dict(r["idle_gaps"]) == {"service/verify": pytest.approx((500e-9 + 500e-9) / 2), "service/starved": pytest.approx((250e-9 + 500e-9) / 2),
                                    "unattributed": pytest.approx(500e-9 / 2)}


def test_busy_is_averaged_over_the_chips_used_and_nothing_is_reported_without_device_operations():
    two = json.loads(json.dumps(SYNTH))
    two["device"]["/device:TPU:1"] = {"XLA Ops": [["mosaic.msm", 0.0, 2000.0]]}
    r = tr.reduce_events(two, [], 1_000_000_000_000, 1_000_000_002_000)
    assert r["devices"] == 2 and r["busy_s"] == pytest.approx((750e-9 + 2000e-9) / 2)
    assert dict(r["idle_gaps"]) == {"unattributed": pytest.approx(1250e-9 / 2)}
    assert tr.reduce_events({"anchor": None, "device": {}}, []) is None
    assert tr.reduce_events({"anchor": None, "device": {"/device:TPU:0": {"Steps": [["0", 0.0, 5.0]]}}}, []) is None


def test_without_an_anchor_the_slice_is_the_span_of_the_operations():
    r = tr.reduce_events(dict(SYNTH, anchor=None), HOST)
    assert r["window_s"] == pytest.approx(1650e-9) and dict(r["idle_gaps"]) == {"unattributed": pytest.approx(900e-9)}


def test_the_reader_finds_planes_lines_and_the_anchor_in_a_recorded_profile():
    """A profile recorded on this sandbox's CPU backend with the harness's own
    options: it has host planes only, so it checks the reader and the anchor,
    not device arithmetic."""
    ev = tr.load_events(os.path.join(FIXTURES, "cpu_small.xplane.pb"))
    assert ev["device"] == {} and ev["anchor"]["wall_ns"] > 1_700_000_000 * 10**9 and ev["anchor"]["start_ns"] >= 0


@pytest.mark.parametrize("path", sorted(glob.glob(os.path.join(FIXTURES, "v5e_*.json"))))
def test_a_slice_of_a_real_v5e_trace(path):
    with open(path) as f:
        fx = json.load(f)
    r = tr.reduce_events(fx["events"], fx["host_spans"], fx["wall_start_ns"], fx["wall_stop_ns"])
    exp = fx["expected"]
    assert r["busy_s"] == pytest.approx(exp["busy_s"]) and r["window_s"] == pytest.approx(exp["window_s"])
    assert 0 < r["busy_s"] < r["window_s"]
    assert [n for n, _ in r["device_ops"]] == [n for n, _ in exp["device_ops"]]
    assert sum(s for _, s in r["idle_gaps"]) == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)
    assert r["idle_gaps"][0][0] == exp["idle_gaps"][0][0]
    if os.path.basename(path) == "v5e_sha2b_boundary.json":
        # looked at by hand when it was cut: a 0.869 s gap between two sha2b batches with 4 ms
        # of operations on either side; the host spends it in the service's verify and in the
        # host side of prove_tpu_batch
        assert r["window_s"] == pytest.approx(0.8771, abs=1e-4) and r["busy_s"] == pytest.approx(0.00792, abs=1e-5)
        assert [n for n, _ in r["idle_gaps"][:2]] == ["service/verify", "service/prove"]
        assert dict(r["idle_gaps"])["service/verify"] == pytest.approx(0.4684, abs=1e-3)


def test_host_spans_takes_the_stage_spans_that_are_intervals_of_a_host_thread():
    from benchmarks.run import host_spans

    records = [{"replica": 2, "spans": [{"name": "prove", "t0": 10.0, "ms": 5.0}, {"name": "witness_batch", "t0": 9.0, "ms": 1.0}]},
               {"replica": 2, "spans": [{"name": "prove", "t0": 10.0, "ms": 5.0}]}]      # one span a batch, on each of its requests
    stages = [{"stage": "service/prove", "t0": 10.0, "ms": 5.0, "replica": 2},              # the same span, from the sink
              {"stage": "service/prove/tpu/prove_batch/finish", "t0": 14.0, "ms": 0.9, "replica": 2},
              {"stage": "service/witness/service/witness_check", "t0": 9.5, "ms": 0.2, "replica": 2},
              {"stage": "service/poll", "t0": 15.0, "ms": 200.0, "tid": None},
              {"stage": "service/starved", "t0": 9.0, "ms": 1.0},
              {"stage": "service/prove/tpu/prove_batch/device_idle/poll", "t0": 15.0, "ms": 150.0},   # an account of a gap, laid end to end
              {"stage": "service/prove/tpu/prove_batch/stage/msm_h", "t0": 11.0, "ms": 2.0},          # the device's clock
              {"stage": "service/prove/tpu/prove_batch/upload", "t0": 10.1, "ms": 0.1},
              {"stage": "replicas/idle", "t0": 0.0, "ms": 9000.0, "replica": 2}]                      # a sum over the window
    got = {(sp["label"], sp["rank"], sp["replica"]) for sp in host_spans(records, stages)}
    assert got == {("service/prove", 0, 2), ("service/witness_batch", 1, 2), ("tpu/prove_batch/finish", 0, 2),
                   ("service/witness_check", 1, 2), ("service/poll", 0, None), ("service/starved", 0, None)}
    assert len(host_spans(records, stages)) == 6
