"""trace.py threading semantics + the bounded ring + atomic dumps.

The service overlaps a witness producer thread with the proving thread
and fans MSMs onto a worker pool; these tests pin the per-thread
nesting isolation, the stack/context handoff (current_stack/adopt_stack,
current_context/adopt_context) that keeps worker records attributable,
and the ring-buffer bound that closes the run()-loop leak."""

import json
import threading
from concurrent.futures import ThreadPoolExecutor

import pytest

from zkp2p_tpu.utils import trace as tr


def setup_function(_fn):
    tr.reset()
    tr.clear_context()


def test_per_thread_nesting_isolation():
    """Two threads nesting concurrently must never see each other's
    frames in their stage paths."""
    barrier = threading.Barrier(2)
    paths = {"a": [], "b": []}

    def worker(name):
        for _ in range(50):
            with tr.trace(f"{name}_outer"):
                barrier.wait()
                with tr.trace(f"{name}_inner"):
                    pass

    ta = threading.Thread(target=worker, args=("a",))
    tb = threading.Thread(target=worker, args=("b",))
    ta.start(), tb.start()
    ta.join(), tb.join()
    for rec in tr.records():
        stage = rec["stage"]
        assert not ("a_" in stage and "b_" in stage), f"cross-thread frame leak: {stage}"
        if "inner" in stage:
            name = stage[0]
            assert stage == f"{name}_outer/{name}_inner"


def test_stack_and_context_adoption_across_worker_pool():
    """The prover's overlap schedule hands current_stack()/
    current_context() to pool workers so their MSM records keep the
    submitting stage prefix AND the ambient request_id."""
    tr.set_context(request_id="req-42")
    with tr.trace("prove"):
        stack, ctx = tr.current_stack(), tr.current_context()

        def seeded(tag):
            tr.adopt_stack(stack)
            tr.adopt_context(ctx)
            with tr.trace(f"msm_{tag}"):
                pass
            return tr.records()[-1]

        with ThreadPoolExecutor(max_workers=4) as ex:
            recs = list(ex.map(seeded, ["a", "b1", "b2", "c"]))
    for rec in recs:
        assert rec["stage"].startswith("prove/msm_")
        assert rec["request_id"] == "req-42"
    # the submitting thread's own record also carries the context...
    assert tr.records()[-1]["stage"] == "prove"
    assert tr.records()[-1]["request_id"] == "req-42"
    tr.clear_context()
    # ...and a cleared context stops tagging
    with tr.trace("after"):
        pass
    assert "request_id" not in tr.records()[-1]


def test_explicit_attrs_win_over_context():
    tr.set_context(request_id="ambient")
    with tr.trace("s", request_id="explicit"):
        pass
    assert tr.records()[-1]["request_id"] == "explicit"
    tr.clear_context()


def test_ring_buffer_bound_and_drop_count():
    tr._resize_ring(16)
    try:
        for i in range(50):
            with tr.trace("x", i=i):
                pass
        assert len(tr.records()) == 16
        assert tr.dropped() == 34
        # newest records survive, oldest dropped
        assert tr.records()[-1]["i"] == 49
        assert tr.records()[0]["i"] == 34
    finally:
        tr._resize_ring(65536)
        tr.reset()


def test_drain_empties_ring():
    with tr.trace("a"):
        pass
    with tr.trace("b"):
        pass
    got = tr.drain()
    assert [r["stage"] for r in got] == ["a", "b"]
    assert tr.records() == []


def test_dump_stamps_run_id_pid_and_manifest(tmp_path):
    p = str(tmp_path / "t.jsonl")
    with tr.trace("stage_one"):
        pass
    tr.dump_trace(p)
    tr.dump_trace(p)  # appends, never truncates
    lines = [json.loads(ln) for ln in open(p)]
    manifests = [ln for ln in lines if ln.get("type") == "manifest"]
    stages = [ln for ln in lines if "stage" in ln]
    assert len(manifests) == 2  # one per dump
    for m in manifests:
        assert m["run_id"] and m["pid"] and "knobs" in m and "host" in m
        assert "trace_dropped" in m
    assert stages and all(ln["run_id"] == manifests[0]["run_id"] for ln in stages)
    assert all(ln["pid"] == manifests[0]["pid"] for ln in stages)


def test_concurrent_dumps_produce_only_intact_lines(tmp_path):
    """dump_trace is ONE O_APPEND write: concurrent dumpers (service
    workers sharing a sink) must interleave whole dumps, never bytes."""
    p = str(tmp_path / "c.jsonl")
    for i in range(64):
        with tr.trace("warm", i=i):
            pass

    def dumper():
        for _ in range(5):
            tr.dump_trace(p)

    threads = [threading.Thread(target=dumper) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for ln in open(p):
        json.loads(ln)  # raises on a torn line


# ------------------------------------------------- the span model (PR 24)


def test_a_span_records_start_id_and_parent_across_adopt_stack():
    """t0 on the wall clock, an id unique in the process, and the id of
    the span that caused it — kept when a worker thread adopts the stack."""
    import time

    t_before = time.time()
    with tr.trace("outer", n=3) as outer:
        with tr.trace("inner"):
            pass
        stack = tr.current_stack()

        def worker():
            tr.adopt_stack(stack)
            with tr.trace("pooled"):
                pass

        th = threading.Thread(target=worker)
        th.start()
        th.join(timeout=10)
        assert not th.is_alive()
        outer["added"] = "by the body"
    by = {r["stage"]: r for r in tr.records()}
    assert set(by) == {"outer", "outer/inner", "outer/pooled"}
    out = by["outer"]
    assert out["parent"] is None and out["n"] == 3 and out["added"] == "by the body"
    assert t_before <= out["t0"] <= time.time() and out["ms"] >= 0
    assert by["outer/inner"]["parent"] == out["id"] == by["outer/pooled"]["parent"]
    assert len({r["id"] for r in by.values()}) == 3
    assert by["outer/pooled"]["tid"] != out["tid"] == by["outer/inner"]["tid"]
    assert out["t0"] <= by["outer/inner"]["t0"] <= by["outer/pooled"]["t0"]


def test_record_writes_an_interval_read_from_clocks_under_the_open_span():
    tr.set_context(request_id="req-7")
    with tr.trace("batch") as batch:
        tr.record("stage/one", 100.0, 100.25, chunk=0)
    tr.record("root_interval", 5.0, 5.5, request_id="explicit")
    tr.clear_context()
    by = {r["stage"]: r for r in tr.records()}
    one = by["batch/stage/one"]
    assert (one["t0"], one["ms"], one["chunk"]) == (100.0, 250.0, 0)
    assert one["parent"] == batch["id"] and one["request_id"] == "req-7" and one["id"] != batch["id"]
    assert by["root_interval"]["parent"] is None and by["root_interval"]["request_id"] == "explicit"
    # record() opens nothing: the next span is no child of it
    with tr.trace("after"):
        pass
    assert tr.records()[-1]["parent"] is None


def test_a_leaf_span_is_a_parent_but_no_path_prefix_and_t0_backdates():
    import time

    t_scan = time.time() - 0.2
    with tr.trace("svc/sweep", leaf=True, t0=t_scan) as sweep:
        with tr.trace("svc/prove"):
            with tr.trace("phase", leaf=True) as phase:
                with tr.trace("dispatch"):
                    pass
                tr.record("stage/x", t_scan, t_scan + 0.1)
    by = {r["stage"]: r for r in tr.records()}
    assert set(by) == {"svc/sweep", "svc/prove", "svc/prove/phase", "svc/prove/dispatch", "svc/prove/stage/x"}
    assert by["svc/prove"]["parent"] == sweep["id"]
    assert by["svc/prove/dispatch"]["parent"] == phase["id"] == by["svc/prove/stage/x"]["parent"]
    assert sweep["t0"] == round(t_scan, 6) and sweep["ms"] >= 200.0


def test_an_open_span_is_a_trace_annotation_only_where_jax_is_imported(monkeypatch):
    """utils/trace.py never imports jax; where the process has, an open
    span is a TraceAnnotation of the same path, entered and left once."""
    import sys
    import types

    seen = []

    class Ann:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            seen.append(("enter", self.name))

        def __exit__(self, *exc):
            seen.append(("exit", self.name))

    monkeypatch.setitem(sys.modules, "jax", types.SimpleNamespace(profiler=types.SimpleNamespace(TraceAnnotation=Ann)))
    with tr.trace("a"):
        with tr.trace("b"):
            pass
        tr.record("past", 1.0, 2.0)  # in the past: no annotation
    assert seen == [("enter", "a"), ("enter", "a/b"), ("exit", "a/b"), ("exit", "a")]
    monkeypatch.delitem(sys.modules, "jax")
    with tr.trace("c"):
        pass
    assert len(seen) == 4 and not hasattr(tr, "jax_profile")
    src = open(tr.__file__).read()
    assert "import jax" not in src and "JAX_TRACE_DIR" not in src


def _burn(cpu_s):
    """Keep this thread on a CPU until it has spent `cpu_s` there."""
    import time

    end = time.thread_time() + cpu_s
    while time.thread_time() < end:
        pass


def test_a_closed_span_carries_the_cpu_time_its_thread_spent_in_it():
    import time

    with tr.trace("busy") as busy:
        _burn(0.03)
    with tr.trace("asleep") as asleep:
        time.sleep(0.05)
    tr.record("past", 1.0, 2.0)
    tr.record("past_with_a_reading", 1.0, 2.0, cpu_ms=12.5)
    assert 30.0 <= busy["cpu_ms"] <= busy["ms"] + 1.0  # on a CPU all the while: cpu_ms reads about ms
    assert asleep["ms"] >= 50.0 and asleep["cpu_ms"] < 10.0  # waiting: next to none
    by = {r["stage"]: r for r in tr.records()}
    assert "cpu_ms" not in by["past"] and by["past_with_a_reading"]["cpu_ms"] == 12.5


def test_the_thread_tally_holds_each_span_s_self_time_by_its_last_path_element():
    import time

    mark = tr.thread_tally()
    with tr.trace("svc/outer"):
        time.sleep(0.02)
        with tr.trace("inner"):  # svc/outer/inner
            _burn(0.03)
        inside = tr.thread_tally()  # `outer` is open: its part so far counts, less what `inner` covered
        with tr.trace("svc/inner"):  # another path, the same last element: one entry
            time.sleep(0.01)
    after = tr.thread_tally()

    def since(reading, name, i=0):
        return reading.get(name, (0.0, 0.0))[i] - mark.get(name, (0.0, 0.0))[i]

    assert 20.0 <= since(inside, "outer") < 20.0 + 15.0 and since(inside, "outer", 1) < 10.0
    assert 30.0 <= since(inside, "inner") and 29.0 <= since(inside, "inner", 1) <= since(inside, "inner") + 1.0
    assert 40.0 <= since(after, "inner") and since(after, "inner", 1) < since(after, "inner") - 5.0
    # self time: the whole of `outer` less both children, wall and cpu
    outer = [r for r in tr.records() if r["stage"] == "svc/outer"][0]
    kids = [r for r in tr.records() if r["stage"].endswith("inner")]
    assert since(after, "outer") == pytest.approx(outer["ms"] - sum(r["ms"] for r in kids), abs=0.5)
    assert since(after, "outer", 1) == pytest.approx(outer["cpu_ms"] - sum(r["cpu_ms"] for r in kids), abs=0.5)
    # two readings partition the thread's time between them: nothing is counted twice
    assert sum(since(after, n) for n in ("outer", "inner")) == pytest.approx(outer["ms"], abs=0.5)


def test_the_tally_is_a_thread_s_own_and_a_record_feeds_it_unless_told_not_to():
    import time

    def worker(out):
        with tr.trace("w/job"):
            time.sleep(0.02)
        out.update(tr.thread_tally())

    mark, theirs = tr.thread_tally(), {}
    with tr.trace("main/job"):
        th = threading.Thread(target=worker, args=(theirs,))
        th.start()
        th.join()
        now = time.time()
        tr.record("waited", now - 0.004, now)  # a part of `job`, read from clocks: job's self time loses it
        gap = tr.record("gap", now - 5.0, now, tally=False)  # an account of time other spans cover
        tr.record("gap/part", now - 5.0, now - 4.0, parent=gap, tally=False, cpu_ms=1.0)
    mine = tr.thread_tally()
    assert 20.0 <= theirs["job"][0] and "waited" not in theirs  # a fresh thread's tally is its own spans'
    assert mine["waited"][0] - mark.get("waited", (0.0, 0.0))[0] == pytest.approx(4.0, abs=0.01)
    assert "gap" not in mine and "part" not in mine
    job = [r for r in tr.records() if r["stage"] == "main/job"][0]
    assert mine["job"][0] - mark.get("job", (0.0, 0.0))[0] == pytest.approx(job["ms"] - 4.0, abs=0.5)
    by = {r["stage"]: r for r in tr.records()}
    assert by["main/job/gap/part"]["parent"] == gap == by["main/job/gap"]["id"] and by["main/job/gap"]["parent"] == job["id"]
    assert by["main/job/gap/part"]["cpu_ms"] == 1.0


def test_a_backdated_span_tallies_from_where_it_opened():
    import time

    mark = tr.thread_tally().get("sweep", (0.0, 0.0))[0]
    with tr.trace("svc/sweep", t0=time.time() - 0.5) as sweep:
        time.sleep(0.01)
    assert sweep["ms"] >= 510.0  # the record runs from t0
    assert 10.0 <= tr.thread_tally()["sweep"][0] - mark < 100.0  # the tally, from the `with`: the time before it was some other span's
