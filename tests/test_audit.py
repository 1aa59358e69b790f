"""Execution-path audit (utils.audit): the gate-arming matrix, the
execution digest, and the flight recorder.

The gate-matrix test pins the one rule every backend gate funnels
through: `on_tpu()` is the first device's `platform == "tpu"` and
nothing else.  The device platform is mocked as "tpu" / "cpu" and every
choice the device prover makes by itself must follow it.
"""

import re

import jax
import pytest

from zkp2p_tpu.utils import audit
from zkp2p_tpu.utils.metrics import REGISTRY


def _patch_backend(monkeypatch, device_platform: str):
    """Mock the first device's .platform attribute."""
    dev = type("FakeDev", (), {"platform": device_platform})()
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [dev])


# ---------------------------------------------------------------- gates


@pytest.mark.parametrize("plat,expect", [("tpu", True), ("cpu", False)])
def test_on_tpu_matrix(monkeypatch, plat, expect):
    from zkp2p_tpu.utils.jaxcfg import on_tpu

    _patch_backend(monkeypatch, plat)
    assert on_tpu() is expect
    assert audit.gate_arms()["on_tpu"] == ("tpu" if expect else "host")


def _shape_key(n_wires=7, log_m=3):
    """A DeviceProvingKey of shapes alone, for a `_prove_device` whose
    stage programs are stood in for: 7 wires, a over all of them (4
    narrow, 3 wide), b over 5 (3 + 2), c over 4 (3 + 1)."""
    import numpy as np

    from zkp2p_tpu.prover import groth16_tpu as g

    u32, i32 = np.uint32, np.int32
    g1 = lambda n: (np.zeros((n, 16), u32), np.zeros((n, 16), u32))  # noqa: E731
    g2 = lambda n: (np.zeros((n, 2, 16), u32), np.zeros((n, 2, 16), u32))  # noqa: E731
    sel = lambda n: np.arange(n, dtype=i32)  # noqa: E731
    return g.DeviceProvingKey(
        n_public=1, n_wires=n_wires, log_m=log_m,
        a_coeff=np.zeros((1, 16), u32), a_wire=sel(1), a_row=sel(1),
        b_coeff=np.zeros((1, 16), u32), b_wire=sel(1), b_row=sel(1),
        a_bases=g1(n_wires), b1_bases=g1(5), b2_bases=g2(5), c_bases=g1(4), h_bases=g1(1 << log_m),
        b_sel=sel(5), c_sel=sel(4),
        a_nsel=sel(4), a_wsel=sel(3) + 4, b_nsel=sel(3), b_wsel=sel(2) + 3, c_nsel=sel(3), c_wsel=sel(1) + 3,
        alpha_1=None, beta_1=None, beta_2=None, delta_1=None, delta_2=None)


def _stand_in_stages(monkeypatch, g, dpk):
    """The six stage programs stood in for; returns the base counts the
    G1 MSMs were handed, by program."""
    import numpy as np

    n, m = dpk.n_wires, 1 << dpk.log_m
    counts = {"g1": [], "g1_narrow": []}
    planes = lambda b, k, cols: (np.zeros((b, k, cols), np.uint32), np.zeros((b, k, cols), bool))  # noqa: E731

    def h_planes(dpk_, w_mont, h_window):
        b = w_mont.shape[0]
        return (planes(b, 64, n), planes(b, g.NARROW_PLANES, n)), planes(b, 64, m)

    def msm(name, limbs):
        def run(bases, planes_):
            if name in counts:
                counts[name].append(int(bases[0].shape[0]))
            return tuple(np.zeros((planes_[0].shape[0],) + limbs, np.uint32) for _ in range(3))
        return run

    adds = type("Adds", (), {"add": staticmethod(lambda a, b: a)})
    monkeypatch.setattr(g, "_jit_h_planes", h_planes)
    monkeypatch.setattr(g, "_jit_msm_g1", msm("g1", (16,)))
    monkeypatch.setattr(g, "_jit_msm_g1_narrow", msm("g1_narrow", (16,)))
    monkeypatch.setattr(g, "_jit_msm_g2", msm("g2", (2, 16)))
    monkeypatch.setattr(g, "_jit_msm_g2_narrow", msm("g2_narrow", (2, 16)))
    monkeypatch.setattr(g, "G1J", adds)
    monkeypatch.setattr(g, "G2J", adds)
    monkeypatch.setattr(g, "_h_table_window", lambda log_m, device=None, mesh=None: None)  # the scan road: h through the G1 program too
    return counts


@pytest.mark.parametrize("plat,armed", [("tpu", True), ("cpu", False)])
def test_auto_gates_resolve_documented_arms(monkeypatch, plat, armed):
    """What the device prover chooses by itself it chooses from the
    DEVICE platform: on a TPU the batch is chunked, and the MSMs of a
    class are padded to one base count so they share one executable (h
    apart, at its own size); elsewhere neither."""
    import numpy as np

    from zkp2p_tpu.prover import groth16_tpu as g

    _patch_backend(monkeypatch, plat)
    monkeypatch.setattr(g, "BATCH_CHUNK", "auto")
    assert g._batch_chunk_size() == (4 if armed else 0)
    assert audit.gate_arms()["batch_chunk"] == ("4" if armed else "0")

    dpk = _shape_key()
    counts = _stand_in_stages(monkeypatch, g, dpk)
    acc = g._prove_device(dpk, np.zeros((2, dpk.n_wires, 16), np.uint32))
    assert len(acc) == 5
    assert counts["g1_narrow"] == ([4, 4, 4] if armed else [4, 3, 3])  # a, b1, c
    assert counts["g1"] == ([3, 3, 3, 8] if armed else [3, 2, 1, 8])  # a, b1, c wide; then h over the domain


def test_gate_arms_after_a_prove(monkeypatch):
    """What a prove leaves in the gate map: the platform, the field and
    curve implementations, the chunk and the mesh — and no arm of an
    MSM formulation, because there is one.  The key's own stage programs
    are traced (a gate baked into a program records at its trace), not
    compiled: each answers with zeros of its output's shape."""
    import jax
    import numpy as np
    from test_msm_resident import _no_narrow_class, _toy_world

    from zkp2p_tpu.prover import groth16_tpu as g

    def traced(jitted):
        def run(*args, **kw):
            out = jax.eval_shape(jitted, *args, **kw)
            return jax.tree_util.tree_map(lambda s: np.zeros(s.shape, s.dtype), out)
        return run

    _cs, _pk, dpk, wits = _toy_world(monkeypatch)
    dpk = _no_narrow_class(dpk)  # one MSM a query: no curve add between two classes to run op by op
    for name in ("_jit_h_planes", "_jit_h_table", "_jit_msm_h_resident"):
        getattr(g, name).clear_cache()  # an arm is recorded at a trace: not by a program another file's test left traced
        monkeypatch.setattr(g, name, traced(getattr(g, name)))
    monkeypatch.setattr(g, "_assemble", lambda dpk_, acc, r, s: acc)
    monkeypatch.setattr(audit, "_arms", {})
    g.prove_tpu_batch(dpk, wits[:1], rs=[5], ss=[7])
    arms = audit.gate_arms()
    device = {k: v for k, v in arms.items() if not k.startswith("native_")}
    assert set(device) - {"field_conv"} == {"on_tpu", "field_mul", "curve_kernel", "batch_chunk", "tpu_shard"}, arms
    assert (device["on_tpu"], device["field_mul"], device["curve_kernel"]) == ("host", "xla", "xla")
    assert (device["batch_chunk"], device["tpu_shard"]) == ("0", "off")
    assert not {"msm_unified", "msm_affine", "msm_h", "msm_glv"} & set(arms)


def test_field_and_curve_gates(monkeypatch):
    from zkp2p_tpu.curve import jcurve
    from zkp2p_tpu.curve.jcurve import G1J
    from zkp2p_tpu.field import jfield

    _patch_backend(monkeypatch, "cpu")
    monkeypatch.setattr(jfield, "FIELD_MUL_IMPL", "auto")
    monkeypatch.setattr(jcurve, "CURVE_IMPL", "auto")
    assert jfield.field_mul_impl() == "xla"
    assert G1J._pallas() is False
    assert audit.gate_arms()["field_mul"] == "xla"
    assert audit.gate_arms()["curve_kernel"] == "xla"
    # "pallas" requested on a host backend stays OFF for both gates:
    # the kernels are compiled for the chip or not used — interpret
    # mode is never chosen implicitly (the differential tests pass it
    # explicitly) — the REQUESTED-but-not-armed case preflight flags
    monkeypatch.setattr(jfield, "FIELD_MUL_IMPL", "pallas")
    assert jfield.field_mul_impl() == "xla"
    assert audit.gate_arms()["field_mul"] == "xla"
    monkeypatch.setattr(jcurve, "CURVE_IMPL", "pallas")
    assert G1J._pallas() is False
    assert audit.gate_arms()["curve_kernel"] == "xla"
    # on the TPU both arm, forced or auto; "xla" still forces them off
    _patch_backend(monkeypatch, "tpu")
    assert G1J._pallas() is True
    assert jfield.field_mul_impl() == "pallas"
    monkeypatch.setattr(jfield, "FIELD_MUL_IMPL", "auto")
    assert jfield.field_mul_impl() == "pallas"
    monkeypatch.setattr(jfield, "FIELD_MUL_IMPL", "xla")
    monkeypatch.setattr(jcurve, "CURVE_IMPL", "xla")
    assert jfield.field_mul_impl() == "xla"
    assert G1J._pallas() is False


def test_native_gates(monkeypatch):
    from zkp2p_tpu.prover import native_prove as npv

    monkeypatch.setenv("ZKP2P_MSM_GLV", "1")
    monkeypatch.setenv("ZKP2P_MSM_BATCH_AFFINE", "0")
    monkeypatch.setenv("ZKP2P_MSM_MULTI", "0")
    monkeypatch.setenv("ZKP2P_MSM_PRECOMP", "0")
    assert npv._glv_arm() is True
    assert npv._use_batch_affine() is False
    assert npv._use_msm_multi() is False
    assert npv._use_msm_precomp() is False
    # batch-affine off gates the IFMA tier off regardless of hardware
    assert npv._native_ifma_tier() is False
    arms = audit.gate_arms()
    assert arms["native_msm_glv"] == "on"
    assert arms["native_batch_affine"] == "off"
    assert arms["native_msm_multi"] == "off"
    assert arms["native_msm_precomp"] == "off"
    assert arms["native_tier"] == "scalar"
    # default arm: multi + precomp ON (the _not_zero rule — off only on
    # a leading '0')
    monkeypatch.delenv("ZKP2P_MSM_MULTI", raising=False)
    assert npv._use_msm_multi() is True
    assert audit.gate_arms()["native_msm_multi"] == "on"
    monkeypatch.delenv("ZKP2P_MSM_PRECOMP", raising=False)
    assert npv._use_msm_precomp() is True
    assert audit.gate_arms()["native_msm_precomp"] == "on"


# ------------------------------------------------------------- digest


def test_execution_digest_stable_and_arm_sensitive():
    d_ab = audit.execution_digest({"g1": "a", "g2": "b"})
    assert re.fullmatch(r"[0-9a-f]{16}", d_ab)
    # order-independent: the digest hashes the SORTED map
    assert audit.execution_digest({"g2": "b", "g1": "a"}) == d_ab
    # one flipped arm changes it; one added gate changes it
    assert audit.execution_digest({"g1": "c", "g2": "b"}) != d_ab
    assert audit.execution_digest({"g1": "a", "g2": "b", "g3": "x"}) != d_ab


def test_record_arm_counters_and_map():
    base = REGISTRY.counter("zkp2p_path_taken_total", {"gate": "test_gate", "arm": "x"}).value
    assert audit.record_arm("test_gate", "x") == "x"
    audit.record_arm("test_gate", "x")
    assert REGISTRY.counter("zkp2p_path_taken_total", {"gate": "test_gate", "arm": "x"}).value == base + 2
    assert audit.gate_arms()["test_gate"] == "x"
    # bools render as on/off and pass through unchanged
    assert audit.record_arm("test_gate_b", True) is True
    assert audit.gate_arms()["test_gate_b"] == "on"


def test_record_arm_survives_registry_reset():
    """REGISTRY.reset() orphans instruments; the audit counter cache is
    generation-keyed so later records land in live instruments."""
    audit.record_arm("test_gen_gate", "a")
    REGISTRY.reset()
    audit.record_arm("test_gen_gate", "a")
    assert REGISTRY.counter("zkp2p_path_taken_total", {"gate": "test_gen_gate", "arm": "a"}).value == 1


def test_run_manifest_carries_gates_and_digest():
    from zkp2p_tpu.utils.metrics import run_manifest

    audit.record_arm("test_manifest_gate", "armed")
    man = run_manifest()
    assert man["gates"]["test_manifest_gate"] == "armed"
    assert man["execution_digest"] == audit.execution_digest()


# ------------------------------------------------------ flight recorder


def test_memory_sampler_degrades_on_cpu():
    # XLA:CPU exposes no memory_stats — sampling must be a cheap no-op
    assert audit.sample_device_memory("test") is None


def test_memory_sampler_gauges(monkeypatch):
    class Dev:
        platform = "tpu"

        @staticmethod
        def memory_stats():
            return {"bytes_in_use": 100, "peak_bytes_in_use": 250, "bytes_limit": 1000}

    monkeypatch.setattr(jax, "devices", lambda *a, **k: [Dev()])
    monkeypatch.setattr(audit, "_mem_devices", None)  # re-probe with the fake
    got = audit.sample_device_memory("test_stage")
    assert got == {"device": 0, "bytes_in_use": 100, "peak_bytes_in_use": 250, "bytes_limit": 1000}
    assert REGISTRY.gauge("zkp2p_hbm_bytes_in_use", {"device": "0"}).value == 100
    assert REGISTRY.gauge("zkp2p_hbm_peak_bytes", {"device": "0"}).value == 250
    # stage peak keeps the MAX across samples
    assert REGISTRY.gauge("zkp2p_hbm_stage_peak_bytes", {"stage": "test_stage"}).value == 250

    class Smaller(Dev):
        @staticmethod
        def memory_stats():
            return {"bytes_in_use": 50, "peak_bytes_in_use": 60, "bytes_limit": 1000}

    monkeypatch.setattr(jax, "devices", lambda *a, **k: [Smaller()])
    monkeypatch.setattr(audit, "_mem_devices", None)
    audit.sample_device_memory("test_stage")
    assert REGISTRY.gauge("zkp2p_hbm_stage_peak_bytes", {"stage": "test_stage"}).value == 250


def test_compile_listener_attributes_stage():
    import jax.numpy as jnp

    from zkp2p_tpu.utils.trace import trace

    assert audit.install_compile_listener()
    assert audit.install_compile_listener()  # idempotent
    n0 = REGISTRY.counter("zkp2p_compile_events_total", {"stage": "audit_compile_test"}).value
    with trace("audit_compile_test"):
        # a fresh closure constant -> a fresh executable -> one compile
        jax.jit(lambda x: x * 7919 + 11)(jnp.arange(4)).block_until_ready()
    assert REGISTRY.counter("zkp2p_compile_events_total", {"stage": "audit_compile_test"}).value > n0
    assert REGISTRY.counter("zkp2p_compile_seconds_total", {"stage": "audit_compile_test"}).value > 0


def test_a_forced_relowering_under_a_span_is_counted_with_the_stage_path(capsys):
    """The listener counts tracing and lowering as it counts compiling,
    labelled by the span open on the thread; under a service/* span — a
    replica lowering while it serves — it also logs the path and the
    seconds.  Every counter is there at zero from installation."""
    import jax.numpy as jnp

    from zkp2p_tpu.utils.trace import trace

    assert audit.install_compile_listener()
    names = {m["name"] for m in REGISTRY.snapshot() if not m["labels"]}
    assert {"zkp2p_lower_events_total", "zkp2p_lower_seconds_total",
            "zkp2p_compile_events_total", "zkp2p_compile_seconds_total"} <= names
    stage = {"stage": "service/prove/tpu/prove_batch/dispatch"}
    lowered = lambda: REGISTRY.counter("zkp2p_lower_events_total", stage).value  # noqa: E731
    fn = jax.jit(lambda x: x * 104729 + 13)
    x4, x8 = jnp.arange(4), jnp.arange(8)  # made outside the span: making them lowers programs too
    with trace("service/prove"), trace("tpu/prove_batch"), trace("device", leaf=True), trace("dispatch"):
        n0 = lowered()
        fn(x4).block_until_ready()
        assert lowered() == n0 + 1  # a warmed window reads 0; this one reads 1, with the stage's path
        fn(x4).block_until_ready()
        assert lowered() == n0 + 1  # a cache hit lowers nothing
        fn(x8).block_until_ready()  # a new shape: the forced re-lowering
        assert lowered() == n0 + 2
    assert REGISTRY.counter("zkp2p_lower_seconds_total", stage).value > 0
    err = capsys.readouterr().err
    assert "[service] lowering under service/prove/tpu/prove_batch/dispatch: " in err


# ------------------------------------------------------------ preflight


def test_preflight_reports_every_gate_and_is_stable():
    rep = audit.preflight(workload=False)
    for gate in (
        "on_tpu", "field_mul", "curve_kernel", "batch_chunk", "tpu_shard", "native_msm_glv",
        "native_batch_affine", "native_msm_multi", "native_tier",
    ):
        assert rep["gates"].get(gate), f"gate {gate} reported no arm"
    # the device prover has one MSM formulation, so no arm of one
    assert not {"msm_unified", "msm_affine", "msm_h", "msm_glv"} & set(rep["gates"])
    assert re.fullmatch(r"[0-9a-f]{16}", rep["execution_digest"])
    assert rep["backend"] == "cpu"
    assert "tpu_probe" not in rep  # no probe: the backend is what JAX initialised
    # a second in-process run arms the same gates to the same arms
    rep2 = audit.preflight(workload=False)
    assert rep2["gates"] == rep["gates"]
    assert rep2["execution_digest"] == rep["execution_digest"]


def test_preflight_flags_misarmed_pallas(monkeypatch):
    """pallas requested on a host backend: the gate stays on the XLA
    arm (never interpret mode) and preflight says so."""
    from zkp2p_tpu.field import jfield

    monkeypatch.setenv("ZKP2P_FIELD_MUL", "pallas")
    monkeypatch.setattr(jfield, "FIELD_MUL_IMPL", "pallas")
    rep = audit.preflight(workload=False)
    assert rep["gates"]["field_mul"] == "xla"
    assert any("field_mul=pallas requested" in w for w in rep["warnings"]), rep["warnings"]
    monkeypatch.delenv("ZKP2P_FIELD_MUL")
    monkeypatch.setattr(jfield, "FIELD_MUL_IMPL", "auto")
    ok = audit.preflight(workload=False)
    assert not any("field_mul" in w for w in ok["warnings"])
