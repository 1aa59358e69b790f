"""Execution-path audit (utils.audit): the gate-arming matrix, the
execution digest, and the flight recorder.

The gate-matrix test pins the one rule every backend gate funnels
through: `on_tpu()` is the first device's `platform == "tpu"` and
nothing else.  The device platform is mocked as "tpu" / "cpu" and every
`auto` gate must resolve to its documented arm.
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


@pytest.mark.parametrize("plat,armed", [("tpu", True), ("cpu", False)])
def test_auto_gates_resolve_documented_arms(monkeypatch, plat, armed):
    """Every 'auto' impl gate arms exactly when the DEVICE platform is
    a TPU."""
    from zkp2p_tpu.prover import groth16_tpu as g

    _patch_backend(monkeypatch, plat)
    monkeypatch.setattr(g, "MSM_UNIFIED", "auto")
    monkeypatch.setattr(g, "MSM_AFFINE", "auto")
    monkeypatch.setattr(g, "MSM_H", "auto")
    monkeypatch.setattr(g, "MSM_SIGNED", True)
    monkeypatch.setattr(g, "MSM_GLV", True)
    monkeypatch.setattr(g, "BATCH_CHUNK", "auto")
    assert g._unified() is armed
    assert g._affine() is armed
    assert g._h_bucket() is armed
    assert g._glv() is True  # GLV is backend-independent (signed-gated)
    assert g._batch_chunk_size() == (4 if armed else 0)
    arms = audit.gate_arms()
    assert arms["msm_unified"] == ("on" if armed else "off")
    assert arms["msm_affine"] == ("on" if armed else "off")
    assert arms["msm_h"] == ("bucket" if armed else "windowed")
    assert arms["msm_glv"] == "on"
    assert arms["batch_chunk"] == ("4" if armed else "0")


def test_forced_arms_beat_the_backend(monkeypatch):
    """'1'/'bucket' force the arm even on a host backend (the tests-only
    configuration), and signed-off disarms bucket-h and GLV."""
    from zkp2p_tpu.prover import groth16_tpu as g

    _patch_backend(monkeypatch, "cpu")
    monkeypatch.setattr(g, "MSM_UNIFIED", "1")
    monkeypatch.setattr(g, "MSM_AFFINE", "1")
    monkeypatch.setattr(g, "MSM_H", "bucket")
    monkeypatch.setattr(g, "MSM_SIGNED", True)
    assert g._unified() is True and g._affine() is True and g._h_bucket() is True
    # signed off: bucket-h and GLV ride the signed machinery
    monkeypatch.setattr(g, "MSM_SIGNED", False)
    monkeypatch.setattr(g, "MSM_GLV", True)
    assert g._h_bucket() is False and g._glv() is False
    assert audit.gate_arms()["msm_h"] == "windowed"
    assert audit.gate_arms()["msm_glv"] == "off"


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
    assert npv._use_glv() is True
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
        "on_tpu", "field_mul", "curve_kernel", "msm_unified", "msm_affine",
        "msm_h", "msm_glv", "batch_chunk", "native_msm_glv",
        "native_batch_affine", "native_msm_multi", "native_tier",
    ):
        assert rep["gates"].get(gate), f"gate {gate} reported no arm"
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
