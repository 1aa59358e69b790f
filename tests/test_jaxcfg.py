"""Compile-cache placement (utils.jaxcfg): the directory is decided from
OUTSIDE the program.  JAX_COMPILATION_CACHE_DIR set -> exactly that
directory, and the program sets no directory in code; unset ->
`<checkout>/.jax_cache`, no suffix.  A path derived from the host, a
pid, a temp name or the time would move between machines and never hit.
"""

import os
import subprocess
import sys

import jax

from zkp2p_tpu.utils import jaxcfg

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _record_updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda k, v: calls.append((k, v)))
    return calls


def test_variable_set_means_exactly_that_directory(monkeypatch, tmp_path):
    monkeypatch.delenv("ZKP2P_NO_CACHE", raising=False)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert jaxcfg.cache_dir() == str(tmp_path)
    calls = _record_updates(monkeypatch)
    jaxcfg.enable_cache()
    # JAX reads the variable itself: the program sets no directory
    assert "jax_compilation_cache_dir" not in [k for k, _v in calls]
    assert ("jax_persistent_cache_min_compile_time_secs", 1.0) in calls


def test_variable_unset_means_checkout_jax_cache_no_suffix(monkeypatch):
    monkeypatch.delenv("ZKP2P_NO_CACHE", raising=False)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert jaxcfg.cache_dir() == want
    calls = _record_updates(monkeypatch)
    jaxcfg.enable_cache(min_compile_s=0.0)
    assert ("jax_compilation_cache_dir", want) in calls


def test_no_cache_switch_touches_nothing(monkeypatch):
    monkeypatch.setenv("ZKP2P_NO_CACHE", "1")
    calls = _record_updates(monkeypatch)
    jaxcfg.enable_cache()
    assert calls == []


_ENTRY = r"""
import os, sys
sys.path.insert(0, sys.argv[1])
from zkp2p_tpu.pipeline import cli
import jax
seen = {}
def fake(args):
    seen["dir"] = jax.config.jax_compilation_cache_dir
    seen["platforms"] = jax.config.jax_platforms
    from jax._src import xla_bridge
    seen["backend_up"] = xla_bridge.backends_are_initialized()
cli.cmd_verify = fake
cli.cmd_prove = fake
cli.main(sys.argv[2:])
print("SEEN", seen["dir"], seen["platforms"], seen["backend_up"])
"""


def _cli_entry(env_dir, *argv):
    env = {k: v for k, v in os.environ.items()
           if k not in ("ZKP2P_NO_CACHE", "JAX_COMPILATION_CACHE_DIR", "JAX_PLATFORMS")}
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run(
        [sys.executable, "-c", _ENTRY, REPO, *argv],
        capture_output=True, text=True, timeout=120, env=env, check=True,
    ).stdout
    return [ln for ln in out.splitlines() if ln.startswith("SEEN")][0].split()[1:]


def test_cli_entry_point_places_the_cache_by_the_same_rule(tmp_path):
    """`python -m zkp2p_tpu ...` resolves the same directory as pytest,
    bench.py and chip_smoke.py: all of them call enable_cache()."""
    d, _plat, up = _cli_entry(str(tmp_path), "verify", "--proof", "p", "--public", "q")
    assert d == str(tmp_path) and up == "False"
    d, _plat, up = _cli_entry(None, "verify", "--proof", "p", "--public", "q")
    assert d == os.path.join(REPO, ".jax_cache") and up == "False"


def test_native_prover_process_stays_off_the_chip(tmp_path):
    """One process per chip: `--prover native` pins this process's JAX
    to the host platform before any backend exists; `--prover tpu`
    leaves the platform to JAX."""
    _d, plat, up = _cli_entry(str(tmp_path), "prove", "--prover", "native")
    assert plat == "cpu" and up == "False"
    _d, plat, _up = _cli_entry(str(tmp_path), "prove", "--prover", "tpu")
    assert plat != "cpu"


_NO_BACKEND = r"""
import sys
sys.path.insert(0, sys.argv[1])
from jax._src import xla_bridge
import zkp2p_tpu.prover, zkp2p_tpu.prover.native_prove, zkp2p_tpu.prover.setup_device
import zkp2p_tpu.pipeline.service, zkp2p_tpu.pipeline.fleet, zkp2p_tpu.parallel.mesh
print("IMPORTS", xla_bridge.backends_are_initialized())
from zkp2p_tpu.pipeline import cli
try:
    cli.main(["--circuit", "toy", "fleet", "--spool", sys.argv[2], "--workers", "1",
              "--worker-cmd", '["sleep", "5"]', "--max-seconds", "1"])
except SystemExit as e:
    print("EXIT", e.code)
print("SUPERVISOR", xla_bridge.backends_are_initialized())
"""


def test_importing_the_program_and_supervising_a_fleet_initialise_no_backend(tmp_path):
    """A process that has initialised a JAX backend holds the chip.
    Importing the prover must not (the field constants are host
    arrays), and the fleet supervisor must never: its workers need it."""
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    out = subprocess.run(
        [sys.executable, "-c", _NO_BACKEND, REPO, str(tmp_path)],
        capture_output=True, text=True, timeout=120, env=env, check=True,
    ).stdout
    assert "IMPORTS False" in out, out
    assert "EXIT 0" in out and "SUPERVISOR False" in out, out


_KEY_STABILITY = r"""
import hashlib, sys
sys.path.insert(0, sys.argv[1])
from zkp2p_tpu.utils.jaxcfg import enable_cache
enable_cache()
import jax, jax.numpy as jnp
from zkp2p_tpu.field.jfield import FQ
from zkp2p_tpu.ops import pallas_curve as pc, pallas_mont as pm
S = jax.ShapeDtypeStruct
def by_the_kernel_differential():
    p = tuple(S((8, 16), jnp.uint32) for _ in range(3))
    pc.g1_double.trace(FQ, p, False).lower(lowering_platforms=("tpu",))
def by_the_prover():
    def to_mont():
        a = S((300, 16), jnp.uint32)
        pm.mont_mul.trace(FQ, a, a, False).lower(lowering_platforms=("tpu",))
    to_mont()
{"A": by_the_kernel_differential, "B": by_the_prover}[sys.argv[2]]()
p = tuple(S((64, 16), jnp.uint32) for _ in range(3))
lowered = pc.g1_add.trace(FQ, p, p, False).lower(lowering_platforms=("tpu",))
print("MODULE", hashlib.sha256(lowered.as_text().encode()).hexdigest())
"""


def test_kernel_executable_bytes_do_not_depend_on_the_road_taken():
    """The persistent cache hashes an executable's module, Mosaic kernel
    bytecode and its MLIR locations included.  The kernels' field
    product is ONE cached jaxpr, first traced wherever the process first
    needed it: the lowered module must be the same whether that was the
    kernel differential or the prover, or a second process never hits
    (lowered for the TPU here, on the CPU — no chip needed)."""
    def module_hash(road):
        out = subprocess.run(
            [sys.executable, "-c", _KEY_STABILITY, REPO, road],
            capture_output=True, text=True, timeout=300, check=True,
            env={k: v for k, v in os.environ.items() if k != "ZKP2P_NO_CACHE"},
        ).stdout
        return [ln for ln in out.splitlines() if ln.startswith("MODULE")][0]

    assert module_hash("A") == module_hash("B")
