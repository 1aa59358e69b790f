"""What only the chip's machine can satisfy, on one class, so that the
tests can run the rest of a run on the CPU with it stubbed."""

from __future__ import annotations

from typing import Dict, List


class Chip:
    def require(self, chips: int) -> Dict:
        """The device as JAX reports it.  Exits non-zero, naming what was
        found, without a TPU or with fewer chips than the cell asks for:
        there is no CPU tier."""
        import jax

        devs = jax.devices()
        if devs[0].platform != "tpu":
            raise SystemExit(
                f"benchmarks: needs a TPU; JAX found platform {devs[0].platform!r} "
                f"({devs[0].device_kind} x{len(devs)}) — nothing measured")
        if len(devs) < chips:
            raise SystemExit(f"benchmarks: the cell asks for {chips} chips; JAX found {len(devs)} — nothing measured")
        return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}

    def native_library(self) -> None:
        """`get_lib` runs make on the committed source (a no-op when the
        .so here is newer) and never loads a library it could not build."""
        from zkp2p_tpu.native.lib import get_lib

        if get_lib() is None:
            raise SystemExit("benchmarks: the native library did not build (it makes the key and is the oracle)")

    def arm_faults(self, arms: Dict[str, str], want: Dict[str, str]) -> List[str]:
        """Why the gates are not armed as the configuration says, or []."""
        faults = [f"{g}={arms.get(g)!r}, wanted {w!r}" for g, w in want.items() if arms.get(g) != w]
        # host_profile / window_source read "fallback" on any machine without a
        # tuned host profile; they name the C++ prover's constants, not a path
        # round the device
        faults += [f"{g}=fallback" for g, a in arms.items()
                   if a == "fallback" and g not in ("host_profile", "window_source")]
        return faults

    def memory_stats(self) -> List[Dict]:
        import jax

        return [d.memory_stats() or {} for d in jax.devices()]
