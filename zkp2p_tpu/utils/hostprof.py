"""Host auto-tune profile: detection, fingerprinting, persistence.

Every measured constant in the repo — precomp geometry c/q/L, Pippenger
windows, `ZKP2P_NATIVE_THREADS`, batch columns — was hand-picked on one
2-core IFMA box.  `zkp2p-tpu tune` (pipeline.tune) automates the
re-sweep a wider host needs: it measures this host's micro-arms and persists the winners here as an
atomic, fingerprint-keyed JSON profile beside `.bench_cache`.  This
module is the profile's home: hardware detection (cache sizes + core
topology via the native runtime's sysconf probe, sysfs fallback), the
fingerprint policy, load-time validation, and the typed accessors the
resolvers consume (precomp geometry, native thread default, AmortModel
seed points).

Fingerprint policy: the profile embeds the hardware identity it was
tuned on (CPU model, logical/physical core counts, SMT width, L1d/L2/L3
bytes, IFMA tier) and its 16-hex digest is both the default filename
key and the load-time check.  A profile copied onto foreign hardware —
or a host whose topology changed under a pinned path — is REJECTED and
the caller falls back to the committed hand-picked constants, so a
stale profile can degrade a host back to baseline but never mis-tune
it.  The IFMA field is the *gated* tier (ZKP2P_NATIVE_IFMA applied):
a profile tuned with the 52-limb paths on must not steer a scalar run.

The profile-load gate is `record_arm`'d ("host_profile" -> off | tuned
| fallback) and preflight-armed, so tuned-vs-fallback A/Bs are
execution-digest-distinguishable.  Consumers treat every accessor as
Optional: no profile, a foreign profile, or ZKP2P_PROFILE=0 all resolve
to None and the documented fallback constants apply (byte-identical to
the pre-profile behavior, pinned by tests/test_tune.py).
"""

from __future__ import annotations

import glob
import hashlib
import json
import os
import threading
from typing import Dict, Optional, Tuple

SCHEMA_VERSION = 1
PROFILE_PREFIX = "host_profile_"

# hardware-identity fields, in digest order — the fingerprint contract.
# Append-only: dropping or reordering silently orphans every profile.
FP_FIELDS = (
    "cpu_model", "cpu_count", "physical_cores", "smt_per_core",
    "l1d_bytes", "l2_bytes", "l3_bytes", "ifma",
)

# profile geometry only applies at and above this family bit-length —
# the same floor the hand-picked fixed-tier c=16 constant uses
# (precomp._pick_window_fixed); below it the small-key heuristic is
# already shape-aware and a bench-shape sweep has nothing to say.
GEOMETRY_MIN_BL = 15

_lock = threading.Lock()
_fp_memo: Optional[Dict] = None
# (path, mtime_ns) -> validated profile dict or None; one entry
_load_memo: Optional[Tuple[Tuple[str, int], Optional[Dict]]] = None


def _sysfs_read(path: str) -> str:
    try:
        with open(path) as f:
            return f.read().strip()
    except OSError:
        return ""


def _cpu_model() -> str:
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith("model name"):
                    return line.split(":", 1)[1].strip()
    except OSError:
        pass
    return "unknown"


def _sysfs_cache_bytes(level: int, want_type: Tuple[str, ...]) -> int:
    """Largest matching cache at `level` across cpu0's index dirs (the
    fallback when the native lib's sysconf probe is unavailable)."""
    best = 0
    for d in glob.glob("/sys/devices/system/cpu/cpu0/cache/index*"):
        if _sysfs_read(os.path.join(d, "level")) != str(level):
            continue
        if _sysfs_read(os.path.join(d, "type")) not in want_type:
            continue
        size = _sysfs_read(os.path.join(d, "size"))
        try:
            mult = 1
            if size.endswith("K"):
                size, mult = size[:-1], 1024
            elif size.endswith("M"):
                size, mult = size[:-1], 1 << 20
            best = max(best, int(size) * mult)
        except ValueError:
            continue
    return best


def _topology() -> Tuple[int, int, int]:
    """(logical_cpus, physical_cores, smt_per_core) from sysfs thread
    siblings; degrades to (cpu_count, cpu_count, 1) when sysfs is
    absent (containers, exotic kernels) — sizing for logical cores is
    today's behavior, so the fallback never regresses it."""
    logical = max(1, os.cpu_count() or 1)
    cores = set()
    seen = 0
    for d in glob.glob("/sys/devices/system/cpu/cpu[0-9]*"):
        sib = _sysfs_read(os.path.join(d, "topology", "thread_siblings_list"))
        if not sib:
            continue
        seen += 1
        cores.add(sib)
    if seen == 0 or not cores:
        return logical, logical, 1
    physical = len(cores)
    return seen, physical, max(1, seen // physical)


def cache_hierarchy() -> Dict[str, int]:
    """{"l1d": B, "l2": B, "l3": B} — native sysconf probe first (the
    csrc detection the MSM schedules key off), sysfs fallback, 0 =
    unknown at that level."""
    from ..native.lib import cache_sizes

    native = cache_sizes() or {}
    out = {}
    for name, level, want in (
        ("l1d", 1, ("Data", "Unified")),
        ("l2", 2, ("Data", "Unified")),
        ("l3", 3, ("Data", "Unified")),
    ):
        v = int(native.get(name) or 0)
        out[name] = v if v > 0 else _sysfs_cache_bytes(level, want)
    return out


def host_fingerprint() -> Dict:
    """This host's hardware identity (memoized per process)."""
    global _fp_memo
    with _lock:
        if _fp_memo is not None:
            return dict(_fp_memo)
    from ..native.lib import ifma_available

    logical, physical, smt = _topology()
    caches = cache_hierarchy()
    fp = {
        "cpu_model": _cpu_model(),
        "cpu_count": logical,
        "physical_cores": physical,
        "smt_per_core": smt,
        "l1d_bytes": caches["l1d"],
        "l2_bytes": caches["l2"],
        "l3_bytes": caches["l3"],
        "ifma": 1 if ifma_available() else 0,
    }
    with _lock:
        _fp_memo = dict(fp)
    return fp


def fingerprint_key(fp: Optional[Dict] = None) -> str:
    """16-hex digest of the identity fields — the profile filename key
    and the load-time foreign-hardware check."""
    fp = host_fingerprint() if fp is None else fp
    blob = json.dumps([(k, fp.get(k)) for k in FP_FIELDS], separators=(",", ":"))
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def default_profile_path() -> Optional[str]:
    """`<precomp cache dir>/host_profile_<fingerprint>.json` — beside
    the `.bench_cache` tables; None when persistence is disabled
    (ZKP2P_MSM_PRECOMP_CACHE=0)."""
    from ..prover.precomp import _cache_dir

    d = _cache_dir()
    if d is None:
        return None
    return os.path.join(d, PROFILE_PREFIX + fingerprint_key() + ".json")


def save_profile(profile: Dict, path: Optional[str] = None) -> Optional[str]:
    """Persist atomically (tmp + rename, the `_persist_table` pattern:
    a fleet worker racing a tune must never load a torn profile).
    Stamps schema + this host's fingerprint; returns the path written,
    None when no path resolves (persistence off)."""
    path = path or default_profile_path()
    if not path:
        return None
    prof = dict(profile)
    prof["schema"] = SCHEMA_VERSION
    prof["fingerprint"] = host_fingerprint()
    prof["fingerprint_key"] = fingerprint_key()
    tmp = f"{path}.tmp.{os.getpid()}"
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    try:
        with open(tmp, "w") as f:
            json.dump(prof, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        try:
            if os.path.exists(tmp):
                os.remove(tmp)
        except OSError:
            pass
        return None
    reset(fingerprint=False)
    return path


def _validated(path: str) -> Optional[Dict]:
    """Load + validate one profile file; None on ANY mismatch (missing,
    unparseable, schema drift, foreign or tampered fingerprint)."""
    try:
        with open(path) as f:
            prof = json.load(f)
    except (OSError, ValueError):
        return None
    if not isinstance(prof, dict) or prof.get("schema") != SCHEMA_VERSION:
        return None
    fp = prof.get("fingerprint")
    if not isinstance(fp, dict):
        return None
    embedded_key = fingerprint_key(fp)
    if prof.get("fingerprint_key") != embedded_key:
        return None  # body edited after signing — distrust all of it
    if embedded_key != fingerprint_key():
        return None  # foreign hardware: rebuild, never mis-tune
    return prof


def load_profile() -> Optional[Dict]:
    """The validated host profile, or None (gate off, no file, foreign
    file).  Records the "host_profile" execution-audit gate on every
    resolution — off | tuned | fallback — so an A/B's two digests
    differ exactly on this arm.  Memoized per (path, mtime)."""
    global _load_memo
    from .audit import record_arm
    from .config import load_config

    cfg = load_config()
    if not cfg.profile:
        record_arm("host_profile", "off")
        return None
    path = cfg.profile_path or default_profile_path()
    prof: Optional[Dict] = None
    if path:
        try:
            mtime = os.stat(path).st_mtime_ns
        except OSError:
            mtime = -1
        key = (path, mtime)
        with _lock:
            memo = _load_memo
        if memo is not None and memo[0] == key:
            prof = memo[1]
        else:
            prof = _validated(path) if mtime >= 0 else None
            with _lock:
                _load_memo = (key, prof)
    record_arm("host_profile", "tuned" if prof is not None else "fallback")
    return prof


def profile_arm() -> str:
    """Resolve + arm the profile gate (the preflight hook)."""
    from .audit import gate_arms

    load_profile()
    return gate_arms().get("host_profile", "fallback")


def geometry_for(family: str, n: int) -> Optional[Dict]:
    """Tuned fixed-tier geometry for a G1 family of n points: a dict
    with "c" (and optionally "q"), or None -> the hand-picked fallback.
    Only applies at bench-sweep scale (bit_length >= min_bl): the tune
    pass measured full-width shapes, and the small-key heuristic is
    already shape-aware."""
    prof = load_profile()
    if prof is None:
        return None
    fixed = prof.get("msm_fixed")
    if not isinstance(fixed, dict):
        return None
    if n.bit_length() < int(fixed.get("min_bl", GEOMETRY_MIN_BL)):
        return None
    geom = fixed.get("families", {}).get(family) or fixed.get("default")
    if not isinstance(geom, dict) or "c" not in geom:
        return None
    try:
        c = int(geom["c"])
    except (TypeError, ValueError):
        return None
    if not 4 <= c <= 20:  # a corrupt c would allocate 2^(c-1) buckets
        return None
    out = {"c": c}
    if "q" in geom:
        try:
            out["q"] = max(1, int(geom["q"]))
        except (TypeError, ValueError):
            pass
    return out


def tuned_threads() -> Optional[int]:
    """The profile's measured-best native thread count (topology-aware:
    physical cores, not SMT siblings), or None -> size from
    os.cpu_count() as today."""
    prof = load_profile()
    if prof is None:
        return None
    try:
        v = int(prof.get("threads", {}).get("native_default"))
    except (TypeError, ValueError):
        return None
    return v if v >= 1 else None


def tuned_window(tag: str, bl: int, threads: int) -> Optional[int]:
    """The measured-best VARIABLE-BASE Pippenger window for `tag`
    ("plain" | "glv") or None -> the committed curve (_pick_window*).

    Applies only at the EXACT measured context: the sweep ran one shape
    at one thread count, and the window optimum is not monotone in
    either (the glv curve steps DOWN a window at 2^19 when the deferred
    bucket block falls out of LLC) — so `bl` must equal the recorded
    scalar-count bit length and `threads` the recorded worker count, or
    the committed curve stays authoritative.  c is bounds-checked like
    geometry_for (a corrupt c would allocate 2^(c-1) buckets)."""
    prof = load_profile()
    if prof is None:
        return None
    win = prof.get("msm_window")
    if not isinstance(win, dict):
        return None
    row = win.get("families", {}).get(tag)
    if not isinstance(row, dict):
        return None
    try:
        c = int(row["c"])
        if int(row["bl"]) != int(bl) or int(win.get("threads")) != int(threads):
            return None
    except (KeyError, TypeError, ValueError):
        return None
    return c if 4 <= c <= 20 else None


def amort_points(tier: str = "native") -> Optional[Dict[int, float]]:
    """Measured batch-cost points {S: seconds} to seed the scheduler's
    AmortModel (pipeline.sched), or None.  Validated here (strictly
    increasing in both axes, positive) so a corrupt profile degrades to
    the built-in curve instead of raising in the service loop.

    Per worker tier: "native" reads the classic sched.amort_points;
    any other tier reads sched.tiers.<tier>.amort_points (the sharded
    pod-mesh curve a tune pass on mesh hardware records) — absent, the
    caller's built-in per-tier default applies."""
    prof = load_profile()
    if prof is None:
        return None
    sched = prof.get("sched", {})
    if tier == "native":
        raw = sched.get("amort_points")
    else:
        tiers = sched.get("tiers")
        raw = tiers.get(tier, {}).get("amort_points") if isinstance(tiers, dict) else None
    if not isinstance(raw, dict) or not raw:
        return None
    try:
        pts = {int(k): float(v) for k, v in raw.items()}
    except (TypeError, ValueError):
        return None
    ss = sorted(pts)
    if ss[0] < 1 or pts[ss[0]] <= 0.0:
        return None
    for a, b in zip(ss, ss[1:]):
        if pts[b] <= pts[a]:
            return None
    return pts


def profile_manifest() -> Dict:
    """Run-manifest block: which arm resolved, from where — so every
    bench/trace artifact can say whether a tuned profile steered it."""
    from .audit import gate_arms
    from .config import load_config

    prof = load_profile()  # records the gate; read the arm back from it
    out: Dict = {
        "arm": gate_arms().get("host_profile", "fallback"),
        "path": load_config().profile_path or default_profile_path(),
        "host_fingerprint": fingerprint_key(),
    }
    if prof is not None:
        out["created_ts"] = prof.get("created_ts")
        out["fingerprint_key"] = prof.get("fingerprint_key")
    return out


def reset(fingerprint: bool = True) -> None:
    """Drop memoized state (tests; save_profile drops the load memo so
    a just-written profile is visible without an mtime race)."""
    global _fp_memo, _load_memo
    with _lock:
        if fingerprint:
            _fp_memo = None
        _load_memo = None
