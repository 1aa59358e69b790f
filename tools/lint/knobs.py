"""Knob discipline: the typed config is the ONLY door to ZKP2P_* env.

Rules (historical bugs they encode — docs/STATIC_ANALYSIS.md):

  knob-registry   every `ZKP2P_*` string referenced in zkp2p_tpu/,
                  tools/, bench.py, __graft_entry__.py, or read via
                  getenv() in csrc/ must be a registered knob in
                  utils/config.py KNOBS.  The invisible-ZKP2P_SLO_P95_S
                  bug: a knob consumed by the SLO tracker that no
                  config, doctor report, or manifest knew existed.

  env-read        raw READS of ZKP2P_* via os.environ.get /
                  os.environ[...] / os.getenv outside the sanctioned
                  fresh-read sites (utils/config.py — THE resolver;
                  utils/faults.py — the fault spec's documented
                  fresh-read; utils/jaxcfg.py — ZKP2P_NO_CACHE consumed
                  before the config package may import).  Writes are
                  the TRANSPORT (apply_env contract) and stay legal
                  everywhere.  A scattered read bypasses the
                  default->env resolution order and the
                  provenance record.
"""

from __future__ import annotations

import ast
import re
from typing import List

from .core import Finding, Tree, call_name, parse_config_registry, str_const

# non-knob ZKP2P_ tokens that legitimately appear in the tree
ALLOWED_EXTRA = {
    "ZKP2P_RUN_SLOW",   # test-tier gate, read only by the suite/Makefile
    "ZKP2P_RUN_XSLOW",  # ditto
    "ZKP2P_",           # prefix literals in scanners/docs
    "ZKP2P_HAVE_IFMA",  # C compile-time macro, not an env knob
    "ZKP2P_REPO",       # subprocess-test plumbing (abs repo path)
    "ZKP2P_ASAN_SO",    # sanitizer-test plumbing
    "ZKP2P_TSAN_SO",    # sanitizer-test plumbing
}

# files whose raw ZKP2P_* reads are the sanctioned fresh-read sites
SANCTIONED_READERS = {
    "zkp2p_tpu/utils/config.py",   # the resolver itself
    "zkp2p_tpu/utils/faults.py",   # ZKP2P_FAULTS fresh-read (docs/ROBUSTNESS.md)
    "zkp2p_tpu/utils/jaxcfg.py",   # ZKP2P_NO_CACHE before config may import
}

_TOKEN = re.compile(r"ZKP2P_[A-Z0-9_]*")
_GETENV_C = re.compile(r'getenv\(\s*"([A-Za-z0-9_]+)"\s*\)')


def check(tree: Tree) -> List[Finding]:
    knobs, _armable = parse_config_registry(tree)
    registered = set(knobs.values())
    findings: List[Finding] = []
    if not registered:
        findings.append(Finding(
            "knob-registry", "zkp2p_tpu/utils/config.py", 1,
            "could not parse the KNOBS registry — the linter's anchor is gone",
        ))
        return findings

    # ---- knob-registry: every ZKP2P_* token is a registered knob ----
    for sf in tree.py_files():
        if sf.relpath.endswith("utils/config.py"):
            continue  # the registry defines the names
        for i, line in enumerate(sf.lines, 1):
            for tok in _TOKEN.findall(line):
                if tok not in registered and tok not in ALLOWED_EXTRA:
                    findings.append(Finding(
                        "knob-registry", sf.relpath, i,
                        f"{tok} is not in the utils/config.py KNOBS registry "
                        "(unregistered knobs are invisible to doctor/manifest/provenance)",
                    ))
    for relpath, text in tree.c_files.items():
        for i, line in enumerate(text.splitlines(), 1):
            for m in _GETENV_C.finditer(line):
                var = m.group(1)
                if var.startswith("ZKP2P_") and var not in registered and var not in ALLOWED_EXTRA:
                    findings.append(Finding(
                        "knob-registry", relpath, i,
                        f"csrc getenv(\"{var}\") has no registered knob — the C runtime "
                        "would read config the typed registry cannot resolve or audit",
                    ))

    # ---- env-read: raw reads outside the sanctioned sites ----
    for sf in tree.py_files():
        if sf.relpath in SANCTIONED_READERS or sf.tree is None:
            continue
        for node in ast.walk(sf.tree):
            var = _read_zkp2p_var(node)
            if var is None:
                continue
            findings.append(Finding(
                "env-read", sf.relpath, node.lineno,
                f"raw os.environ read of {var} outside the sanctioned fresh-read "
                "sites — resolve through utils.config.load_config() so the "
                "resolution order and provenance apply",
            ))
    return findings


def _read_zkp2p_var(node) -> str:
    """The ZKP2P_* var a node READS, or None.  Covers os.environ.get(X),
    os.getenv(X), and os.environ[X] in Load context (subscript STORES
    are apply_env-style transport and stay legal)."""
    if isinstance(node, ast.Call):
        name = call_name(node)
        if name in ("os.environ.get", "os.getenv", "environ.get", "getenv") and node.args:
            s = str_const(node.args[0])
            if s and s.startswith("ZKP2P_"):
                return s
    elif isinstance(node, ast.Subscript) and isinstance(node.ctx, ast.Load):
        base = node.value
        if isinstance(base, ast.Attribute) and base.attr == "environ":
            s = str_const(node.slice)
            if s and s.startswith("ZKP2P_"):
                return s
    return None
