"""Provenance stamps for bench/perf artifacts.

Every performance number this repo records must say *what measured
it*: host, backend, jax/jaxlib versions, git revision, and a hash of
the knobs that shaped the run — otherwise a "0.92x" from a CPU
fallback and a "0.92x" from the real chip are indistinguishable six
weeks later (the CKPT_r05 backend ambiguity). The helpers here are
the single source of those stamps, shared by ``bench.py``,
``tools/capture_perf.py``, ``tools/bench_stability.py``, and the
bench ledger (``tools/bench_ledger.py``).

Deliberately stdlib-only and jax-import-free: the bench *parent*
process never imports jax (it must neither hold the chip nor hang
with it), so
toolchain versions come from package metadata, not the live module.
"""

from __future__ import annotations

import hashlib
import json
import os
import platform
import socket
import subprocess
from typing import Dict, Iterable, Optional


def package_version(name: str) -> str:
    """Installed version of ``name`` without importing it."""
    try:
        from importlib.metadata import version

        return version(name)
    except Exception:  # noqa: BLE001 — absent package / broken dist
        return ""


def git_rev(repo: Optional[str] = None, short: bool = False) -> str:
    """HEAD revision of ``repo`` (default: this file's repo), "" when
    git is unavailable (stripped release trees)."""
    if repo is None:
        repo = os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        )
    cmd = ["git", "rev-parse", "HEAD"]
    if short:
        cmd.insert(2, "--short")
    try:
        out = subprocess.run(
            cmd, cwd=repo, capture_output=True, text=True, timeout=10
        )
        return out.stdout.strip() if out.returncode == 0 else ""
    except (OSError, subprocess.TimeoutExpired):
        return ""


def run_metadata(
    backend: Optional[str] = None, extra: Optional[dict] = None
) -> Dict[str, str]:
    """The stamp every bench/perf artifact carries. ``backend`` comes
    from whoever actually touched the device (the bench child's
    ``jax.default_backend()``); callers that never import jax pass
    None and get the env's declared platform instead."""
    meta = {
        "host": socket.gethostname(),
        "platform": platform.platform(),
        "python": platform.python_version(),
        "jax": package_version("jax"),
        "jaxlib": package_version("jaxlib"),
        "backend": (
            backend
            or os.getenv("JAX_PLATFORMS", "")
            or "undeclared"
        ),
    }
    if extra:
        meta.update({k: str(v) for k, v in extra.items()})
    return meta


def trial_fingerprint(parts: Dict) -> str:
    """Stable short hash identifying an autotune *trial context*: the
    things that, when any of them changes, invalidate a cached tuning
    result — model shape dims, mesh/device extent, kernel/op id,
    dtype, backend, and toolchain versions. Callers pass them as a
    flat JSON-serializable dict; key order never matters. This is the
    key of ``accelerate/tune_cache.py``'s trial store, kept here so
    jax-free tooling (the bench parent, ``tools/capture_perf.py``)
    can compute/compare keys without touching the accelerate package.
    """
    digest = hashlib.sha256(
        json.dumps(parts, sort_keys=True, default=str).encode()
    ).hexdigest()
    return digest[:16]


# BENCH_* variables that are bookkeeping, not measurement knobs: they
# must not perturb the config fingerprint (a capture_perf-driven run
# and an identically-knobbed manual run measured the same config).
# BENCH_IGNORE_TUNED stays IN the hash — it gates whether the pin
# file applies, which does change what was measured.
_NON_KNOB_ENV = frozenset(("BENCH_LEDGER_STAGE", "BENCH_NO_LEDGER"))


def config_fingerprint(
    env: Optional[dict] = None,
    prefixes: Iterable[str] = ("BENCH_",),
    extra_files: Iterable[str] = ("bench_tuned.json",),
    repo: Optional[str] = None,
) -> str:
    """Short stable hash of everything that shapes a bench run: the
    ``BENCH_*`` env knobs plus the autotune pin file's content. Two
    records with equal fingerprints measured the same configuration,
    so the ledger's compare gate diffs like against like."""
    if env is None:
        env = dict(os.environ)
    if repo is None:
        repo = os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        )
    knobs = {
        k: v
        for k, v in env.items()
        if any(k.startswith(p) for p in prefixes)
        and k not in _NON_KNOB_ENV
    }
    payload = {"env": knobs, "files": {}}
    for fname in extra_files:
        path = os.path.join(repo, fname)
        try:
            with open(path) as f:
                payload["files"][fname] = f.read()
        except OSError:
            pass
    digest = hashlib.sha256(
        json.dumps(payload, sort_keys=True).encode()
    ).hexdigest()
    return digest[:12]
