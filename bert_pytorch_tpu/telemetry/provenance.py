"""Run provenance stamps.

A logfile found three rounds later is only evidence if it
says WHAT produced it: which commit, which jax, which mesh, which libtpu
flag pack. `collect()` gathers exactly that, tolerating every failure mode
(no git, no backend up yet) by degrading fields to "unknown" rather than
raising — a provenance stamp must never be the thing that kills a run.
"""

from __future__ import annotations

import os
import subprocess
import time
from typing import Any, Dict, Optional


def git_sha(cwd: Optional[str] = None) -> str:
    """Short SHA (+'-dirty' when the tree is modified) of the repo holding
    this file; 'unknown' when git is unavailable."""
    if cwd is None:
        cwd = os.path.dirname(os.path.abspath(__file__))
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], cwd=cwd,
            capture_output=True, text=True, timeout=10).stdout.strip()
        if not sha:
            return "unknown"
        dirty = subprocess.run(
            ["git", "status", "--porcelain", "--untracked-files=no"],
            cwd=cwd, capture_output=True, text=True, timeout=10).stdout
        return sha + ("-dirty" if dirty.strip() else "")
    except Exception:
        return "unknown"


def collect(mesh=None,
            extra: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """One provenance dict for log headers. Touches the jax backend (the
    device fields): for the process that holds the device."""
    import jax
    import jaxlib

    from bert_pytorch_tpu.parallel.xla_flags import pack_state

    out: Dict[str, Any] = {
        "git_sha": git_sha(),
        "jax_version": jax.__version__,
        "jaxlib_version": jaxlib.__version__,
        "time_unix": round(time.time(), 3),
        **pack_state(),
    }
    try:
        d = jax.devices()[0]
        out["platform"] = d.platform
        out["device_kind"] = d.device_kind
        out["device_count"] = jax.device_count()
        out["process_count"] = jax.process_count()
    except Exception:
        out["platform"] = "unknown"
    if mesh is not None:
        out["mesh"] = {k: int(v) for k, v in dict(mesh.shape).items()}
    if extra:
        out.update(extra)
    return out
