"""Resolve the repo's git SHA for benchmark record attribution.

Benchmark JSON records are only comparable across time when each one says
which commit produced it; ``git_sha()`` is best-effort (returns ``None``
outside a work tree or without git on PATH) so benchmarks never fail on
account of provenance.
"""

from __future__ import annotations

import functools
import os
import subprocess
from pathlib import Path

_REPO_ROOT = Path(__file__).resolve().parent.parent.parent


@functools.lru_cache(maxsize=1)
def git_sha(short: bool = False) -> str | None:
    """Current HEAD commit (``None`` when unresolvable). Cached per process."""
    cmd = ["git", "rev-parse", "--short" if short else "--verify", "HEAD"]
    try:
        out = subprocess.run(
            cmd,
            cwd=_REPO_ROOT,
            capture_output=True,
            text=True,
            timeout=5,
            check=False,
            # never look above the checkout: a copy that is not a git
            # repository has no sha, whatever it is nested in
            env={**os.environ,
                 "GIT_CEILING_DIRECTORIES": str(_REPO_ROOT.parent)},
        )
    except (OSError, subprocess.TimeoutExpired):
        return None
    sha = out.stdout.strip()
    return sha if out.returncode == 0 and sha else None
