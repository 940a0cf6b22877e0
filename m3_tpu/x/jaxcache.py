"""The one rule for where compiled programs are kept.

If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses it and this
module sets nothing.  If it is not, the cache lives at a FIXED path
inside the checkout (``<repo>/.jax_cache``, git-ignored): the path is
part of the cache key, so a directory that moves (a temp name, a pid,
a time) never hits.  Called by every process entry point that compiles
for a device — ``server/node_main``, ``chip_smoke.py``, the tools.
"""

from __future__ import annotations

import os
from pathlib import Path

_REPO = Path(__file__).resolve().parent.parent.parent
DEFAULT_DIR = _REPO / ".jax_cache"


def configure() -> str:
    """Apply the rule; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    DEFAULT_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
