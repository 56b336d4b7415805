"""Where compiled programs persist between processes of one checkout.

Entry points (``launch.tricluster``, ``launch.cluster_serve``'s mining
processes, ``benchmarks.run``, ``chip_smoke.py``) call
:func:`enable_compile_cache` before their first compile; importing the
library never does.
"""
from __future__ import annotations

import os
from pathlib import Path

#: The default cache: a fixed directory of the checkout (git-ignored),
#: so repeated runs of the same checkout find their programs again.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for this process and
    return its directory: ``JAX_COMPILATION_CACHE_DIR`` where it is set,
    else :data:`DEFAULT_DIR`."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
