"""The local mesh the engines and drivers run on.

``make_local_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state.
"""
from __future__ import annotations

import jax

from .._compat import make_mesh  # noqa: F401  (re-export; single shim home)


def make_local_mesh():
    """Mesh over whatever devices exist (tests, examples, local runs):
    (data=n, model=1)."""
    return make_mesh((len(jax.devices()), 1), ("data", "model"))
