"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module never
touches jax device state (required so smoke tests see 1 device while the
dry-run sees 512 placeholders).
"""

from __future__ import annotations

from repro.distributed import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = v5e-256.
    Multi-pod:  (pod=2, data=16, model=16) = 512 chips across 2 pods;
    the ``pod`` axis carries only DP gradient all-reduce (or pipeline
    stages via launch/train.py --pp pods) — the right fit for DCI links.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
