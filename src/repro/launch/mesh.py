"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never
touches jax device state (the dry-run sets XLA_FLAGS before any jax
initialization; tests and benches see 1 CPU device).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod.

    REPRO_MESH="d,m" (env) overrides the per-pod shape for fast in-CI
    smoke runs of the dry-run machinery on few host devices.
    """
    import os
    override = os.environ.get("REPRO_MESH")
    if override:
        d, m = (int(x) for x in override.split(","))
        shape = (2, d, m) if multi_pod else (d, m)
    else:
        shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_host_mesh():
    """Single-host mesh for CPU smoke runs: all local devices on 'data'."""
    n = len(jax.devices())
    return jax.make_mesh((n, 1), ("data", "model"), axis_types=_auto(2))


def _auto(n: int):
    """Auto axis types: sharding is propagated by the compiler (jax's
    ``make_mesh`` defaults to Explicit axes, which the FedSGD dry-run
    and the sharded round scan are not written for)."""
    return (jax.sharding.AxisType.Auto,) * n
