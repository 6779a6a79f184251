"""Production mesh builders.

Functions, not module-level constants, so importing never touches jax
device state (the dry-run must set XLA_FLAGS before first jax init).
Construction goes through ``repro.core.runtime``.
"""
from __future__ import annotations

from repro.core import runtime


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return runtime.make_mesh(shape, axes)


def make_host_mesh(n: int | None = None, axis: str = "data"):
    """1-D mesh over the first ``n`` devices (default: all), raising when
    fewer exist — used by the PF drivers, benchmarks and tests."""
    return runtime.host_mesh(n, axis)
