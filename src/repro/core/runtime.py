"""Distributed runtime facade (DESIGN.md §2, §6).

Every SPMD primitive the PPF library touches — ``shard_map``, mesh
construction, the collectives the DRAs are built from (``psum``,
``all_gather``, ``ppermute``, ``all_to_all``, ...), the simulated
host-device harness and the persistent compilation cache — goes through
this module, so nothing else in ``src/`` or ``tests/`` spells a
``jax.shard_map``-style path directly.  It targets the installed JAX
(``pyproject.toml`` pins the floor) and carries no branches for other
versions.
"""
from __future__ import annotations

import os
import pathlib
from typing import Any, Callable, Sequence

import jax
import numpy as np

Array = jax.Array

__all__ = [
    "shard_map", "make_mesh", "host_mesh",
    "psum", "pmean", "pmax", "pmin", "psum_scatter",
    "all_gather", "ppermute", "all_to_all", "axis_index", "axis_size",
    "butterfly_schedule", "grouped_ppermute", "tree_bytes",
    "simulate_host_devices", "host_device_env", "HOST_DEVICE_FLAG",
    "enable_compile_cache",
]


# ---------------------------------------------------------------------------
# shard_map (the one SPMD entry point)
# ---------------------------------------------------------------------------

def shard_map(f: Callable, mesh, *, in_specs, out_specs,
              check_replication: bool = False) -> Callable:
    """Map ``f`` as an SPMD program over ``mesh``.

    ``check_replication`` is ``jax.shard_map``'s ``check_vma``; the
    library always runs with it off because the DRAs splice per-shard
    buffers whose replication the checker cannot prove.
    """
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_replication)


# ---------------------------------------------------------------------------
# Collectives (per-shard programs only — need an enclosing shard_map)
# ---------------------------------------------------------------------------

psum = jax.lax.psum
pmean = jax.lax.pmean
pmax = jax.lax.pmax
pmin = jax.lax.pmin
psum_scatter = jax.lax.psum_scatter
all_gather = jax.lax.all_gather
ppermute = jax.lax.ppermute
all_to_all = jax.lax.all_to_all
axis_index = jax.lax.axis_index


# static python int inside a shard_map body (call sites use it in
# ``range()`` and shape arithmetic)
axis_size = jax.lax.axis_size


# ---------------------------------------------------------------------------
# Stage schedules for multi-stage collectives (DESIGN.md §14)
# ---------------------------------------------------------------------------

def butterfly_schedule(p: int) -> list[list[tuple[int, int]]]:
    """Distance-doubling pairwise partner schedule over ``p`` shards.

    Stage ``s`` pairs shard ``i`` with ``i XOR 2**s`` — the classic
    hypercube/butterfly topology (Heine–Whiteley–Cemgil,
    arXiv:1812.01502).  Each stage is a valid ``ppermute`` permutation
    (XOR with a constant is an involution, hence a bijection), every
    shard talks to exactly one partner per stage, and after all
    ``log2(p)`` stages every pair of shards is connected by exactly one
    path.  Returns a list of ``log2(p)`` permutations, each a list of
    ``(src, dst)`` pairs ready for :func:`ppermute`.
    """
    if p < 1 or (p & (p - 1)):
        raise ValueError(f"butterfly topology needs a power-of-two shard "
                         f"count, got {p}")
    return [[(i, i ^ (1 << s)) for i in range(p)]
            for s in range(p.bit_length() - 1)]


def grouped_ppermute(tree: Any, axis_name: str,
                     perm: Sequence[tuple[int, int]]) -> Any:
    """``ppermute`` every leaf of a pytree along one permutation.

    One collective launch per leaf; used by the butterfly DRA to ship
    its (state, count, log-weight) slab triples to the stage partner in
    a single logical exchange.
    """
    return jax.tree_util.tree_map(
        lambda x: ppermute(x, axis_name, perm), tree)


def tree_bytes(tree: Any) -> int:
    """Static payload size of a pytree in bytes (shapes are always
    static under SPMD tracing, so this is a plain Python int even for
    tracer leaves) — the unit of the comm-volume accounting
    (DESIGN.md §14.3)."""
    leaves = jax.tree_util.tree_leaves(tree)
    return int(sum(np.prod(x.shape) * jnp_dtype_size(x.dtype)
                   for x in leaves))


def jnp_dtype_size(dtype) -> int:
    """Itemsize of a JAX/NumPy dtype (PRNG key dtypes report their
    underlying data layout)."""
    try:
        return np.dtype(dtype).itemsize
    except TypeError:        # extended dtypes (e.g. PRNG keys)
        return int(dtype.itemsize)


# ---------------------------------------------------------------------------
# Mesh construction
# ---------------------------------------------------------------------------

def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str],
              *, devices: Sequence[Any] | None = None):
    """``jax.make_mesh`` with every axis in Auto sharding mode."""
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(jax.sharding.AxisType.Auto,)
                         * len(axis_names),
                         devices=devices)


def host_mesh(n: int | None = None, axis: str = "data"):
    """1-D mesh over the first ``n`` devices (default: all of them).

    Raises when fewer than ``n`` devices exist: a silently smaller mesh
    would change the experiment (a 1-device mesh even takes the
    single-device filter path, so the DRA would never run).
    """
    devs = jax.devices()
    n = n or len(devs)
    if len(devs) < n:
        raise RuntimeError(
            f"asked for a {n}-device mesh but only {len(devs)} "
            f"{devs[0].platform} device(s) exist")
    return jax.sharding.Mesh(np.array(devs[:n]), (axis,))


# ---------------------------------------------------------------------------
# Simulated multi-device CPU harness (DESIGN.md §6)
# ---------------------------------------------------------------------------

HOST_DEVICE_FLAG = "--xla_force_host_platform_device_count"


def _with_host_device_flag(flags: str, n: int) -> str:
    """Replace/append the host-device-count flag in an XLA_FLAGS string."""
    kept = [t for t in flags.split() if not t.startswith(HOST_DEVICE_FLAG)]
    return " ".join(kept + [f"{HOST_DEVICE_FLAG}={n}"])


def host_device_env(n: int, env: dict | None = None) -> dict:
    """Copy of ``env`` (default: os.environ) with XLA_FLAGS requesting
    ``n`` simulated host devices — for launching subprocess workers."""
    env = dict(os.environ if env is None else env)
    env["XLA_FLAGS"] = _with_host_device_flag(env.get("XLA_FLAGS", ""), n)
    return env


def simulate_host_devices(n: int) -> int:
    """Give this process ``n`` devices to build meshes on.

    On the CPU backend this exposes ``n`` simulated host devices, so it
    must run before JAX initialises its backend (importing ``jax`` is
    fine; creating an array or listing devices is not) and raises if the
    backend is already up with fewer.  On an accelerator the flag is
    inert and the real devices are used: the return value is the number
    visible, which may be fewer than ``n`` — ``host_mesh(n)`` raises in
    that case.
    """
    os.environ["XLA_FLAGS"] = host_device_env(n)["XLA_FLAGS"]
    got = jax.device_count()
    if jax.default_backend() == "cpu" and got < n:
        raise RuntimeError(
            f"asked for {n} simulated host devices but the JAX backend is "
            f"already initialised with {got}; call simulate_host_devices "
            f"before any device use, or launch with XLA_FLAGS set")
    return got


# ---------------------------------------------------------------------------
# Persistent compilation cache
# ---------------------------------------------------------------------------

# fixed, so its path (part of every cache key) is the same on every run
# from this checkout; listed in .gitignore
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str | None:
    """Turn on JAX's persistent compilation cache for an accelerator;
    returns its directory (``None`` on the CPU backend).

    ``JAX_COMPILATION_CACHE_DIR``, when set, is honoured as JAX reads it
    and no other directory is set here; otherwise the cache lives in
    ``.jax_cache/`` at the checkout root.  On the CPU nothing is set:
    XLA:CPU warns that a cached executable may not match the host's
    features (SIGILL), and CPU compiles are cheap.  Entry points call
    this after ``simulate_host_devices`` and before their first compile;
    importing the library never does.
    """
    if jax.default_backend() == "cpu":
        return None
    directory = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not directory:
        directory = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", directory)
    # cache every program, not only the slow-to-compile ones
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return directory
