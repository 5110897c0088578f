"""Production meshes.  Functions, not module constants — importing this
module never touches jax device state (required by the dry-run, which
must set XLA_FLAGS before any jax initialization)."""
from __future__ import annotations

import os
import re

import jax

_FORCE_FLAG = "--xla_force_host_platform_device_count"


def force_host_device_count(n: int, env: str | None = None) -> str:
    """Request ``n`` forced host (CPU) devices by editing ``XLA_FLAGS``.

    The one sanctioned way to set up a multi-device CPU run (dry-run,
    sharded-plane tests/benchmarks, CI smoke jobs).  Unlike the old
    dry-run prologue this *merges*: any other flags the user already has
    in ``XLA_FLAGS`` survive, and an existing device-count flag is
    replaced rather than duplicated.  When ``env`` names an environment
    variable and it is set, its value replaces ``XLA_FLAGS`` wholesale
    (the dry-run's ``DRYRUN_XLA_FLAGS`` escape hatch keeps its original
    full-override semantics).

    Must run before jax initializes its backend — jax locks the device
    count at first device query, not at ``import jax``.  Returns the
    final ``XLA_FLAGS`` value.
    """
    if env is not None and os.environ.get(env):
        os.environ["XLA_FLAGS"] = os.environ[env]
        return os.environ["XLA_FLAGS"]
    flags = os.environ.get("XLA_FLAGS", "")
    flags = re.sub(rf"{_FORCE_FLAG}=\d+\s*", "", flags).strip()
    os.environ["XLA_FLAGS"] = (f"{flags} {_FORCE_FLAG}={int(n)}".strip())
    return os.environ["XLA_FLAGS"]


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache at a fixed path.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    this sets nothing.  Otherwise the cache goes to ``.jax_cache`` at the
    repository root: the path is part of the cache key, so it must not
    move between runs.  Call it from a script's ``main`` only — never at
    import, and never from tests.  Returns the directory in use.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    root = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                        *[os.pardir] * 3))
    path = os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def streaming_mesh(devices: int | None = None):
    """1-D ``("machines",)`` mesh for the sharded streaming data plane.

    Uses the first ``devices`` local devices (all of them by default).
    Built directly over ``jax.devices()`` so a CPU run under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` sees N
    shards; see :func:`force_host_device_count`.
    """
    import numpy as np
    devs = jax.devices()
    if devices is not None:
        if devices > len(devs):
            raise ValueError(
                f"streaming_mesh: {devices} devices requested but only "
                f"{len(devs)} visible; set XLA_FLAGS via "
                f"force_host_device_count() before jax initializes")
        devs = devs[:devices]
    return jax.sharding.Mesh(np.asarray(devs), ("machines",))


def _mesh(shape, axes):
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16×16 = 256 chips ("data", "model").
    Multi-pod: 2×16×16 = 512 chips ("pod", "data", "model")."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (tests use small host-device meshes)."""
    return _mesh(shape, axes)


def data_parallel_size(mesh) -> int:
    size = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names:
            size *= mesh.shape[a]
    return size
