"""Production meshes + the host-platform device bootstrap.

Mesh builders are FUNCTIONS and ``jax`` is imported inside them so
importing this module never touches jax device state — the dry-run (and
every CLI entry point taking ``--devices``) must set ``XLA_FLAGS``
before any device query.
"""
from __future__ import annotations

import os
import warnings


def ensure_host_devices(n: int, *, allow_oversubscribe: bool = True) -> int:
    """Ask XLA for ``n`` host-platform (virtual CPU) devices.

    Must run before jax initializes its backends: appends
    ``--xla_force_host_platform_device_count=n`` to ``XLA_FLAGS`` (the
    CLI entry points call this from ``--devices N`` before importing
    anything heavy).  When ``n`` exceeds the physical core count we warn
    — forced host devices are threads, so an oversubscribed mesh is
    correct but slower than its device count suggests.  Step-based
    metrics stay exact; wall metrics do not.  Pass
    ``allow_oversubscribe=False`` to clamp to the core count instead
    (production posture; the dev/CI posture keeps the requested count so
    a 1-core runner can still exercise a 4-device GSPMD partition).

    Returns the device count actually requested.
    """
    if n < 1:
        raise ValueError(f"ensure_host_devices needs n >= 1, got {n}")
    cores = os.cpu_count() or 1
    if n > cores:
        if allow_oversubscribe:
            warnings.warn(
                f"forcing {n} host devices on {cores} core(s): the mesh "
                "oversubscribes the host — partitioning is real, wall "
                "speedups are not", stacklevel=2)
        else:
            warnings.warn(
                f"clamping forced host devices {n} -> {cores} (host core "
                "count)", stacklevel=2)
            n = cores
    flag = f"--xla_force_host_platform_device_count={n}"
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" in flags:
        # an explicit earlier choice (e.g. tests/conftest.py) wins unless
        # it is too small for the requested mesh
        import re
        m = re.search(r"--xla_force_host_platform_device_count=(\d+)", flags)
        if m and int(m.group(1)) >= n:
            return int(m.group(1))
        flags = re.sub(r"--xla_force_host_platform_device_count=\d+", flag,
                       flags)
        os.environ["XLA_FLAGS"] = flags
        return n
    os.environ["XLA_FLAGS"] = (flags + " " + flag).strip()
    return n


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    import jax
    from jax.sharding import AxisType
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes))


def make_dev_mesh():
    """Whatever this process actually has (CPU smoke / examples)."""
    import jax
    from jax.sharding import AxisType
    n = len(jax.devices())
    return jax.make_mesh((n, 1), ("data", "model"), (AxisType.Auto,) * 2)


def mesh_device_count(mesh) -> int:
    n = 1
    for v in mesh.shape.values():
        n *= v
    return n
