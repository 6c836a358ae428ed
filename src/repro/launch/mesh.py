"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — required for the dry-run's
xla_force_host_platform_device_count dance.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes, devices=None):
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) = 256 chips, axes (data, model).
    Multi-pod:  (2, 16, 16) = 512 chips, axes (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devs = jax.devices()[:n]        # single-pod uses the first 256 of 512
    return _make_mesh(shape, axes, devices=devs)


def make_bcpnn_mesh(n_devices: int | None = None, *, multi_pod: bool = False):
    """BCPNN shards whole HCUs (embarrassingly parallel, paper §II.B): a flat
    'hcu' axis over every chip; multi-pod adds an explicit 'pod' axis so the
    spike all_to_all hierarchy (intra/inter pod) is visible to the compiler."""
    n = n_devices or len(jax.devices())
    devs = jax.devices()[:n]
    if multi_pod:
        return _make_mesh((2, n // 2), ("pod", "hcu"), devices=devs)
    return _make_mesh((n,), ("hcu",), devices=devs)


def elastic_device_count(n_hcu: int, n_available: int) -> int:
    """Degraded-mode mesh size: the largest device count <= the survivors
    that divides the hypercolumn count (`make_dist_run` shards whole HCUs,
    h_local = H // ndev — H % ndev must be 0). Always >= 1: a single
    survivor can host the entire network."""
    n = max(min(int(n_available), int(n_hcu)), 1)
    while n_hcu % n:
        n -= 1
    return n


def make_elastic_mesh(n_hcu: int, devices=None, axis: str = "hcu"):
    """1-D HCU mesh over (a whole-HCU-divisible prefix of) the surviving
    devices — the mesh `ElasticRunner` re-lowers onto after a device loss."""
    devs = list(devices) if devices is not None else jax.devices()
    n = elastic_device_count(n_hcu, len(devs))
    return _make_mesh((n,), (axis,), devices=devs[:n])


def force_host_device_count_flags(n: int, base: str | None = None) -> str:
    """XLA_FLAGS value forcing `n` host-platform (CPU) devices.

    Must be in the environment BEFORE jax initializes, so this is for
    building a CHILD process env (the weak-scaling sweep, the multi-device
    tests), never for mutating the current process. `base` defaults to the
    caller's current XLA_FLAGS so benchmark pins (e.g. the legacy CPU
    runtime, `benchmarks.run.pin_legacy_cpu_runtime`) survive into the
    child; any existing forced-count flag is replaced."""
    import os
    if base is None:
        base = os.environ.get("XLA_FLAGS", "")
    flags = [f for f in base.split()
             if not f.startswith("--xla_force_host_platform_device_count")]
    flags.append(f"--xla_force_host_platform_device_count={int(n)}")
    return " ".join(flags)


def make_host_mesh(shape=None, axes=("data", "model")):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    if shape is None:
        shape = (n, 1)
    return _make_mesh(shape, axes)
