"""Production mesh construction.

A function, not a module-level constant, so importing this module never
touches jax device state (the dry-run must set its host-platform flags
before anything initializes jax).

Mesh shapes (TPU v5e):
  single-pod: (16, 16)      axes ("data", "model")   = 256 chips
  multi-pod:  (2, 16, 16)   axes ("pod", "data", "model") = 512 chips

``data`` is the FSDP/DP axis (fast intra-pod ICI), ``model`` the TP/EP
axis, ``pod`` the slow cross-pod axis carrying only batch DP + the per-step
gradient reduction (optionally int8-compressed, train/compression.py).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: the partitioner places what the
    logical-axis constraints (``sharding.constrain``) leave open.  JAX's
    default is Explicit axes, which refuse those constraints."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_replica_mesh(n: int | None = None):
    """1-D ``("replica",)`` mesh over (up to) ``n`` host devices.

    The detection fleet's mesh: each replica of the sharded
    :class:`~repro.serve.fleet.ShardedDetectionService` pins its plans
    and dispatches to one device along this axis.  Testable on a CPU
    host via ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
    (set before jax initializes — the device count is frozen at first
    use, which is why the mesh tests run it in subprocesses).
    """
    devs = jax.devices()
    n = min(n or len(devs), len(devs))
    return _auto_mesh((n,), ("replica",))


def replica_devices(n: int) -> list:
    """``n`` device handles for ``n`` service replicas, cycling over the
    host's real devices when there are fewer — on a 1-device host every
    replica shares device 0 (the policy layer still shards queues,
    trackers, and plan caches; only the physical placement collapses)."""
    devs = jax.devices()
    return [devs[i % len(devs)] for i in range(n)]


def make_host_mesh(*, multi_pod: bool = False, n: int | None = None):
    """Small mesh over however many (host) devices exist — tests/examples.

    Single-pod: (d, m); multi-pod: (2, d, m) when >= 8 devices.
    """
    n = n or len(jax.devices())
    if multi_pod:
        assert n >= 8 and n % 2 == 0, n
        rest = n // 2
        d = max(s for s in range(1, rest + 1) if rest % s == 0 and s <= rest)
        # squarest (d, m) factorization of rest
        d = max(
            s for s in range(1, int(rest ** 0.5) + 1) if rest % s == 0
        )
        return _auto_mesh((2, rest // d, d), ("pod", "data", "model"))
    d = max(s for s in range(1, int(n ** 0.5) + 1) if n % s == 0)
    return _auto_mesh((n // d, d), ("data", "model"))
