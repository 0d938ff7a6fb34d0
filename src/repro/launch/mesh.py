"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (never a module-level constant) so
importing this module does not touch jax device state; the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
import and then calls it.

Axes:
  single-pod : (data=16, model=16)                = 256 chips (one v5e pod)
  multi-pod  : (pod=2, data=16, model=16)         = 512 chips

The ``pod`` axis is the slow (DCN/inter-pod ICI) dimension: gradient sync is
hierarchical - reduce-scatter on ``data`` inside a pod, all-reduce of the
small shards across ``pod``, all-gather back on ``data``.

Every axis is ``AxisType.Auto``: ``ShardingRules`` places arrays with
``NamedSharding`` and sharding constraints and leaves propagation to GSPMD.
``jax.make_mesh`` otherwise makes explicit axes, under which ops such as the
embedding gather must be told their output sharding.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes, devices=None):
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model: int = 1, devices=None):
    """(data, model) mesh over ``devices`` (default: every local device)."""
    devices = jax.devices() if devices is None else list(devices)
    return _auto_mesh((len(devices) // model, model), ("data", "model"),
                      devices)
