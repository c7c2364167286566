"""Shared small utilities used across the model/parallel stack.

Deliberately lazy: ``utils.knobs`` (the env-knob registry) is imported
by stdlib-only modules (resilience/, observability/) that must not pay
a JAX import, so nothing heavy may execute at package-import time —
``fan_in_normal`` resolves jax inside the call, and the ``data``
re-exports resolve through module ``__getattr__`` (PEP 562).
"""

from __future__ import annotations

_DATA_EXPORTS = ("batch_iterator", "interleave_shards",
                 "prefetch_to_device", "rank_slice", "shard_arrays")

__all__ = ["PLATFORMS", "fan_in_normal", *_DATA_EXPORTS]

# --backend -> JAX platform list: what the spawner writes into a
# worker's environment (manager/topology.py) and what the worker pins
# ``jax_platforms`` to from its own flag (runtime/worker.py).  A TPU
# worker keeps the host CPU backend as its second platform (host-side
# staging with ``jax.devices("cpu")``); the first entry is the default
# backend, and every listed platform must initialise or JAX raises.
PLATFORMS = {"cpu": "cpu", "tpu": "tpu,cpu"}


def fan_in_normal(key, shape, fan_in, dtype):
    """Gaussian init scaled by 1/sqrt(fan_in), cast to ``dtype`` —
    the one initializer every model family uses."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    return (jax.random.normal(key, shape, jnp.float32)
            / np.sqrt(fan_in)).astype(dtype)


def __getattr__(name: str):
    if name in _DATA_EXPORTS:
        from . import data
        return getattr(data, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
