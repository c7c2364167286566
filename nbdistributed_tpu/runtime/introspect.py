"""Worker-side namespace introspection and device status probes.

JAX-native rebuild of the reference's ``_get_namespace_info``
(reference: worker.py:426-485) and ``_get_status``
(reference: worker.py:509-567): arrays are described by shape/dtype/
sharding, devices by their platform/kind, and memory numbers come from
``Device.memory_stats()`` instead of ``torch.cuda`` counters.
"""

from __future__ import annotations

import inspect
import types
from typing import Any


def describe_namespace(namespace: dict) -> dict[str, dict]:
    """Build type descriptors for every non-underscore name — the payload
    that powers coordinator-side IDE proxies (reference: worker.py:426-485,
    consumed at magic.py:1131-1314)."""
    import jax
    import numpy as np

    info: dict[str, dict] = {}
    for name, value in list(namespace.items()):
        if name.startswith("_"):
            continue
        try:
            info[name] = _describe_value(value, jax, np)
        except Exception:
            info[name] = {"kind": "object", "type": type(value).__name__,
                          "repr": "<unreprable>"}
    return info


def _describe_value(value: Any, jax, np) -> dict:
    if isinstance(value, jax.Array):
        return {
            "kind": "array",
            "shape": list(value.shape),
            "dtype": str(value.dtype),
            "sharding": _sharding_str(value),
            "device": _device_str(value),
        }
    if isinstance(value, np.ndarray):
        return {"kind": "array", "shape": list(value.shape),
                "dtype": str(value.dtype), "sharding": None,
                "device": "host"}
    if isinstance(value, jax.sharding.Mesh):
        return {"kind": "mesh", "axes": dict(value.shape),
                "devices": int(np.prod(list(value.shape.values()) or [1]))}
    if isinstance(value, jax.sharding.PartitionSpec):
        return {"kind": "pspec", "repr": repr(value)}
    if isinstance(value, types.ModuleType):
        return {"kind": "module", "name": value.__name__,
                "file": getattr(value, "__file__", None)}
    if isinstance(value, type):
        return {"kind": "class", "name": value.__name__,
                "module": value.__module__}
    if callable(value):
        try:
            sig = str(inspect.signature(value))
        except (ValueError, TypeError):
            sig = "(...)"
        doc = inspect.getdoc(value)
        return {"kind": "callable", "signature": sig,
                "doc": (doc or "")[:200],
                "name": getattr(value, "__name__", "<callable>")}
    if isinstance(value, (bool, int, float, str, bytes)):
        return {"kind": "scalar", "type": type(value).__name__,
                "repr": repr(value)[:200]}
    if isinstance(value, (list, tuple, dict, set)):
        return {"kind": "container", "type": type(value).__name__,
                "len": len(value)}
    return {"kind": "object", "type": type(value).__name__,
            "repr": repr(value)[:200]}  # reference truncates at 200 too


def _sharding_str(arr) -> str | None:
    try:
        return str(arr.sharding.spec) if hasattr(arr.sharding, "spec") \
            else type(arr.sharding).__name__
    except Exception:
        return None


def _device_str(arr) -> str:
    try:
        devs = list(arr.devices())
        if len(devs) == 1:
            return str(devs[0])
        return f"{len(devs)} devices"
    except Exception:
        return "unknown"


def device_status(rank: int, world_size: int) -> dict:
    """Per-worker status snapshot: devices, memory, backend
    (reference: worker.py:509-567, with ``memory_stats()`` supplying what
    ``torch.cuda.memory_allocated`` did).  Memory numbers come from the
    same probe the heartbeat telemetry pushes
    (:func:`~nbdistributed_tpu.observability.telemetry.device_memory`),
    so the pull and push views cannot drift."""
    import jax

    from ..observability.telemetry import device_memory

    devices = []
    for d in jax.local_devices():
        entry: dict[str, Any] = {
            "id": d.id,
            "platform": d.platform,
            "kind": d.device_kind,
        }
        mem = device_memory(d)
        entry["memory_gb"] = None if mem is None else {
            key: (round(mem[key] / 1e9, 3) if mem[key] is not None
                  else None)
            for key in ("in_use", "limit", "peak")
        }
        devices.append(entry)

    return {
        "rank": rank,
        "world_size": world_size,
        "backend": jax.default_backend(),
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "local_device_count": jax.local_device_count(),
        "global_device_count": jax.device_count(),
        "devices": devices,
    }
