"""Eager, notebook-friendly collectives over the global JAX world.

The reference's core capability is seeding ``torch.distributed`` into the
interactive namespace so users call ``dist.all_reduce(t)`` cell by cell
(reference: worker.py:160-177, README.md:97-125).  The TPU-native
equivalent is this module, seeded as ``dist`` (plus its functions
directly): each primitive is an XLA program over the mesh of **all**
global devices, compiled via ``shard_map`` so collectives ride ICI/DCN —
no NCCL/Gloo anywhere (data-plane replacement mapped out in SURVEY §2.3,
§5.8).

Semantics follow torch.distributed where they overlap: every process
passes a host-local value of identical shape; the result is the reduced /
gathered value as seen by this process.  All functions also work in a
single-process world (they become cheap identities), so the same notebook
runs on 1 chip or a pod.

These collectives are **eager**: in a multi-device world they cannot be
traced into ``jit``/``grad`` (they move host-local values into a global
XLA program) and raise a TypeError explaining the two supported
patterns — all-reduce eagerly between jitted halves, or ``shard_map`` +
``jax.lax.psum`` for in-program collectives.  The single-process/
single-device identity path still traces fine, so 1-chip notebooks can
jit straight through them.
"""

from __future__ import annotations

import functools
import time
from typing import Any

import numpy as np

from ..observability import metrics as _obs_metrics
from ..observability.spans import maybe_span as _maybe_span
from ..runtime.collective_guard import check as _guard_check
from ..runtime.collective_guard import done as _guard_done


def _jax():
    import jax
    return jax


def _instrumented(name: str):
    """Observability wrapper for an eager collective: a
    ``collective/<op>`` span while a trace is active (one flag check
    when not) and an always-on duration histogram in the process
    metrics registry.  Metrics are resolved once, at decoration time —
    the per-call cost is one ``observe``.  Composed ops (broadcast →
    all_reduce) record both levels, mirroring their span nesting."""
    reg = _obs_metrics.registry()
    hist = reg.histogram("nbd_collective_seconds",
                         "eager collective duration", {"op": name})
    calls = reg.counter("nbd_collectives_total",
                        "eager collective calls", {"op": name})

    def deco(fn):
        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            t0 = time.perf_counter()
            try:
                with _maybe_span(f"collective/{name}", kind="collective"):
                    out = fn(*args, **kwargs)
            finally:
                # Mark the guard's progress stream not-in-flight even
                # when the op raised (hazard error, interrupt) — the
                # watchdog must not keep seeing a long-dead entry as
                # "still inside".  Nested composite internals are
                # suppressed by the guard itself.
                _guard_done(name)
            calls.inc()
            hist.observe(time.perf_counter() - t0)
            return out
        return wrapped
    return deco


@functools.lru_cache(maxsize=None)
def _proc_mesh():
    """1-D mesh over every global device, axis name ``proc``.

    The eager collectives undo per-process duplication by dividing by /
    striding over ``local_device_count``, which is only right when every
    process owns the same number of devices (one per worker in the
    default one-worker-per-chip layout)."""
    jax = _jax()
    from jax.sharding import Mesh
    devices = jax.devices()
    if len(devices) != jax.process_count() * jax.local_device_count():
        raise RuntimeError(
            f"eager collectives need the same device count on every "
            f"process; this world has {len(devices)} devices over "
            f"{jax.process_count()} process(es) but this process owns "
            f"{jax.local_device_count()}")
    return Mesh(np.asarray(devices), ("proc",))


def world_size() -> int:
    return _jax().process_count()


def rank() -> int:
    return _jax().process_index()


def device_world() -> int:
    return _jax().device_count()


def _to_global(x, mesh):
    """Stack per-process values on a leading ``proc`` axis as a global
    array (one shard per device)."""
    jax = _jax()
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from jax.experimental import multihost_utils

    x = jnp.asarray(x)
    local = jnp.broadcast_to(x[None], (jax.local_device_count(),) + x.shape)
    return multihost_utils.host_local_array_to_global_array(
        np.asarray(local), mesh, P("proc"))


def _reject_tracer(x, what: str):
    """Eager collectives move host-local values into a global array,
    which cannot happen mid-trace.  Without this guard the user sees
    XLA's opaque ``__array__() was called on traced array`` — turn it
    into an actionable error instead."""
    import jax.core

    if isinstance(x, jax.core.Tracer):
        raise TypeError(
            f"{what} is an eager collective and cannot be called inside "
            "jit/grad/vmap tracing. Either call it outside the jitted "
            "function (e.g. jit the local grad step, all-reduce the "
            "grads eagerly, then jit the optimizer update), or express "
            "the collective inside the program with jax.shard_map + "
            "jax.lax.psum over a mesh axis.")


_REDUCERS = {"sum": "psum", "mean": "pmean", "max": "pmax", "min": "pmin"}


@functools.lru_cache(maxsize=None)
def _reduce_fn(mesh, prim_name: str):
    """Jitted device-mesh reduction, cached per (mesh, op) so repeated
    eager calls hit the jit cache instead of retracing."""
    jax = _jax()
    from jax.sharding import PartitionSpec as P

    prim = getattr(jax.lax, prim_name)

    @jax.jit
    @functools.partial(jax.shard_map, mesh=mesh, in_specs=P("proc"),
                       out_specs=P())
    def f(a):
        # Each device holds one copy on the leading axis; drop it, then
        # reduce across the mesh axis.  XLA lowers this to an ICI/DCN
        # all-reduce.
        return prim(a[0], "proc")

    return f


@functools.lru_cache(maxsize=None)
def _gather_fn(mesh):
    jax = _jax()
    from jax.sharding import PartitionSpec as P

    # check_vma off: all_gather's output is replicated over "proc" but the
    # static varying-axes analysis cannot prove it.
    @jax.jit
    @functools.partial(jax.shard_map, mesh=mesh, in_specs=P("proc"),
                       out_specs=P(), check_vma=False)
    def f(a):
        return jax.lax.all_gather(a[0], "proc")

    return f


@_instrumented("all_reduce")
def all_reduce(x, op: str = "sum"):
    """Elementwise reduce across all ranks; every rank gets the result
    (torch ``dist.all_reduce`` analog, but functional).

    Rank semantics hold for any local device count: the underlying XLA
    all-reduce runs over every device, and the per-process duplicate
    copies are compensated (sum is rescaled; mean/max/min are invariant
    under duplication).  With one process the call is an identity.
    """
    _guard_check("all_reduce")
    jax = _jax()
    import jax.numpy as jnp

    if op not in _REDUCERS:
        raise ValueError(f"op must be one of {sorted(_REDUCERS)}")
    if jax.process_count() == 1 and jax.local_device_count() == 1:
        return jnp.asarray(x)  # identity — works even under tracing
    _reject_tracer(x, "all_reduce")

    mesh = _proc_mesh()
    garr = _to_global(x, mesh)
    out = _reduce_fn(mesh, _REDUCERS[op])(garr).addressable_data(0)
    local = jax.local_device_count()
    if op == "sum" and local > 1:
        # Each process contributed `local` copies; undo the inflation.
        if jnp.issubdtype(out.dtype, jnp.integer):
            out = out // local
        else:
            out = out / local
    return out


@_instrumented("all_gather")
def all_gather(x):
    """Gather per-rank values; returns a stacked array with leading
    dimension = number of ranks (``dist.all_gather`` analog).
    Lowered to an XLA all-gather over ICI/DCN; per-process duplicate
    rows (when a worker owns several devices) are sliced away."""
    _guard_check("all_gather")
    jax = _jax()
    import jax.numpy as jnp

    if jax.process_count() == 1 and jax.local_device_count() == 1:
        return jnp.asarray(x)[None]
    _reject_tracer(x, "all_gather")

    mesh = _proc_mesh()
    garr = _to_global(x, mesh)
    out = _gather_fn(mesh)(garr).addressable_data(0)
    local = jax.local_device_count()
    if local > 1:
        # Device order in the mesh groups local devices per process, so
        # one row per process is every `local`-th entry.
        out = out[::local]
    return out


@_instrumented("broadcast")
def broadcast(x, root: int = 0):
    """Every process returns root's value (``dist.broadcast`` analog).
    Implemented as mask-and-sum so any root works, not just process 0
    (``multihost_utils.broadcast_one_to_all`` only supports root 0)."""
    _guard_check("broadcast")
    _check_root(root, "broadcast")
    jax = _jax()
    import jax.numpy as jnp

    if jax.process_count() == 1:
        return jnp.asarray(x)  # identity — works even under tracing
    _reject_tracer(x, "broadcast")
    x = jnp.asarray(x)
    contribution = x if rank() == root else jnp.zeros_like(x)
    return all_reduce(contribution, op="sum")


@_instrumented("barrier")
def barrier(name: str = "nbd_barrier"):
    """Block until every process arrives (``dist.barrier`` analog;
    reference uses it for %sync at worker.py:213-215)."""
    _guard_check("barrier")
    jax = _jax()
    if jax.process_count() == 1:
        return
    from jax.experimental import multihost_utils
    multihost_utils.sync_global_devices(name)


@functools.lru_cache(maxsize=None)
def _reduce_scatter_fn(mesh):
    """True reduce-scatter (psum_scatter): each device receives its
    reduced chunk — half the wire traffic of all-reduce + local slice."""
    jax = _jax()
    from jax.sharding import PartitionSpec as P

    @jax.jit
    @functools.partial(jax.shard_map, mesh=mesh, in_specs=P("proc"),
                       out_specs=P("proc"))
    def f(a):
        return jax.lax.psum_scatter(a[0], "proc", scatter_dimension=0,
                                    tiled=True)

    return f


@_instrumented("reduce_scatter")
def reduce_scatter(x, op: str = "sum"):
    """Reduce across processes, then return this process's equal chunk of
    the leading axis (``dist.reduce_scatter`` analog).

    For ``op="sum"`` with one device per process this is a real XLA
    reduce-scatter (psum_scatter — no full all-reduce on the wire);
    other ops / multi-device processes fall back to all-reduce+slice.
    """
    _guard_check("reduce_scatter")
    jax = _jax()
    import jax.numpy as jnp

    n = jax.process_count()
    if n == 1:
        return jnp.asarray(x)  # identity — works even under tracing
    _reject_tracer(x, "reduce_scatter")
    x = jnp.asarray(x)
    if x.shape[0] % n:
        raise ValueError(f"leading dim {x.shape[0]} not divisible by "
                         f"{n} processes")
    if op == "sum" and jax.local_device_count() == 1:
        mesh = _proc_mesh()
        garr = _to_global(x, mesh)
        return _reduce_scatter_fn(mesh)(garr).addressable_data(0)
    reduced = all_reduce(x, op=op)
    chunks = jnp.split(jnp.asarray(reduced), n, axis=0)
    return chunks[rank()]


@functools.lru_cache(maxsize=None)
def _quantized_all_reduce_fn(mesh, block: int):
    """EQuARX-style quantized all-reduce (Dryden et al. /
    arXiv:2506.17615 pattern, built from XLA collectives): fp32
    reduce-scatter, then each device block-quantizes its reduced shard
    to int8 (per-block absmax scales) and the expensive all-gather
    phase moves int8 + scales instead of fp32 — ~1.6x less wire
    traffic overall, more at lower bits.  One compiled program."""
    jax = _jax()
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    @jax.jit
    @functools.partial(jax.shard_map, mesh=mesh, in_specs=P("proc"),
                       out_specs=P(), check_vma=False)
    def f(a):
        shard = jax.lax.psum_scatter(a[0], "proc", scatter_dimension=0,
                                     tiled=True)               # (m,) fp32
        blocks = shard.reshape(-1, block)
        absmax = jnp.max(jnp.abs(blocks), axis=1, keepdims=True)
        scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
        q = jnp.clip(jnp.round(blocks / scale), -127, 127).astype(jnp.int8)
        qg = jax.lax.all_gather(q, "proc", tiled=True)
        sg = jax.lax.all_gather(scale.astype(jnp.float32), "proc",
                                tiled=True)
        return (qg.astype(jnp.float32) * sg).reshape(-1)

    return f


@_instrumented("all_reduce_quantized")
def all_reduce_quantized(x, op: str = "sum", *, block: int = 256):
    """Approximate all-reduce with int8-quantized gather phase.

    Same contract as :func:`all_reduce` (sum/mean) but the result is
    quantized to 8 bits blockwise after the reduction — relative error
    bounded by ~1/254 per block — in exchange for moving ~1.6× fewer
    bytes (the technique of EQuARX, arXiv:2506.17615, composed here
    from XLA's own collectives).  Intended for DCN-bound gradient
    exchange; use :func:`all_reduce` when exactness matters.
    """
    _guard_check("all_reduce_quantized")
    jax = _jax()
    import jax.numpy as jnp

    if op not in ("sum", "mean"):
        raise ValueError("all_reduce_quantized supports op sum|mean")
    if jax.process_count() == 1 and jax.local_device_count() == 1:
        return jnp.asarray(x)
    _reject_tracer(x, "all_reduce_quantized")
    x = jnp.asarray(x)
    orig_shape, orig_dtype = x.shape, x.dtype

    mesh = _proc_mesh()
    n_dev = mesh.devices.size
    flat = x.astype(jnp.float32).reshape(-1)
    pad = (-flat.size) % (n_dev * block)
    flat = jnp.pad(flat, (0, pad))
    out = _quantized_all_reduce_fn(mesh, block)(
        _to_global(flat, mesh)).addressable_data(0)
    local = jax.local_device_count()
    if local > 1:
        out = out / local  # per-process duplicate copies, as in all_reduce
    if op == "mean":
        out = out / world_size()
    if pad:
        out = out[:-pad]
    if jnp.issubdtype(orig_dtype, jnp.integer):
        # Truncation would bias quantization noise toward zero (e.g. a
        # true 3 dequantizing to 2.996 must not become 2).
        out = jnp.round(out)
    return out.reshape(orig_shape).astype(orig_dtype)


def _check_root(root: int, what: str) -> None:
    """torch.distributed raises on an invalid root; so do we — the
    mask-and-sum broadcast would otherwise silently yield zeros and
    the root-gated returns would yield None on every rank."""
    w = world_size()
    if not 0 <= root < w:
        raise ValueError(f"{what}: root {root} out of range for "
                         f"world size {w}")


@_instrumented("scatter")
def scatter(x, root: int = 0):
    """Rank ``root`` provides a stacked ``(world, ...)`` array; every
    rank returns its own row (``dist.scatter`` analog, functional).

    XLA's collectives are symmetric, so the one-sided scatter is a
    broadcast of root's stack + a local row slice — simple and
    correct; the extra wire traffic vs a true scatter is
    ``(world-1)/world`` of the stack, acceptable at notebook scale
    (use sharded arrays + ``jax.device_put`` for bulk data placement).
    Non-root ranks still pass a same-shape array (any values) — every
    process participates, as with all eager collectives here."""
    _guard_check("scatter")
    _check_root(root, "scatter")
    jax = _jax()
    import jax.numpy as jnp

    from ..runtime.collective_guard import nested as _guard_nested

    x = jnp.asarray(x)
    w = world_size()
    if x.shape[:1] != (w,):
        raise ValueError(
            f"scatter needs a ({w}, ...) stacked array (one row per "
            f"rank), got shape {x.shape}")
    if w == 1:
        return x[0]
    with _guard_nested():   # one user-level op = one counted op
        return broadcast(x, root=root)[rank()]


@_instrumented("gather")
def gather(x, root: int = 0):
    """Gather per-rank values to ``root``: root returns the stacked
    ``(world, ...)`` array, every other rank returns None
    (``dist.gather`` analog).  Implemented over the symmetric
    all-gather; see :func:`scatter` for the symmetry note."""
    _guard_check("gather")
    _check_root(root, "gather")
    from ..runtime.collective_guard import nested as _guard_nested
    with _guard_nested():
        out = all_gather(x)
    return out if rank() == root else None


@_instrumented("reduce")
def reduce(x, root: int = 0, op: str = "sum"):
    """Reduce across ranks to ``root``: root returns the reduced
    value, every other rank returns None (``dist.reduce`` analog,
    over the symmetric all-reduce)."""
    _guard_check("reduce")
    _check_root(root, "reduce")
    from ..runtime.collective_guard import nested as _guard_nested
    with _guard_nested():
        out = all_reduce(x, op=op)
    return out if rank() == root else None


class DistNamespace:
    """``dist``-style facade seeded into worker namespaces so users who
    know torch.distributed feel at home (reference seeds ``dist`` at
    worker.py:162)."""

    all_reduce = staticmethod(all_reduce)
    all_gather = staticmethod(all_gather)
    broadcast = staticmethod(broadcast)
    barrier = staticmethod(barrier)
    reduce_scatter = staticmethod(reduce_scatter)
    scatter = staticmethod(scatter)
    gather = staticmethod(gather)
    reduce = staticmethod(reduce)

    @staticmethod
    def get_rank() -> int:
        return rank()

    @staticmethod
    def get_world_size() -> int:
        return world_size()

    def __repr__(self) -> str:
        return (f"<nbdistributed_tpu dist: rank {rank()}/"
                f"{world_size()} processes, {device_world()} devices>")


def clear_mesh_cache() -> None:
    """Reset the cached mesh and jitted collectives (for tests that
    re-enter worlds)."""
    _proc_mesh.cache_clear()
    _reduce_fn.cache_clear()
    _gather_fn.cache_clear()
    _reduce_scatter_fn.cache_clear()
    _quantized_all_reduce_fn.cache_clear()
