"""Data-parallel training: the DDP capability, the XLA way.

The reference demonstrates DDP through user-space HF Accelerate in its
notebook (00_accelerate.ipynb cells 36-40) and hand-written all_reduce
loops (README.md:97-111).  TPU-native DDP needs no wrapper class at all:
replicate params, shard the batch on the ``dp`` mesh axis, and jit.
Left to the sharding lattice, XLA sums the gradients with all-reduces
that *block* on this chip (the TensorCore waits for the links, 31 ms
of a 229 ms Mistral-7B step on four v5e chips), so over more than one
shard the step differentiates each shard's rows inside a ``shard_map``
and sums the large gradients' reduce half with asynchronous
``ppermute`` sends that the backward pass runs beside
(``overlap.exchange_sum``, ISSUE 36).  This module packages that
recipe.
"""

from __future__ import annotations

import re

from . import mesh as mesh_mod

_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
          "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
          "u64": 8}


def collectives_of(compiled) -> dict:
    """What a compiled step (``step.lower(...).compile()``, or its
    text) says of its gradient sum: ``async_sends``, the asynchronous
    sends it holds (``collective-permute`` instructions, a ``-start`` /
    ``-done`` pair counted once), and the bytes its blocking
    ``all-reduce`` and ``all-gather`` instructions return.  A DDP step
    over several shards sends the large leaves' reduce half and
    gathers their sums; GSPMD's holds no send and all-reduces every
    weight."""
    text = compiled if isinstance(compiled, str) else compiled.as_text()

    def returned(op):
        total = 0
        for shapes in re.findall(rf" = (.*?) {op}(?:-start)?\(", text):
            for dtype, dims in re.findall(r"\b([a-z]+\d+|pred)\[([\d,]*)\]",
                                          shapes):
                size = _BYTES[dtype]
                for d in filter(None, dims.split(",")):
                    size *= int(d)
                total += size
        return total

    return {"async_sends": len(re.findall(
                r" collective-permute(?:-start)?\(", text)),
            "blocking_all_reduce_bytes": returned("all-reduce"),
            "blocking_all_gather_bytes": returned("all-gather")}


def make_ddp_step(loss_fn, optimizer, mesh, *, dp_axis: str = "dp",
                  donate: bool = True, guard: bool = False):
    """Build a jitted DDP train step.

    ``loss_fn(params, batch) -> scalar``.  Params/opt state are
    replicated; the batch arrives sharded on ``dp_axis``; over more
    than one shard each differentiates its own rows and the gradients
    are summed inside the backward pass (see ``make_tp_train_step``'s
    ``exchanged_grads_of``).  The loss returned and differentiated is
    then the mean of the shards' losses, each over its own rows: give
    it a loss that is an equal-weight mean over rows (then that is the
    global batch's loss).  One that is not (packed rows dividing by the
    targets kept, a sum, statistics over the batch) comes out as that
    mean and not as the loss of the global batch, as under gradient
    accumulation it comes out as the mean over the microbatches.

    DDP is the all-replicated special case of the tensor-parallel step
    builder — one step body to maintain (grad clipping, loss scaling,
    etc. land in one place).

    Returns ``step(params, opt_state, batch) -> (params, opt_state,
    loss)``; with ``guard=True`` (ISSUE 19) the step instead returns
    ``(params, opt_state, loss, aux)`` and skips the update on
    non-finite gradients — see
    :func:`~nbdistributed_tpu.parallel.tensor_parallel.make_tp_train_step`.
    """
    from . import tensor_parallel
    return tensor_parallel.make_tp_train_step(
        loss_fn, optimizer, mesh, param_rules=None, dp_axis=dp_axis,
        donate=donate, guard=guard)


def ddp_init(params, opt_state, mesh):
    """Replicate params + optimizer state across the mesh (the
    ``accelerator.prepare`` analog)."""
    return (mesh_mod.replicate(params, mesh),
            mesh_mod.replicate(opt_state, mesh))
