"""Paged KV storage: fixed-size blocks under the dense decode path.

The dense serving cache is one ``(L, max_batch, Hkv, max_len, D)``
pool — every slot reserves ``max_len`` tokens of KV for its whole
lifetime, so a server sized for long contexts wastes almost all of its
cache on short chats.  This module pages that storage: the pool
becomes ``(L, n_blocks + 1, Hkv, block_tokens, D)`` — one "batch row"
per fixed-size *block* — and each slot holds a table of physical block
ids covering exactly ``ceil((prompt + max_new) / block_tokens)``
blocks.  Capacity is then measured in blocks (the
:class:`~..serving_fast.paging.BlockAllocator` arithmetic the gateway
uses for admission), so ``max_batch`` can exceed what a dense pool of
the same HBM could hold and short requests stop reserving long-context
KV.  The int8/int4 quantized layout comes for free: the pool is built
by the same :func:`~.generate.init_kv_cache` (values + per-token
scales), and every helper here tree-maps over the cache dict, so
paged + quantized compose without new code.

**Compute path (stated honestly).**  The attention kernels are
unchanged: each step *gathers* the table-selected blocks into a dense
``(L, S, Hkv, T', D)`` view, runs the existing
:func:`~.generate.forward_with_cache`, and *scatters* back only what
changed (decode: the one block containing the written position per
active slot; prefill: the slot's whole row).  The gather is one
``jnp.take`` per cache leaf — XLA fuses it, but the dense view is
materialized per step, so paging here buys *capacity accounting and
admission semantics*, not peak-HBM-per-step; a fused paged-attention
kernel (block tables consumed inside the Pallas decode kernel,
ops/decode.py) is the stated next step on the roadmap.

**The trash block.**  Physical block ``n_blocks`` is never allocated.
Unallocated table entries point at it, and the decode scatter
redirects *inactive* slots there, so a freed-and-reallocated block can
never be corrupted by a stale slot's frozen-position write (the dense
pool tolerates those because admission re-prefills the whole row;
a paged block may be owned by someone else by then).  Garbage in the
trash block — or in allocated-but-unwritten blocks — is unreachable by
attention: positions ``> cache_len`` are masked, and a slot's
``cache_len`` never passes its allocated token count.

Exactness: gather ∘ scatter is the identity on the blocks a slot owns,
so a paged greedy decode computes what the dense server (and a solo
:func:`~.generate.generate`) computes.  In float32 the tokens are
bit-identical — asserted by the paged-decode unit tests on the CPU
(including the quantized round-trip tolerance) and by ``chip_smoke.py``
on the TPU at ``highest`` matmul precision; in bf16 on the TPU see the
rounding note in :mod:`.serving`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..serving_fast.paging import BlockAllocator, blocks_needed
from .generate import init_kv_cache, kv_cache_shardings


def make_paged_pool(cfg, n_blocks: int, block_tokens: int, *,
                    mesh=None, quantized: bool = False):
    """The physical block pool: ``init_kv_cache`` with the batch axis
    repurposed as blocks (+1 trash block).  With a mesh, only the
    KV-head (tp) axis is sharded — block ids are dynamic gather
    indices, so the block axis stays replicated and GSPMD keeps the
    gather local per shard."""
    rules = None
    if mesh is not None:
        rules = kv_cache_shardings(
            dp_axis=None,
            tp_axis="tp" if "tp" in mesh.shape else None,
            sp_axis=None, quantized=quantized)
    return init_kv_cache(cfg, int(n_blocks) + 1, int(block_tokens),
                         mesh=mesh, rules=rules, quantized=quantized)


# The gathers and scatters below carry a ``jax.named_scope`` each, so
# their ops can be told from the model's in a profile (trace-time
# metadata only: the compiled program is the same).


@jax.named_scope("gather_dense")
def gather_dense(pool, table):
    """Table-select every slot's blocks into a dense cache view.

    pool leaves ``(L, NB+1, Hkv, bt, D)``, table ``(S, MB)`` physical
    ids -> dense leaves ``(L, S, Hkv, MB*bt, D)`` — the exact layout
    ``forward_with_cache`` expects, with ``T' = MB*bt``.
    """
    def one(c):
        g = jnp.take(c, table, axis=1)        # (L, S, MB, Hkv, bt, D)
        g = jnp.transpose(g, (0, 1, 3, 2, 4, 5))
        sh = g.shape
        return g.reshape(sh[0], sh[1], sh[2], sh[3] * sh[4], sh[5])
    return jax.tree_util.tree_map(one, pool)


@jax.named_scope("gather_row")
def gather_row(pool, row_ids):
    """One slot's blocks as a dense ``(L, 1, Hkv, MB*bt, D)`` row —
    the prefill working view."""
    def one(c):
        g = jnp.take(c, row_ids, axis=1)      # (L, MB, Hkv, bt, D)
        g = jnp.transpose(g, (0, 2, 1, 3, 4))
        sh = g.shape
        return g.reshape(sh[0], sh[1], sh[2] * sh[3],
                         sh[4])[:, None]
    return jax.tree_util.tree_map(one, pool)


@jax.named_scope("scatter_row")
def scatter_row(pool, row, row_ids):
    """Write a slot's whole dense row back to its physical blocks.
    Trash-mapped ids receive the row's pad garbage — harmless by
    construction (see module docstring)."""
    def one(c, r):
        sh = c.shape                          # (L, NB+1, Hkv, bt, D)
        r = r[:, 0]                           # (L, Hkv, MB*bt, D)
        r = r.reshape(sh[0], sh[2], -1, sh[3], sh[4])
        r = jnp.transpose(r, (0, 2, 1, 3, 4))  # (L, MB, Hkv, bt, D)
        return c.at[:, row_ids].set(r)
    return jax.tree_util.tree_map(one, pool, row)


@jax.named_scope("scatter_step")
def scatter_step(pool, dense, table, pos, active, trash: int,
                 block_tokens: int):
    """Write back the ONE block per slot that a decode step touched.

    ``pos`` is the position the step wrote (pre-increment ``lens``).
    Inactive slots are redirected to the trash block — their frozen-
    position write must never land in a block that may have been
    reallocated to another request.
    """
    blk_log = pos // block_tokens                       # (S,)
    phys = jnp.take_along_axis(table, blk_log[:, None],
                               axis=1)[:, 0]            # (S,)
    phys = jnp.where(active, phys, trash)

    def one(c, d):
        sh = c.shape                          # (L, NB+1, Hkv, bt, D)
        d = d.reshape(d.shape[0], d.shape[1], d.shape[2], -1,
                      block_tokens, d.shape[-1])
        blk = jnp.take_along_axis(
            d, blk_log[None, :, None, None, None, None],
            axis=3)[:, :, :, 0]               # (L, S, Hkv, bt, D)
        return c.at[:, phys].set(blk)
    return jax.tree_util.tree_map(one, pool, dense)


def apply_moves(pool, moves: dict[int, int]):
    """Apply a :meth:`BlockAllocator.defrag` move map to the physical
    pool with ONE gather per leaf: ``new[dst] = old[src]``.  The map is
    read atomically, so chains of moves (a live block compacting into
    another live block's vacated id) are safe."""
    if not moves:
        return pool
    n = jax.tree_util.tree_leaves(pool)[0].shape[1]
    src = np.arange(n)
    for old, new in moves.items():
        src[new] = old
    src = jnp.asarray(src, jnp.int32)
    return jax.tree_util.tree_map(
        lambda c: jnp.take(c, src, axis=1), pool)


class PagedKVCache:
    """Host-side paging state for one decode server: the block
    allocator (owner = slot id) plus per-slot block tables, with
    cached device mirrors.  The physical pool itself lives in the
    server (it is donated through the jitted step/prefill programs —
    a second reference here would dangle)."""

    def __init__(self, *, slots: int, max_len: int, n_blocks: int,
                 block_tokens: int):
        self.slots = int(slots)
        self.block_tokens = int(block_tokens)
        self.n_blocks = int(n_blocks)
        self.trash = self.n_blocks
        self.max_blocks = blocks_needed(max_len, block_tokens)
        if self.max_blocks < 1:
            raise ValueError(f"max_len {max_len} yields an empty "
                             f"block table")
        self.allocator = BlockAllocator(n_blocks, block_tokens)
        # -1 = unallocated (mapped to trash on the device mirror).
        self._table = np.full((self.slots, self.max_blocks), -1,
                              np.int32)
        self._dev = None                      # invalidated on change

    # -- allocation (owner = slot) ------------------------------------
    def alloc(self, slot: int, tokens: int) -> None:
        """Worst-case allocation for a request that may reach
        ``tokens`` KV entries.  Raises
        :class:`~..serving_fast.paging.BlocksExhausted` untaken."""
        ids = self.allocator.alloc(str(slot),
                                   blocks_needed(tokens,
                                                 self.block_tokens))
        self._table[slot, :] = -1
        self._table[slot, :len(ids)] = ids
        self._dev = None

    def free(self, slot: int) -> int:
        n = self.allocator.free(str(slot))
        self._table[slot, :] = -1
        self._dev = None
        return n

    def defrag(self) -> dict[int, int]:
        """Compact the allocator and refresh the host tables; the
        caller applies the returned moves to the pool with
        :func:`apply_moves` (host table and device storage move in
        lock-step or not at all)."""
        moves = self.allocator.defrag()
        if moves:
            for slot in range(self.slots):
                ids = self.allocator._tables.get(str(slot))
                if ids is not None:
                    self._table[slot, :len(ids)] = ids
            self._dev = None
        return moves

    # -- device mirrors ------------------------------------------------
    def device_table(self):
        """(S, MB) int32 physical-id table, -1 entries mapped to the
        trash block.  Rebuilt only when the tables changed — the
        common decode tick reuses the cached device array."""
        if self._dev is None:
            t = np.where(self._table < 0, self.trash, self._table)
            self._dev = jnp.asarray(t, jnp.int32)
        return self._dev

    def device_row(self, slot: int):
        """(MB,) int32 physical ids for one slot (prefill's view)."""
        return self.device_table()[slot]

    # -- accounting ----------------------------------------------------
    @property
    def used_blocks(self) -> int:
        return self.allocator.used_blocks

    @property
    def free_blocks(self) -> int:
        return self.allocator.free_blocks

    def largest_free_run(self) -> int:
        """Longest contiguous free-block run (fragmentation telemetry
        for the serving observatory / %dist_top frag column)."""
        return self.allocator.largest_free_run()

    def snapshot(self) -> dict:
        return self.allocator.snapshot()
