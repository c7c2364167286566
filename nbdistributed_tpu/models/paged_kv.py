"""Paged KV storage: fixed-size blocks, read and written in place by the
decode step and by the prefill chunk.

A dense serving cache is one ``(L, max_batch, Hkv, max_len, D)``
pool — every slot reserves ``max_len`` tokens of KV for its whole
lifetime, so a server sized for long contexts wastes almost all of its
cache on short chats.  This module pages that storage: the pool
is ``(L, n_blocks + 1, Hkv, block_tokens, D)`` — one "batch row"
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

**Compute path.**  Both serving programs consume the physical pool
where it lies (:class:`PagedKV`, the pool's side of
:func:`~.generate.forward_with_cache`'s one seam).  The pool rides the
layer scan as carry, updated in place under the program's donation,
and each layer does two things to it: it *writes* its new entries
straight into the row's pages — a decode step one token a slot
(:func:`write_token`: physical block ``table[b, pos // bt]``, offset
``pos % bt``), a prefill chunk its ``S`` tokens (:func:`write_chunk`:
the pages the span touches, whole) — and it *attends* through the
table.  A step with ``cfg.use_flash`` on one device attends inside a
Pallas kernel of :mod:`..ops.decode` (``paged_decode_attention``, the
latent pool's twin): layer, block table and positions are
scalar-prefetch operands, and each row's grid step copies the row's
live pages out of the pool itself, so a step reads the tokens its rows
hold and never ``max_len``, and no dense view of the pool exists
(``DecodeServer.kv_view_bytes`` reads 0).  Otherwise (``use_flash``
off, under a mesh, or an int8 pool) a step's :func:`gather_layer`
takes *that layer's* blocks to a ``(S, Hkv, T', D)`` view for the
dense attention paths of :mod:`.generate` (the view lives for one
layer, and
``kv_view_bytes`` counts what a step gathers that way).  A chunk runs
the kernel's recurrence in ``jax.numpy`` over key tiles of whole pages
taken through the table, as many as the row holds
(``paged_prefill_attention``), in every case.

**The trash block.**  Physical block ``n_blocks`` is never allocated.
Unallocated table entries point at it, and the decode step's write
redirects *inactive* slots there, so a freed-and-reallocated block can
never be corrupted by a stale slot's frozen-position write (the
block may be owned by someone else by then).  Garbage in the
trash block — or in allocated-but-unwritten blocks — is unreachable by
attention: positions ``> cache_len`` are masked, and a slot's
``cache_len`` never passes its allocated token count.  A prefill chunk
keeps what no token of the row wrote out of the weighted sum too (a
probability of zero does not clean a NaN); a decode step does not yet,
so garbage has to be finite, as zeros and a former owner's tokens are.

Exactness: a slot's pages hold, token for token, what its dense row
would, so a paged greedy decode computes what a solo
:func:`~.generate.generate` computes.  In float32 the tokens are
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


# The gathers, scatters and the write below carry a ``jax.named_scope``
# each, so their ops can be told from the model's in a profile
# (trace-time metadata only: the compiled program is the same).


@jax.named_scope("gather_layer")
def gather_layer(pool, layer, table):
    """Table-select every slot's blocks of ONE layer into a dense
    view: pool leaves ``(L, NB+1, Hkv, bt, D)``, ``layer`` a traced
    scalar, table ``(S, MB)`` physical ids -> leaves ``(S, Hkv, MB*bt,
    D)``, what the dense attention paths expect with ``T' = MB*bt``.
    Only for a step that cannot read the pool in place (see
    :func:`reads_in_place`)."""
    def one(c):
        g = jnp.take(c[layer], table, axis=0)  # (S, MB, Hkv, bt, D)
        g = jnp.transpose(g, (0, 2, 1, 3, 4))
        sh = g.shape
        return g.reshape(sh[0], sh[1], sh[2] * sh[3], sh[4])
    return jax.tree_util.tree_map(one, pool)


@jax.named_scope("write_token")
def write_token(pool, layer, new, table, pos, active):
    """Write each slot's ONE new token of one layer into its page,
    or its one block of them.

    ``new`` leaves ``(S, Hkv, n, D)`` (the pool's leaves for the
    step's tokens; ``n`` is 1, or the block length of a block-causal
    model, whose blocks start at multiples of ``n`` and so lie inside
    one page where ``n`` divides the page: :func:`_write_blocks`
    stages those), ``pos`` (S,) the position of the first.  Physical
    block ``table[b, pos // bt]``, offset ``pos % bt``, all heads.
    Inactive slots are redirected to the trash block — their
    frozen-position write must never land in a block that may have
    been reallocated to another request."""
    first = jax.tree_util.tree_leaves(pool)[0]
    trash, bt = first.shape[1] - 1, first.shape[3]
    blk = jnp.minimum(pos // bt, table.shape[1] - 1)
    phys = jnp.take_along_axis(table, blk[:, None], axis=1)[:, 0]
    if active is not None:
        phys = jnp.where(active, phys, trash)
    off = pos % bt
    if jax.tree_util.tree_leaves(new)[0].shape[2] > 1:
        return _write_blocks(pool, layer, new, phys, off)

    def one(c, n):
        # One slice update a slot, not one scatter: a slice update
        # takes the pool in whatever layout it has and rewrites it in
        # place, where a scatter over the token axis makes XLA re-lay
        # the whole pool out around it, twice a layer.
        n = n.astype(c.dtype)                 # (S, Hkv, n, D)
        for b in range(n.shape[0]):
            c = jax.lax.dynamic_update_slice(
                c, n[b][None, None], (layer, phys[b], 0, off[b], 0))
        return c
    return jax.tree_util.tree_map(one, pool, new)


def _write_blocks(pool, layer, new, phys, off):
    """:func:`write_token`'s slice updates for a block a row, staged
    with under half the operations: a row's page and offset are taken
    out of their vectors and wrapped once for all leaves, by ``lax``
    and not by indexing, and an update wraps no index again.  A
    block server's pass is unrolled over its layers and runs a hundred
    rows and more, so these updates are most of what tracing and
    lowering it cost; one token a row keeps the form above, whose text
    the dense family's tests pin.

    Every index still goes through the wrap once (a select that
    changes nothing): the TPU compiler keeps a select's scalar result
    in scalar memory, where an update finds it, and leaves a bare
    element of a vector (or its clamp by ``max``) in HBM, from where
    every update fetches it: 1.8 us an update for 0.67, at 2,240
    updates a pass (PERF.md, PR 46)."""
    def scalar(x, size):
        return jax.lax.select(jax.lax.lt(x, np.int32(0)),
                              jax.lax.add(x, np.int32(size)), x)

    first = jax.tree_util.tree_leaves(pool)[0]
    rows = range(phys.shape[0])
    layer = scalar(jnp.asarray(layer), first.shape[0])
    at = [(scalar(jax.lax.index_in_dim(phys, b, keepdims=False),
                  first.shape[1]),
           scalar(jax.lax.index_in_dim(off, b, keepdims=False),
                  first.shape[3]))
          for b in rows]

    def one(c, n):
        n = n.astype(c.dtype)                 # (S, Hkv, n, D)
        for b, (page, start) in zip(rows, at):
            c = jax.lax.dynamic_update_slice(
                c, jax.lax.expand_dims(jax.lax.slice_in_dim(n, b, b + 1),
                                       (0,)),
                (layer, page, 0, start, 0), allow_negative_indices=False)
        return c
    return jax.tree_util.tree_map(one, pool, new)


@jax.named_scope("write_chunk")
def write_chunk(pool, layer, new, table, start):
    """Write each row's chunk of ``S`` new tokens of one layer into its
    pages, where they lie.

    ``new`` leaves ``(B, Hkv, S, D)``, ``start`` (B,) the position of
    each row's first token; any ``start``, any ``S``.  The pages the
    span ``[start, start + S)`` can touch are taken out of the layer
    (one more than ``S`` fills, for a ``start`` inside a page), the
    chunk is laid over them at ``start % bt``, and they go back whole,
    one slice update a page as :func:`write_token` one a token.  The
    padded tail of a chunk goes with it: entries the table maps to the
    trash block, and pages past the table, land in the trash block."""
    first = jax.tree_util.tree_leaves(pool)[0]
    trash, bt = first.shape[1] - 1, first.shape[3]
    B, _, S, _ = jax.tree_util.tree_leaves(new)[0].shape
    n = (S - 1) // bt + 2
    page = (start // bt)[:, None] + jnp.arange(n)[None, :]     # (B, n)
    ids = jnp.where(
        page < table.shape[1],
        jnp.take_along_axis(table, jnp.minimum(page, table.shape[1] - 1),
                            axis=1), trash)
    off = start % bt

    def one(c, x):
        hkv, width = c.shape[2], c.shape[4]
        x = x.astype(c.dtype)
        for b in range(B):
            span = c[layer, ids[b]].transpose(1, 0, 2, 3)  # (Hkv, n, bt, D)
            span = jax.lax.dynamic_update_slice(
                span.reshape(hkv, n * bt, width), x[b], (0, off[b], 0))
            span = span.reshape(hkv, n, bt, width)
            for j in range(n):
                c = jax.lax.dynamic_update_slice(
                    c, span[:, j][None, None],
                    (layer, ids[b, j], 0, 0, 0))
        return c
    return jax.tree_util.tree_map(one, pool, new)


def reads_in_place(cfg, mesh, quantized: bool = False) -> bool:
    """Whether a decode step over a paged pool attends inside the
    Pallas kernel, block tables and all — what ``cfg.use_flash``
    chooses on one device, as everywhere else in
    :func:`~.generate.forward_with_cache` — or gathers a per-layer
    view for the dense attention paths.  An int8 pool gathers: the
    kernel copies pages out of the pool itself, and Mosaic refuses the
    copy of a scale leaf's ``(Hkv, bt, 1)`` page (a slice one lane
    wide)."""
    return bool(cfg.use_flash) and mesh is None and not quantized


class PagedKV:
    """The paged pool's side of :func:`~.generate.forward_with_cache`'s
    seam (see :class:`~.generate.DenseKV` for the contract): the whole
    ``(L, NB+1, Hkv, bt, width)`` pool is held across the layer scans
    and updated in place, and a layer takes only its index.  What a
    page holds is the mixer's business: K and V heads
    (:class:`~.generate.GQAMixer`) or one latent row
    (:class:`~.mla.MLAMixer`).

    One new token a row is a decode step (rows outside ``active`` write
    to the trash block and attend nothing), and so, under a
    block-causal mask, is a block of ``cfg.block_length`` tokens a row
    with ``active`` given; several otherwise are a chunk of a
    prefill, every row of which takes part, ``token_mask`` telling its
    real tokens from its padded tail.  Either way a layer writes its
    new entries into the row's pages and attends through the table
    over the keys the row holds, never a dense view of the row."""

    def __init__(self, pool: dict, table, active, mixer, cfg, mesh,
                 token_mask=None):
        self.held = pool
        n_layers = jax.tree_util.tree_leaves(pool)[0].shape[0]
        self.per_layer = jnp.arange(n_layers, dtype=jnp.int32)
        self._table, self._active = table, active
        self._mixer = mixer
        self._in_place = reads_in_place(cfg, mesh, "k_s" in pool)
        self._block = getattr(cfg, "block_length", 1)
        # A chunk's real tokens end at its last real one: no real
        # query needs a key past it.
        self._length = None if token_mask is None else jnp.max(
            jnp.where(token_mask, jnp.arange(1, token_mask.shape[1] + 1),
                      0), axis=1).astype(jnp.int32)

    def layer(self, pool, layer_idx, q, new, positions, layer):
        pos = positions[:, 0]
        # A decode step carries one token a row, or (``row_mask``
        # given) the block a row that a block-causal model denoises.
        if q.shape[1] > 1 and not (self._active is not None
                                   and q.shape[1] == self._block):
            if self._active is not None:
                raise ValueError("a chunk of new tokens takes every "
                                 "row: row_mask is the decode step's")
            pool = write_chunk(pool, layer_idx, new, self._table, pos)
            o = self._mixer.attend_paged(
                q, pool, layer_idx, self._table, pos, None, layer,
                length=self._length)
            return o, pool, None
        # Write first, attend second: the kernel sees position pos.
        pool = write_token(pool, layer_idx, new, self._table, pos,
                           self._active)
        if self._in_place:
            o = self._mixer.attend_paged(q, pool, layer_idx,
                                         self._table, pos,
                                         self._active, layer)
        elif q.shape[1] > 1:
            raise ValueError("a block step attends the pool in place: "
                             "use_flash on one device, no int8 pool")
        else:
            view = gather_layer(pool, layer_idx, self._table)
            o = self._mixer.attend(q, view, positions, layer)
        return o, pool, None

    def result(self, pool, per_layer):
        return pool


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
