"""Continuous-batching decode server: staggered admission over a fixed
set of slots and one paged KV pool, one shared forward per step.

The reference framework has no serving path at all (its users call HF
``generate`` per prompt in cells); this is the TPU-native serving loop
the KV-cache machinery was built to support.  Design:

* **Static shapes, dynamic occupancy.**  The cache is one paged pool
  (:mod:`.paged_kv`): ``(L, kv_blocks + 1, Hkv, kv_block_tokens, D)``
  physical blocks, and a request occupies a batch *slot* and a table of
  ``ceil((prompt + max_new) / kv_block_tokens)`` blocks for its
  lifetime.  Admission, completion, and re-use never change any array
  shape — XLA compiles exactly two programs (prefill per prompt bucket
  or chunk, one decode step) no matter how requests arrive.  A block of
  at least ``max_len`` tokens makes a row a table of one page: the
  dense slot pool.
* **Per-slot cache pointers.**  The decode step runs ALL slots in one
  ``forward_with_cache`` call with a per-row ``(B,)`` ``cache_len`` —
  the same machinery batched speculative decoding uses
  (speculative.py) — so requests at different depths share every
  matmul.  Decode-step cost is one B-row forward regardless of how
  staggered the batch is: that sharing is the whole point of
  continuous batching.
* **Inactive slots freeze exactly like finished speculative streams:**
  their advance is masked to zero, their cache write lands in the
  pool's trash block, and for MoE configs ``row_mask`` keeps them out
  of expert capacity dispatch, so an empty or finished slot never
  perturbs a live one.
* **One step in flight.**  :meth:`DecodeServer.step` dispatches
  decode step n + 1 before it fetches step n's tokens: the next step's
  inputs (the pool, the rows' lengths and last tokens) are device
  arrays that step n returned, so nothing of the host's work between
  two steps (the fetch, emission, admission, building a chunk, the
  reply of a tick and the next request for one) needs the chip to
  wait.  What the host knows without the fetch decides which rows a
  step runs: a budget's end is counted at dispatch; an EOS or a
  cancel is learned a step late, and that row runs one surplus step
  whose token is dropped and whose write lands in pages the row still
  reserved.  Each step in flight carries the ``{slot: request}`` it
  was dispatched with, and a token is emitted only to the request
  that still holds its slot; a freed slot's next request is ordered
  behind the step in flight by the device's program order.  There is
  no other mode: the server runs ahead whenever a row is active and
  drains when none is.
* **Prefill-on-admit** runs the prompt as a single-row forward into
  the slot's pages, right-padded to a length *bucket* (one compile per
  bucket, ``pad_to`` granularity), or in fixed chunks
  (``prefill_chunk``).  Pad positions write garbage beyond the prompt
  — harmless by the write-then-attend order: a decode step at position
  ``p`` overwrites slot ``p`` before any query attends it, and
  attention masks ``t <= p``.  Pads are masked out of MoE expert
  dispatch (``token_mask``) so they can never consume capacity slots
  and evict real prompt tokens, and the lm_head runs only at the last
  real position (``last_index``).

* **A block server** (a config with ``block_length`` > 1,
  :mod:`.sdar`) runs a *pass* a step where the others run a token: a
  row carries a block of ``L`` tokens and which of them are open, and a
  denoising pass fixes the scheduled number of open positions and
  advances no position.  The pass that fixes a block's last open
  position finishes it: the block is emitted at that pass's fetch and
  the row's next block is all masks.  The finished block's K/V are
  committed by a *lane* of the pass that opens the next block
  (:func:`~.sdar.with_lanes`: one more row of that pass, whose logits
  nobody computes; ``lens`` moves there), so a commit takes no pass of
  its own.  A pass has :func:`~.sdar.lanes` of them; a row that finds
  none free sits that pass out and takes the next, and a request's
  last block is never committed, since no block attends it.  The
  schedule is static, so the host knows without a fetch which pass
  finishes a block, which rows need a lane and whose budget ends, and
  one pass stays in flight exactly as a step does.  A prompt's whole
  blocks are prefilled (no logits: nothing is sampled from them) and
  its remainder is seated in the row's first block.

Greedy serving reproduces a standalone :func:`~.generate.generate`
call per request: admission order, batch occupancy, and other
requests' traffic cannot change any request's tokens for the dense
family.  In float32 the streams are bit-identical (asserted in the CPU
tests; on a v5e at ``highest`` matmul precision through chunked
prefill, asserted by ``chip_smoke.py``).  In bf16 on the TPU
the step over ``max_batch`` rows and generate's one-row step are
different compiled programs whose matmuls round differently, so where
the top two logits sit within ~0.01 standard deviations the argmax can
flip and the stream diverges from there (PERF.md, PR 21: batch width
alone does it; padding, chunking and paging alone do not).  Every
served token is still the model's greedy choice to that margin, and
one server geometry is deterministic and occupancy-independent.  For MoE, a request served
*alone* matches generate exactly — pads are masked out of expert
dispatch AND admission runs at the exact prompt length (expert
capacity is shape-derived, so a padded bucket would inflate it past
the solo run's; the cost is one admission compile per distinct
prompt length for MoE configs).  Multiple live MoE requests pool
expert capacity across rows — batched-decode semantics, the same
caveat as batched speculative decoding.
"""

from __future__ import annotations

import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..observability import spans as obs_spans
from ..serving_fast.paging import BlocksExhausted
from . import sdar
from .generate import _sample, forward_with_cache
from .paged_kv import PagedKVCache, make_paged_pool, reads_in_place
from .transformer import TransformerConfig


def _capacity_dispatch(cfg, mesh, ep_axis: str) -> bool:
    """Whether the config's experts are dispatched into per-expert (or
    per-shard) capacity buffers whose size follows the call's token
    count — what ties a request's result to the shape it was run at.
    Dropless routing on one shard has no capacity, and neither have
    :class:`~.mla.LatentMoEConfig`'s experts."""
    from .moe import MoEConfig
    if not isinstance(cfg, MoEConfig):
        return False
    sharded = mesh is not None and ep_axis in mesh.shape
    return cfg.moe_dispatch != "dropless" or sharded


# The phases of one :meth:`DecodeServer.step`, in order.  Adjacent
# phases share their boundary instant, so a step's phases sum to its
# wall time; the same names are the ``serve/step/*`` spans and
# profiler annotations (observability/spans.py::phase).
STEP_PHASES = ("prefill", "dispatch", "sync", "emit")


class _KVKind(NamedTuple):
    """One kind of paged K/V that attention reads: a model whose layers
    all keep their own pages alike has one, a model with layer kinds
    (:mod:`.hybrid`) one a kind that keeps pages."""
    name: str
    window: int | None          # its layers' window; None: all a row holds
    page_bytes: int             # one live page of K and V, over the
    #                             layers that read it in a decode step


class _InFlight(NamedTuple):
    """A decode step that was dispatched and whose tokens the host has
    not fetched yet."""
    tokens: object              # (B,) the slots' next tokens; a block
    #                             server's pass: ((B, L) the blocks as
    #                             the pass left them, (B, L) the pass
    #                             at which each position was fixed)
    load: jax.Array | None      # the step's routing load (a config
    #                             whose experts report one)
    rows: dict[int, int]        # {slot: request id} it ran
    kv_bytes: tuple             # K and V page bytes its attention
    #                             reads, one count a :class:`_KVKind`
    blocks: dict | None = None  # a block server's pass: {slot: index
    #                             of the block's first new token} of
    #                             the rows whose block it finishes,
    fixed: int = 0              # the positions it fixes, all rows,
    fused: int = 0              # the commits its lanes carry
    waits: int = 0              # and the rows it left out for want of
    #                             a lane


class DecodeServer:
    """Continuous-batching server around one model and one paged pool.

    Host-side orchestration (admission queue, completion, output
    collection) wraps two jitted device programs: a per-bucket prefill
    and the shared decode step.  Use::

        srv = DecodeServer(params, cfg, max_batch=8, max_len=512)
        rid = srv.submit([1, 2, 3], max_new_tokens=16)
        while not srv.done():
            srv.step()   # dispatches a step, emits the one before:
                         # 1 token per request that one ran
        tokens = srv.outputs[rid]

    ``kv_block_tokens`` / ``kv_blocks`` are the pool's geometry
    (:mod:`.paged_kv`): each request reserves
    ``ceil((prompt + max_new) / kv_block_tokens)`` blocks at admission,
    and capacity is measured in blocks rather than slots — short
    requests do not reserve ``max_len`` of KV each.  Exhaustion leaves
    requests pending (never a silent wedge — the gateway's accounting
    allocator issues the explicit verdicts).

    ``prefill_chunk=N`` (dense layers or dropless experts) admits long
    prompts in fixed-size segments through one compiled (1, N) program
    — admission activation memory O(N) instead of O(S_prompt), no
    per-bucket compiles (see :meth:`_run_prefill`).
    ``interleave_prefill=True`` (requires ``prefill_chunk``) admits
    them one chunk per :meth:`step` interleaved with decode, bounding
    the prefill work any single tick can add — the chunked-prefill
    TPOT guarantee.
    """

    def __init__(self, params, cfg: TransformerConfig, *,
                 max_batch: int, max_len: int,
                 temperature: float = 0.0, top_k: int | None = None,
                 top_p: float | None = None, eos_id: int | None = None,
                 kv_quantized: bool = False, mesh=None,
                 ep_axis: str = "ep", pad_to: int = 64, key=None,
                 prefill_chunk: int | None = None,
                 kv_block_tokens: int = 64,
                 kv_blocks: int | None = None,
                 interleave_prefill: bool = False):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        if pad_to < 1:
            raise ValueError(f"pad_to must be >= 1, got {pad_to}")
        if top_k is not None and not 1 <= top_k <= cfg.vocab_size:
            raise ValueError(f"top_k must be in [1, vocab_size="
                             f"{cfg.vocab_size}], got {top_k}")
        if top_p is not None and not 0.0 < top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        if prefill_chunk is not None and prefill_chunk < 1:
            raise ValueError(f"prefill_chunk must be >= 1, got "
                             f"{prefill_chunk}")
        if kv_block_tokens < 1:
            raise ValueError(f"kv_block_tokens must be >= 1, got "
                             f"{kv_block_tokens}")
        if interleave_prefill and prefill_chunk is None:
            raise ValueError("interleave_prefill needs prefill_chunk "
                             "(the per-step prefill work bound)")
        if _capacity_dispatch(cfg, mesh, ep_axis):
            # Expert capacity is computed from the *static* token count
            # of the prefill shape: a padded bucket would inflate it
            # past what a solo generate() run of the same prompt gets,
            # and capacity changes which tokens drop — silently
            # breaking the solo-request exactness guarantee.  Such
            # admission therefore compiles per distinct prompt length
            # (pad_to=1); dense configs and dropless experts keep the
            # bucket economy.
            pad_to = 1
            if prefill_chunk is not None:
                # Chunked admission derives capacity from the CHUNK's
                # token count — again not a solo run's.  Same reason.
                raise ValueError(
                    "prefill_chunk needs dense layers or dropless "
                    "experts: capacity-based expert dispatch derives "
                    "capacity from the shape, so per-chunk capacity "
                    "would differ from a solo run's and change which "
                    "tokens drop")
        from .hybrid import StatefulConfig
        from .mla import LatentMoEConfig
        from .nemotron_h import NemotronHConfig
        self._routed = isinstance(cfg, (LatentMoEConfig, NemotronHConfig,
                                        sdar.SDARConfig))
        # Generation by diffusion over blocks: a row carries a block of
        # ``_L`` tokens, a step is a pass over it (1: a token a step).
        self._blocks = isinstance(cfg, sdar.SDARConfig)
        self._L = cfg.block_length if self._blocks else 1
        if self._blocks:
            L = self._L
            bad = {k: v for k, v in (("max_len", max_len),
                                     ("kv_block_tokens", kv_block_tokens),
                                     ("pad_to", pad_to),
                                     ("prefill_chunk", prefill_chunk))
                   if v is not None and v % L}
            if bad:
                raise ValueError(f"a block server of block length {L} "
                                 f"needs multiples of it: {bad}")
            if temperature or kv_quantized or mesh is not None \
                    or not cfg.use_flash:
                raise ValueError(
                    "a block server is greedy (temperature 0) and reads "
                    "its pool in place: use_flash on one device, no "
                    "int8 pool")
        # Layer kinds that keep state beside (or in place of) pages:
        # several kinds of cache, and a prefill chunk that is told its
        # row and whether it ends the prompt.
        self._hybrid = isinstance(cfg, StatefulConfig)
        if self._hybrid and (kv_quantized or mesh is not None):
            raise ValueError("state-space layers are served from "
                             "unquantized caches on one device")
        self._params = params
        self._cfg = cfg
        self._mesh = mesh
        self._ep_axis = ep_axis
        self._B = max_batch
        self._T = max_len
        self._pad_to = pad_to
        self._prefill_chunk = prefill_chunk
        self._temperature = temperature
        self._top_k = top_k
        self._top_p = top_p
        self._eos = eos_id
        self._key = key if key is not None else jax.random.PRNGKey(0)

        # The cache: (L, kv_blocks+1, Hkv, kv_block_tokens, D) physical
        # blocks, donated through both jitted programs.
        if kv_blocks is None:
            # Derived default: every slot can hold max_len tokens, so
            # paging with no explicit budget never refuses a request a
            # pool of max_len rows would have taken.
            kv_blocks = max_batch * (-(-max_len // kv_block_tokens))
        self._paged = PagedKVCache(
            slots=max_batch, max_len=max_len, n_blocks=kv_blocks,
            block_tokens=kv_block_tokens)
        # One page of K and V in bytes, over the layers that read it:
        # what a step's attention fetches per live page of a slot
        # (``step`` sums them into ``kv_read_bytes_total``).
        page_bytes = lambda pool: sum(
            c.nbytes // c.shape[1]
            for c in jax.tree_util.tree_leaves(pool))
        # Bytes of per-row state a decode step reads and writes (every
        # row's: the update is elementwise over the whole array).
        self._state_bytes = 0
        if self._hybrid:
            from .hybrid import make_hybrid_cache
            # Rows of state and of window rings are the slots: a free
            # slot is a free row, so they never refuse a request that
            # the slot count admits, and the allocator (the gateway's
            # mirror of it too) goes on counting the ``full`` kind's
            # blocks alone.
            self._cache = make_hybrid_cache(
                cfg, kv_blocks, kv_block_tokens, rows=max_batch,
                max_len=max_len, chunk=prefill_chunk)
            self._kinds = tuple(
                _KVKind(kind, pool.window,
                        page_bytes(self._cache[kind]) * pool.readers)
                for kind, pool in cfg.page_pools().items())
            self._state_bytes = 2 * sum(
                c.nbytes for c in
                jax.tree_util.tree_leaves(self._cache["ssm"]))
        else:
            self._cache = make_paged_pool(
                cfg, kv_blocks, kv_block_tokens, mesh=mesh,
                quantized=kv_quantized)
            self._kinds = (_KVKind("kv", cfg.sliding_window,
                                   page_bytes(self._cache)),)
        # Bytes a decode step gathers from the pool into dense
        # views, all layers: 0 where the kernel reads the pool in
        # place, else every slot's whole block table once a layer
        # (the fallback's cost per step; a count from shapes).
        self.kv_view_bytes = (
            0 if reads_in_place(cfg, mesh, kv_quantized) else
            sum(k.page_bytes for k in self._kinds)
            * self._paged.max_blocks * max_batch)
        self._lens = jnp.zeros((max_batch,), jnp.int32)
        self._last = jnp.zeros((max_batch,), jnp.int32)
        self._active = jnp.zeros((max_batch,), bool)
        if self._blocks:
            self._block = sdar.fresh_block(cfg, max_batch)

        # Host-side bookkeeping.
        self._free = list(range(max_batch))
        self._slot_req: dict[int, int] = {}      # slot -> request id
        self._budget: dict[int, int] = {}        # request id -> remaining
        # The rows the next decode step runs, as the host knows them
        # without a fetch: slot -> [position the step writes, steps
        # left to dispatch].  ``_active[slot]`` on the device is true
        # exactly for these.  A budget's end is known here, at
        # dispatch; an EOS only at the fetch, one step late.  A block
        # server's row: slot -> [position of its open block, tokens
        # left to dispatch, open positions left in the block, tokens
        # the block yields (its length less a prompt's remainder), 0
        # or, while the block before it awaits a lane, 1 + the passes
        # the row has sat out for one].  ``_active`` is not kept for
        # such a server: a pass's rows are made from this.
        self._run: dict[int, list[int]] = {}
        # The one decode step in flight (dispatched, not fetched).
        self._flying: _InFlight | None = None
        self._pending: list[tuple[int, list[int], int]] = []
        self._next_id = 0
        self.outputs: dict[int, list[int]] = {}
        # A block server's record beside ``outputs``, token for token:
        # the pass of its block at which each was fixed.
        self.fixed_at: dict[int, list[int]] = {}
        self.prompts: dict[int, list[int]] = {}
        self._finished: set[int] = set()
        # Interleaved chunked prefill (ISSUE 17): slots whose prompt
        # is still streaming in, insertion-ordered.  Each step()
        # advances AT MOST ONE chunk of the oldest entry before
        # decoding, so a long prompt can never starve active streams'
        # TPOT — prefill work per tick is bounded by prefill_chunk.
        self._interleave = bool(interleave_prefill)
        self._prefilling: dict[int, list] = {}   # slot -> [rid, prompt,
        #                                          budget, written]
        # What :meth:`take_account` reports, cumulative.  Prompt tokens
        # written by prefill and tokens emitted by decode (ISSUE 18);
        self.prefill_tokens_total = 0
        self.decode_tokens_total = 0
        # bytes of K and V pages the decode steps' attention fetched
        # (a count a kind of K/V; ``kv_read_bytes_total`` their sum)
        # and the steps that ran, counted on the host from the rows'
        # positions at dispatch and added when the step's tokens are
        # fetched, as everything of a step is (so a ratio over steps
        # has both its ends from the same steps); of those steps, the
        # ones fetched with their successor already dispatched; bytes
        # of per-row state those steps read and wrote;
        self.kv_read_bytes_by_kind = [0] * len(self._kinds)
        self.decode_steps_total = 0
        self.ahead_steps_total = 0
        self.state_bytes_total = 0
        # keys the prefill chunk programs attended (live pages x
        # block, from each chunk's ``start`` and ``length``; the
        # windowed kind's where there are several) and the chunk
        # programs run; of those, the programs that ran the layers
        # past the shared K/V (a model whose prefill runs them on the
        # prompt's last token only: one a prompt), and the shared
        # layer's keys those attended;
        self.prefill_keys_total = 0
        self.prefill_chunks_total = 0
        self.cross_decoder_runs_total = 0
        self.cross_decoder_keys_total = 0
        # a block server's row-passes: a row's denoising passes, the
        # blocks that reached their request, the positions the
        # denoising passes fixed, the commits that rode a lane of
        # another block's pass, and the row-passes sat out for want of
        # a lane (counted, like a step, when the pass is fetched);
        self.denoise_passes_total = 0
        self.blocks_emitted_total = 0
        self.tokens_fixed_total = 0
        self.fused_commits_total = 0
        self.lane_waits_total = 0
        # seconds per phase of step() (and of submit()'s admission,
        # which is prefill), on this process's perf_counter;
        self.phase_s = dict.fromkeys(STEP_PHASES, 0.0)
        self._accounted = self._totals()
        # and, not cumulative, the routing load of the decode steps
        # since the last account (a config whose experts report one:
        # ``_routed``), fetched with each step's tokens: experts touched
        # summed over steps (each the mean over the expert layers), the
        # most rows one expert took in a step, rows routed a layer
        # summed over steps.
        self.moe_load = [0.0, 0.0, 0.0]
        # The gateway's sequence number of the tick being served: it
        # rides the phases' spans and profiler annotations and changes
        # nothing else.
        self.tick: int | None = None

        self._prefill_fn = self._make_prefill()
        self._step_fn = self._jit_step()

    # ---- jitted programs -------------------------------------------------

    # Every jitted serving program carries a name that says what it is
    # (``jit_nbd_decode_step*`` / ``jit_nbd_prefill*``): the profile's
    # "XLA Modules" line splits device time by it.

    def _make_prefill(self):
        """The prefill program, one forward over the pool itself with
        the slot's one-row block table: ``prompt`` (1, s_pad)
        right-padded, ``start`` the position of its first token (0 for
        a whole prompt; a chunk's offset), ``length`` its real tokens;
        returns (pool, logits at the last REAL token).  Each layer
        writes the chunk's new entries into the row's pages where they
        lie and attends through the table over the keys the row holds
        (:class:`~.paged_kv.PagedKV`): what a chunk costs goes with
        ``start + length``, not with ``max_len``, and both are data,
        so one compile a chunk shape serves every slot, every
        (re)allocation and every offset.  token_mask keeps the pad
        positions out of MoE expert dispatch (they would consume
        capacity slots and could evict real prompt tokens); last_index
        gathers the hidden state at the last REAL token before the
        lm_head, so pads never touch the (d_model x vocab) matmul
        either.  The wrapper resolves the slot's table host-side and
        counts the keys the program attends
        (:attr:`prefill_keys_total`)."""
        cfg, mesh, ep_axis = self._cfg, self._mesh, self._ep_axis

        def nbd_prefill_paged(params, pool, row_ids, prompt, start,
                              length, slot=None, final=True):
            """``slot`` and ``final`` are what only a model with
            per-row state is told: the row, and whether the chunk ends
            its prompt (static: two programs a chunk shape, and one
            that does not end it has no logits)."""
            s_pad = prompt.shape[1]
            mask = (jnp.arange(s_pad)[None, :] < length)
            logits, pool = forward_with_cache(
                params, prompt, pool, start, cfg, mesh=mesh,
                ep_axis=ep_axis, token_mask=mask,
                last_index=(length - 1)[None],
                block_table=row_ids[None], slot=slot, final=final)
            return pool, (None if logits is None else logits[0, 0])

        # The pool is donated: admission updates it in place.  One jit
        # serves every prompt bucket — jax.jit retraces (and caches)
        # per input shape, so padding to pad_to multiples bounds the
        # compile count.
        jit_fn = jax.jit(nbd_prefill_paged, donate_argnums=(1,),
                         static_argnames=("final",))

        def wrapper(params, pool, prompt, slot: int, start: int,
                    length: int, final: bool = True):
            # Host integers in, never device scalars read back: a
            # read would wait for the step in flight, and the chunk
            # would then be launched with the chip idle.
            self.prefill_keys_total += self._chunk_keys(start, length)
            self.prefill_chunks_total += 1
            args = (params, pool, self._paged.device_row(slot), prompt,
                    np.int32(start), np.int32(length))
            if self._blocks:
                # nothing is sampled from a prompt's logits: no head
                return jit_fn(*args, final=False)
            if not self._hybrid:
                return jit_fn(*args)
            if final:
                self.cross_decoder_runs_total += 1
                self.cross_decoder_keys_total += start + length
            return jit_fn(*args, slot=np.int32(slot), final=final)

        wrapper.program = jit_fn    # to lower it without a live slot
        return wrapper

    def _chunk_keys(self, start: int, length: int) -> int:
        """Keys a prefill chunk program attends, a layer: whole pages,
        from the page of its first token's window to the page of its
        last real token (as :meth:`_step_kv_read_bytes` counts a
        step's), by the kind of K/V every token of a chunk attends
        (the last kind: where a model has several, the others' layers
        run on a prompt's last token only)."""
        bt = self._paged.block_tokens
        last = (start + length - 1) // bt
        first = self._first_live_page(self._kinds[-1], start)
        return (last - first + 1) * bt

    def _first_live_page(self, kind: _KVKind, pos: int) -> int:
        """The first page a query at ``pos`` attends in K/V of
        ``kind``: its window's."""
        if not kind.window:
            return 0
        return max(0, pos + 1 - kind.window) // self._paged.block_tokens

    def _jit_step(self):
        """The decode step over the physical pool, which it consumes
        where it lies (each layer writes its one new token per slot
        into its page — inactive slots into the trash block — and
        attends through the block table; see
        :class:`~.paged_kv.PagedKV`).  The pool is donated and updated
        in place."""
        cfg, mesh, ep_axis = self._cfg, self._mesh, self._ep_axis
        temperature, top_k, top_p = (self._temperature, self._top_k,
                                     self._top_p)
        routed = self._routed
        if self._blocks:
            def nbd_denoise_step_paged(params, pool, table, lens, block,
                                       active, key):
                """One pass over every row's open block and the commit
                lanes ``block["lane"]`` names -> (pool, lens, the rows'
                blocks, what the pass left of them for the host, the
                routing load): one forward over the ``L`` tokens of
                each (:func:`~.sdar.with_lanes`), whose K/V land in
                their rows' pages, the head over the open blocks
                alone, then :func:`~.sdar.denoise`.  ``key`` is
                unused: a block server is greedy."""
                tokens, at, rows, taking, lens = sdar.with_lanes(
                    block, lens, table, active, cfg)
                logits, pool, load = forward_with_cache(
                    params, tokens, pool, at, cfg, row_mask=taking,
                    block_table=rows, with_moe_load=True,
                    head_rows=active.shape[0])
                block, out = sdar.denoise(logits, block, active, cfg)
                return pool, lens, block, out, load

            return jax.jit(nbd_denoise_step_paged, donate_argnums=(1,))

        def nbd_decode_step_paged(params, pool, table, lens, last,
                                  active, key):
            """-> (pool, lens, next tokens, the step's routing load
            where the config's experts report one, else None)."""
            logits, pool, *load = forward_with_cache(
                params, last[:, None], pool, lens, cfg, mesh=mesh,
                ep_axis=ep_axis, row_mask=active, block_table=table,
                with_moe_load=routed)
            with jax.named_scope("sample"):
                nxt = _sample(logits[:, -1], temperature, key, top_k,
                              top_p)
            nxt = jnp.where(active, nxt, last)
            lens = lens + active.astype(lens.dtype)
            return pool, lens, nxt, (load[0] if routed else None)

        return jax.jit(nbd_decode_step_paged, donate_argnums=(1,))

    def step_kernels(self) -> int:
        """Compiled Pallas (Mosaic) kernels in the decode-step program
        :meth:`step` runs, lowered at the live pool's shapes — 0 where
        kernels are interpreted (the CPU) or the step fell back to the
        einsum path.  Lowering only traces, so the donated pool is
        untouched."""
        return self._step_fn.lower(
            self._params, self._cache, self._paged.device_table(),
            self._lens, self._block if self._blocks else self._last,
            self._active, self._key).as_text().count("tpu_custom_call")

    # ---- host-side API ---------------------------------------------------

    def submit(self, prompt, max_new_tokens: int) -> int:
        """Queue a request; returns its id.  Admitted to a slot on this
        call if one is free, else at the next :meth:`step`."""
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got "
                             f"{max_new_tokens}")
        if len(prompt) + max_new_tokens > self._T:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens "
                f"({max_new_tokens}) exceeds max_len {self._T}")
        rid = self._next_id
        self._next_id += 1
        self.prompts[rid] = prompt
        self.outputs[rid] = []
        if self._blocks:
            self.fixed_at[rid] = []
        self._pending.append((rid, prompt, max_new_tokens))
        self._admit_as_prefill(time.perf_counter())
        return rid

    def _admit_as_prefill(self, t0: float) -> float:
        """:meth:`_admit_pending` under the ``prefill`` phase (an
        admission runs the prefill program and waits for its first
        token), from the instant ``t0``; returns the instant it
        ended."""
        with obs_spans.phase("serve/step/prefill", self.tick):
            self._admit_pending()
        t1 = time.perf_counter()
        self.phase_s["prefill"] += t1 - t0
        return t1

    def _bucket(self, n: int) -> int:
        return -(-n // self._pad_to) * self._pad_to

    def _sample_key(self):
        if self._temperature == 0.0:
            return self._key
        self._key, k = jax.random.split(self._key)
        return k

    def _prefill_segment(self, slot: int, tokens: list, start: int,
                         width: int, final: bool):
        """Run the prefill program over ``tokens`` at position
        ``start``, right-padded to ``width``; returns the logits at
        the last real token (``final``: the segment ends its prompt; a
        model told so returns none from one that does not).  The pad
        is clamped so the padded write never reaches past max_len."""
        width = min(width, self._T - start)
        seg = np.asarray(tokens + [0] * (width - len(tokens)),
                         np.int32)[None, :]
        self._cache, logits = self._prefill_fn(
            self._params, self._cache, seg, slot, start, len(tokens),
            **({"final": final} if self._hybrid else {}))
        return logits

    def _prefilled(self, prompt: list) -> int:
        """Prompt tokens the prefill programs write: all of them, or
        under a block-causal mask the prompt's whole blocks (the
        remainder is seated in the row's first block, so no real
        query sees a key that a pad wrote)."""
        return len(prompt) // self._L * self._L

    def _run_prefill(self, prompt: list, slot: int):
        """Prefill one slot with a whole prompt; returns the
        last-real-token logits.

        Default: one bucketed whole-prompt forward (compile count
        bounded by distinct buckets).  With ``prefill_chunk`` and a
        longer prompt: fixed-size segments stream through ONE compiled
        (1, chunk) program at increasing cache offsets — admission
        activation memory drops from O(S_prompt) to O(chunk) and long
        prompts stop minting per-bucket compiles.  The final segment
        (padded to the chunk) carries the logits; a causal forward
        makes chunked and single-shot prefill the same computation
        (same argument as :func:`~.generate.prefill_chunked`)."""
        ck, n = self._prefill_chunk, self._prefilled(prompt)
        if not n:
            return None         # shorter than a block: nothing to write
        if ck is None or n <= ck:
            return self._prefill_segment(slot, prompt[:n], 0,
                                         self._bucket(n), True)
        for start in range(0, n, ck):
            logits = self._prefill_segment(
                slot, prompt[start:min(start + ck, n)], start, ck,
                start + ck >= n)
        return logits

    def _admit_pending(self) -> None:
        while self._pending and self._free:
            rid, prompt, budget = self._pending[0]
            slot = self._free[0]
            # Worst-case block reservation at admission, so a stream
            # can never stall mid-decode on allocation.  Exhaustion
            # leaves the request PENDING — it admits when finishing
            # streams free blocks.  The gateway's accounting allocator
            # normally prevents reaching this; it is the worker-side
            # backstop.
            try:
                self._paged.alloc(slot, len(prompt) + budget)
            except BlocksExhausted:
                break
            self._pending.pop(0)
            self._free.pop(0)
            if (self._interleave
                    and len(prompt) > self._prefill_chunk):
                # Long prompt: stream it in chunk-by-chunk across
                # decode ticks instead of stalling the batch for one
                # monolithic prefill.  The slot is reserved (and its
                # blocks held) but stays inactive until the last
                # chunk.
                self._prefilling[slot] = [rid, prompt, budget, 0]
                continue
            self.prefill_tokens_total += self._prefilled(prompt)
            self._start_stream(slot, rid, prompt, budget,
                               self._run_prefill(prompt, slot))

    def _start_stream(self, slot: int, rid: int, prompt: list[int],
                      budget: int, last_logits) -> None:
        """The end of an admission: sample the stream's first token
        from the prompt's last logits, then finish the request or
        activate its slot.  A block server samples nothing here: it
        seats the prompt's remainder in the row's first block."""
        if self._blocks:
            return self._seat_block(slot, rid, prompt, budget)
        tok = int(_sample(last_logits[None], self._temperature,
                          self._sample_key(), self._top_k,
                          self._top_p)[0])
        self.outputs[rid].append(tok)
        self._lens = self._lens.at[slot].set(len(prompt))
        self._last = self._last.at[slot].set(tok)
        if budget == 1 or (self._eos is not None and tok == self._eos):
            self._finish(slot, rid)
        else:
            self._slot_req[slot] = rid
            self._budget[rid] = budget - 1
            self._run[slot] = [len(prompt), budget - 1]
            self._active = self._active.at[slot].set(True)

    def _seat_block(self, slot: int, rid: int, prompt: list[int],
                    budget: int) -> None:
        """A block server's end of an admission: the row's first block
        is the prompt's remainder (fixed) followed by masks, at the
        position its whole blocks end."""
        at = self._prefilled(prompt)
        self._block = sdar.seat_block(self._block, slot, prompt[at:],
                                      self._cfg)
        self._lens = self._lens.at[slot].set(at)
        self._slot_req[slot] = rid
        self._budget[rid] = budget
        opened = self._L - (len(prompt) - at)
        self._run[slot] = [at, budget, opened, opened, 0]

    def _finish(self, slot: int, rid: int) -> None:
        """Free the slot and its pages.  A step in flight may still
        run the row: its token is dropped at the fetch (the slot is no
        longer ``rid``'s) and its write lands in pages the row had
        reserved, before any program of the slot's next request
        (program order: they consume the pool that step returns)."""
        self._finished.add(rid)
        self._slot_req.pop(slot, None)
        self._budget.pop(rid, None)
        if self._run.pop(slot, None) is not None and not self._blocks:
            self._active = self._active.at[slot].set(False)
        self._free.append(slot)
        self._paged.free(slot)

    def _advance_prefill(self) -> None:
        """Advance AT MOST ONE chunk of the oldest mid-prefill prompt
        — the per-tick prefill work bound that keeps long prompts from
        starving active streams' TPOT.  The final (possibly partial)
        chunk samples the first token and activates the slot; the
        segmentation matches :meth:`_run_prefill` exactly, so the
        stream is bit-identical to a monolithic admission."""
        if not self._prefilling:
            return
        slot, st = next(iter(self._prefilling.items()))
        rid, prompt, budget, written = st
        ck = self._prefill_chunk
        end = self._prefilled(prompt)
        seg = prompt[written:min(written + ck, end)]
        logits = self._prefill_segment(slot, seg, written, ck,
                                       written + ck >= end)
        self.prefill_tokens_total += len(seg)
        st[3] = written + len(seg)
        if st[3] < end:
            return
        del self._prefilling[slot]
        self._start_stream(slot, rid, prompt, budget, logits)

    def cancel(self, rid: int) -> bool:
        """Abort an in-flight request NOW: drop it from the pending
        queue, the prefill stream, or its active slot, freeing the
        slot and its KV blocks.  Returns False for
        unknown/already-finished ids.  The shed/release path uses
        this — a shed request must not pin blocks until its stream
        would have ended."""
        for i, (r, _p, _b) in enumerate(self._pending):
            if r == rid:
                self._pending.pop(i)
                self._finished.add(rid)
                return True
        for slot, st in list(self._prefilling.items()):
            if st[0] == rid:
                del self._prefilling[slot]
                self._finish(slot, rid)
                return True
        for slot, r in list(self._slot_req.items()):
            if r == rid:
                self._finish(slot, rid)
                return True
        return False

    def step(self) -> dict[int, list[int]]:
        """Dispatch the next decode step, then fetch and emit the one
        before it; returns {request_id: tokens emitted by this call} —
        one token per request the fetched step ran.  In order: admit
        pending requests and advance at most one mid-prefill chunk
        (interleave mode); dispatch step n + 1 for the rows of
        :attr:`_run`, from the device arrays step n returned; fetch
        step n's tokens, computed already or computing ahead of what
        was just queued; emit them.  So one step stays in flight from
        call to call (and from tick to tick: the chip works on it
        while the reply travels), the first call of a busy spell
        emits nothing, and a call with no row left to run drains the
        step in flight.  Each phase (:data:`STEP_PHASES`) adds its
        seconds to :attr:`phase_s`; the call's phases telescope."""
        ph, tick = self.phase_s, self.tick
        t0 = time.perf_counter()
        with obs_spans.phase("serve/step/prefill", tick):
            self._admit_pending()
            self._advance_prefill()
        t1 = time.perf_counter()
        ph["prefill"] += t1 - t0
        flying = self._flying
        if flying is None and not self._run:
            return {}
        with obs_spans.phase("serve/step/dispatch", tick):
            # Returns before the chip is done.
            self._flying = self._dispatch_step() if self._run else None
        t2 = time.perf_counter()
        ph["dispatch"] += t2 - t1
        with obs_spans.phase("serve/step/sync", tick):
            # The host blocked on the chip: the wait for step n, with
            # step n + 1 queued behind it.
            toks, load = (jax.device_get((flying.tokens, flying.load))
                          if flying else (None, None))
        t3 = time.perf_counter()
        ph["sync"] += t3 - t2
        with obs_spans.phase("serve/step/emit", tick):
            emitted = self._emit_step(flying, toks, load) if flying else {}
        t4 = time.perf_counter()
        ph["emit"] += t4 - t3
        if self._pending:
            self._admit_as_prefill(t4)
        return emitted

    def _dispatch_step(self) -> _InFlight:
        """Enqueue one decode step over the rows of :attr:`_run` and
        start the copy of what the host will fetch, so that the fetch
        does not queue behind programs dispatched later.  A row whose
        budget this step ends leaves :attr:`_run` here, before the
        step's tokens are known."""
        if self._blocks:
            return self._dispatch_pass()
        rows = {slot: self._slot_req[slot] for slot in self._run}
        kv_bytes = self._step_kv_read_bytes()
        self._cache, self._lens, self._last, load = self._step_fn(
            self._params, self._cache, self._paged.device_table(),
            self._lens, self._last, self._active, self._sample_key())
        self._last.copy_to_host_async()
        if load is not None:
            load.copy_to_host_async()
        for slot, st in list(self._run.items()):
            st[0] += 1
            st[1] -= 1
            if not st[1]:
                del self._run[slot]
                self._active = self._active.at[slot].set(False)
        return _InFlight(self._last, load, rows, kv_bytes)

    def _dispatch_pass(self) -> _InFlight:
        """A block server's :meth:`_dispatch_step`: one pass over the
        rows of :attr:`_run`.  What each row's pass is follows from the
        schedule alone.  A row whose block before this one awaits its
        commit books a lane, the longest wait first; with none free it
        sits the pass out: nothing of it runs, so its schedule stands.
        Every other row fixes ``fixed_per_pass`` open positions, and
        the pass that fixes a block's last finishes it: the block
        leaves at that pass's fetch, and the row opens its next block
        with a lane to book, or, its budget spent, leaves with nothing
        to commit."""
        L, per_pass, run = self._L, self._cfg.fixed_per_pass, self._run
        lane = np.full(self._block["lane"].shape, self._B, np.int32)
        asking = sorted((slot for slot, st in run.items() if st[4]),
                        key=lambda slot: -run[slot][4])
        booked, sitting = asking[:lane.size], set(asking[lane.size:])
        lane[:len(booked)] = booked
        for slot in sitting:
            run[slot][4] += 1
        rows = {slot: self._slot_req[slot] for slot in run
                if slot not in sitting}
        active = np.zeros((self._B,), bool)
        active[list(rows)] = True
        # an open block's queries share its last key, a lane's the last
        # key of the block before the open one
        kv_bytes = self._step_kv_read_bytes(
            [run[slot][0] + L - 1 for slot in rows]
            + [run[slot][0] - 1 for slot in booked])
        self._cache, self._lens, self._block, out, load = self._step_fn(
            self._params, self._cache, self._paged.device_table(),
            self._lens, {**self._block, "lane": lane}, active, self._key)
        fetched = (out["tokens"], out["when"])
        for a in (*fetched, load):
            a.copy_to_host_async()
        blocks, fixed = {}, 0
        for slot in rows:
            st = run[slot]
            n = min(per_pass, st[2])
            st[2] -= n
            st[4] = 0
            fixed += n
            if st[2]:
                continue
            blocks[slot] = L - st[3]            # the block's last pass
            st[1] -= st[3]
            if st[1] <= 0:
                del run[slot]
            else:
                st[0] += L
                st[2] = st[3] = L
                st[4] = 1
        return _InFlight(fetched, load, rows, kv_bytes, blocks, fixed,
                         len(booked), len(sitting))

    def _step_kv_read_bytes(self, last_keys=None) -> tuple:
        """Bytes of K and V pages the next decode step's attention
        fetches, a count a kind of K/V over the layers that read it:
        for every row the kernel runs, the pages from the window's
        first to the one its last key lies in, once a row.
        ``last_keys``: the last position each kernel row attends (the
        rows of :attr:`_run`, each at its new token, where not
        given)."""
        bt = self._paged.block_tokens
        if last_keys is None:
            last_keys = [st[0] for st in self._run.values()]
        return tuple(
            kind.page_bytes * sum(
                pos // bt - self._first_live_page(kind, pos) + 1
                for pos in last_keys)
            for kind in self._kinds)

    def _emit_step(self, step: _InFlight, toks, load) -> dict:
        """Count a fetched step and emit its tokens: a row's only if
        the request it was dispatched for still holds the slot (an EOS
        is learned one step late, a cancel at any time: the surplus
        token is dropped)."""
        for i, n in enumerate(step.kv_bytes):
            self.kv_read_bytes_by_kind[i] += n
        self.state_bytes_total += self._state_bytes
        self.decode_steps_total += 1
        self.ahead_steps_total += self._flying is not None
        if load is not None:
            touched, most, rows = (float(v) for v in load)
            self.moe_load[0] += touched
            self.moe_load[1] = max(self.moe_load[1], most)
            self.moe_load[2] += rows
        if self._blocks:
            return self._emit_pass(step, *toks)
        return {rid: self._emit(slot, rid, [int(toks[slot])])
                for slot, rid in step.rows.items()
                if self._slot_req.get(slot) == rid}

    def _emit_pass(self, step: _InFlight, tokens, when) -> dict:
        """Count a fetched pass and emit the blocks it finished: the
        new tokens of a row's block (a prompt's remainder is not
        output), each with the pass that fixed it.  Their tokens are
        final here, a pass before a lane commits them."""
        self.denoise_passes_total += len(step.rows)
        self.tokens_fixed_total += step.fixed
        self.fused_commits_total += step.fused
        self.lane_waits_total += step.waits
        emitted = {}
        for slot, first in step.blocks.items():
            rid = step.rows[slot]
            if self._slot_req.get(slot) != rid:
                continue
            self.blocks_emitted_total += 1
            toks = self._emit(slot, rid,
                              [int(t) for t in tokens[slot][first:]])
            self.fixed_at[rid].extend(
                int(w) for w in when[slot][first:first + len(toks)])
            emitted[rid] = toks
        return emitted

    def _emit(self, slot: int, rid: int, toks: list[int]) -> list[int]:
        """Budget-then-EOS truncation + bookkeeping for an emission —
        the ONE definition of the cut semantics, and the one place a
        token enters :attr:`outputs` after admission (the device can
        overshoot: the surplus is discarded here or, for a step in
        flight when the slot was freed, in :meth:`_emit_step`, and the
        slot's stale device state dies with the slot)."""
        toks = toks[: self._budget[rid]]
        if self._eos is not None and self._eos in toks:
            toks = toks[: toks.index(self._eos) + 1]
        self.outputs[rid].extend(toks)
        self.decode_tokens_total += len(toks)
        self._budget[rid] -= len(toks)
        if (self._budget[rid] == 0
                or (self._eos is not None and toks
                    and toks[-1] == self._eos)):
            self._finish(slot, rid)
        return toks

    def release(self, rid: int) -> list[int]:
        """Drop a finished request's host-side record (prompt, output,
        finished flag) and return its tokens — the eviction API that
        keeps a long-running server's host memory bounded.  Unknown or
        already-released ids raise (a silent [] would be
        indistinguishable from a request that emitted nothing)."""
        if rid in self._budget \
                or any(r == rid for r, _, _ in self._pending) \
                or any(st[0] == rid
                       for st in self._prefilling.values()):
            raise ValueError(f"request {rid} is still in flight")
        if rid not in self.outputs:
            raise KeyError(f"unknown or already-released request {rid}")
        toks = self.outputs.pop(rid)
        self.fixed_at.pop(rid, None)
        self.prompts.pop(rid, None)
        self._finished.discard(rid)
        return toks

    def done(self) -> bool:
        """Nothing left to do: a step in flight is work, since its
        tokens are not emitted yet."""
        return (not self._slot_req and not self._pending
                and not self._prefilling and self._flying is None)

    def run_until_done(self, max_steps: int | None = None):
        """Drive :meth:`step` until every request finishes; returns
        ``self.outputs``."""
        steps = 0
        while not self.done():
            self.step()
            steps += 1
            if max_steps is not None and steps > max_steps:
                raise RuntimeError(
                    f"server not drained after {max_steps} steps")
        return self.outputs

    @property
    def finished(self):
        return set(self._finished)

    @property
    def n_active(self) -> int:
        return len(self._slot_req)

    def prefill_progress(self) -> dict[int, tuple[int, int]]:
        """Mid-prefill streams: ``{request_id: (tokens_written,
        prompt_len)}`` — the serve_step reply forwards this so the
        gateway's observatory can annotate prefill[chunk i/n]."""
        return {st[0]: (st[3], len(st[1]))
                for st in self._prefilling.values()}


    @property
    def kv_read_bytes_total(self) -> int:
        return sum(self.kv_read_bytes_by_kind)

    @property
    def _page_bytes(self) -> int:
        """One live page of every kind of K/V, over its readers."""
        return sum(k.page_bytes for k in self._kinds)

    def _totals(self) -> dict:
        return {"pf": self.prefill_tokens_total,
                "dc": self.decode_tokens_total,
                "steps": self.decode_steps_total,
                "keys": self.prefill_keys_total,
                "chunks": self.prefill_chunks_total,
                "ahead": self.ahead_steps_total,
                "state": self.state_bytes_total,
                "xdec": self.cross_decoder_runs_total,
                "xkeys": self.cross_decoder_keys_total,
                "dn:passes": self.denoise_passes_total,
                "dn:blocks": self.blocks_emitted_total,
                "dn:fixed": self.tokens_fixed_total,
                "dn:fused": self.fused_commits_total,
                "dn:waits": self.lane_waits_total,
                **{"kv:" + k.name: n for k, n in
                   zip(self._kinds, self.kv_read_bytes_by_kind)},
                **{"ph:" + k: v for k, v in self.phase_s.items()}}

    def take_account(self) -> dict:
        """This server's part of a tick's account: what it did since
        the last call, under the keys of a ``serve_step`` reply's
        ``tick`` block, which the worker's handler completes with its
        own and :meth:`~..observability.servingobs.ServingObservatory.
        note_tick` reads — the two ends a new quantity is added at.
        ``pf`` / ``dc``: prompt tokens prefilled, tokens decoded;
        ``ph``: seconds in each of :data:`STEP_PHASES`; ``kvr``: bytes
        of K and V pages the decode steps' attention fetched, and the
        steps; ``ahd``: of those steps, the ones whose tokens were
        fetched with the next step already dispatched behind them,
        and the steps; ``pfk``: keys the prefill chunk programs
        attended, and the programs run; ``moe`` (a config that
        routes): experts touched summed over those steps, most rows on
        one expert, rows routed a layer summed.  A model with several
        kinds of cache adds ``kvk``: ``kvr``'s bytes a kind of K/V;
        ``st``: bytes of per-row state the decode steps read and
        wrote, and the steps; ``xdec``: chunk programs that ran the
        layers past the shared K/V, the chunk programs, and the shared
        layer's keys the former attended.  A block server adds
        ``dn``: its rows' denoising passes, the commit passes that
        took a row-pass of their own (0: a commit rides a lane of
        another block's pass), the blocks that reached a request, the
        positions fixed, the commits the lanes carried and the
        row-passes sat out for want of a lane (a step is then a pass
        over every row's block: ``kvr`` counts a row's pages once a
        pass and once more for its lane, ``moe`` the lanes' routed
        rows too, ``dc`` the tokens that left with a finished block).
        A decode step counts, in
        all of these, when its tokens are fetched: the step in flight
        at the call is the next account's."""
        now = self._totals()
        d = {k: v - self._accounted[k] for k, v in now.items()}
        self._accounted = now
        kv = {k.name: d["kv:" + k.name] for k in self._kinds}
        account = {"pf": d["pf"], "dc": d["dc"],
                   "kvr": [sum(kv.values()), d["steps"]],
                   "pfk": [d["keys"], d["chunks"]],
                   "ahd": [d["ahead"], d["steps"]],
                   "ph": {k: d["ph:" + k] for k in self.phase_s}}
        if self._hybrid:
            account.update(kvk=kv, st=[d["state"], d["steps"]],
                           xdec=[d["xdec"], d["chunks"], d["xkeys"]])
        if self._blocks:
            account["dn"] = [d["dn:passes"], 0, d["dn:blocks"],
                             d["dn:fixed"], d["dn:fused"], d["dn:waits"]]
        if self._routed:
            account["moe"] = [round(v, 3) for v in self.moe_load]
            self.moe_load = [0.0, 0.0, 0.0]
        return account

    def kv_snapshot(self) -> dict:
        """The pool's block occupancy (``{"blocks", "block_tokens",
        "used", "free", "owners"}``) — the worker's heartbeat telemetry
        and status surfaces read this.  A model with several kinds of
        cache adds ``kinds``: the blocks above are its ``full`` layers';
        a ``window`` ring (where it has window layers) and a row of
        ``state`` belong to a slot, so their rows in use are the slots
        taken."""
        snap = self._paged.snapshot()
        if self._hybrid:
            from .hybrid import cache_bytes_by_kind
            size = cache_bytes_by_kind(self._cache)
            taken = self._B - len(self._free)
            snap["kinds"] = {"state": {"rows": self._B, "used": taken,
                                       "bytes": size["ssm"]}}
            for kind in self._kinds:
                if kind.window is None:
                    snap["kinds"][kind.name] = {
                        "blocks": snap["blocks"], "used": snap["used"],
                        "bytes": size[kind.name]}
                    continue
                ring = (self._cache[kind.name]["k"].shape[1] - 1) // self._B
                snap["kinds"][kind.name] = {
                    "rows": self._B, "used": taken, "ring_pages": ring,
                    "bytes": size[kind.name]}
        return snap
