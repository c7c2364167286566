"""Continuous-batching decode server: staggered admission over a fixed
slot pool, one shared forward per step.

The reference framework has no serving path at all (its users call HF
``generate`` per prompt in cells); this is the TPU-native serving loop
the KV-cache machinery was built to support.  Design:

* **Static shapes, dynamic occupancy.**  The cache is one
  ``(L, max_batch, Hkv, max_len, D)`` pool; a request occupies a batch
  *slot* for its lifetime.  Admission, completion, and re-use never
  change any array shape — XLA compiles exactly two programs (prefill
  per prompt bucket, one decode step) no matter how requests arrive.
* **Per-slot cache pointers.**  The decode step runs ALL slots in one
  ``forward_with_cache`` call with a per-row ``(B,)`` ``cache_len`` —
  the same machinery batched speculative decoding uses
  (speculative.py) — so requests at different depths share every
  matmul.  Decode-step cost is one B-row forward regardless of how
  staggered the batch is: that sharing is the whole point of
  continuous batching.
* **Inactive slots freeze exactly like finished speculative streams:**
  their advance is masked to zero, their (idempotent) cache writes
  land at a frozen position, and for MoE configs ``row_mask`` keeps
  them out of expert capacity dispatch, so an empty or finished slot
  never perturbs a live one.
* **Prefill-on-admit** runs the prompt as a single-row forward into
  the slot's cache rows, right-padded to a length *bucket* (one
  compile per bucket, ``pad_to`` granularity).  Pad positions write
  garbage cache slots beyond the prompt — harmless by the write-then-
  attend order: a decode step at position ``p`` overwrites slot ``p``
  before any query attends it, and attention masks ``t <= p``.  Pads
  are masked out of MoE expert dispatch (``token_mask``) so they can
  never consume capacity slots and evict real prompt tokens, and the
  lm_head runs only at the last real position (``last_index``).

**Speculative serving** (``draft_params``/``draft_cfg``/``gamma``):
every step runs one draft-propose / target-verify round
(:func:`~.speculative.spec_round`) — the draft proposes ``gamma``
tokens per slot, ONE batched target forward verifies every slot's
candidates, and each active request emits its accepted prefix + the
correction/bonus token (1..gamma+1 tokens per step, diverging freely
per slot).  Greedy speculative serving reproduces the target's own
greedy decode per request — the draft only affects speed.  Budget
and EOS cut a stream mid-round by truncating its emission; the
slot's stale device state dies with the slot.

Greedy serving reproduces a standalone :func:`~.generate.generate`
call per request: admission order, batch occupancy, and other
requests' traffic cannot change any request's tokens for the dense
family.  In float32 the streams are bit-identical (asserted in the CPU
tests; on a v5e at ``highest`` matmul precision through paged KV +
chunked prefill, asserted by ``chip_smoke.py``).  In bf16 on the TPU
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

import jax
import jax.numpy as jnp

from ..observability import spans as obs_spans
from .generate import (_sample, forward_with_cache, init_kv_cache,
                       kv_cache_shardings)
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


class DecodeServer:
    """Slot-pool continuous-batching server around one model.

    Host-side orchestration (admission queue, completion, output
    collection) wraps two jitted device programs: a per-bucket prefill
    and the shared decode step.  Use::

        srv = DecodeServer(params, cfg, max_batch=8, max_len=512)
        rid = srv.submit([1, 2, 3], max_new_tokens=16)
        while not srv.done():
            srv.step()   # plain: 1 token per active request;
                         # speculative mode: 1..gamma+1 per request
        tokens = srv.outputs[rid]

    ``prefill_chunk=N`` (dense family) admits long prompts in
    fixed-size segments through one compiled (1, N) program —
    admission activation memory O(N) instead of O(S_prompt), no
    per-bucket compiles (see :meth:`_run_prefill`).

    :meth:`cache_prefix` registers a shared system prompt: its KV
    block is prefilled once, and matching submissions admit by one
    HBM copy + suffix-only prefill (see the method docstring).

    ``kv_block_tokens=N`` switches the cache to **paged** storage
    (ISSUE 17, :mod:`.paged_kv`): the pool holds ``kv_blocks`` fixed-
    size physical blocks, each request reserves
    ``ceil((prompt + max_new) / N)`` of them at admission, and
    capacity is measured in blocks rather than slots — short requests
    stop reserving ``max_len`` of KV each.  Exhaustion leaves
    requests pending (never a silent wedge — the gateway's accounting
    allocator issues the explicit verdicts).  ``interleave_prefill=
    True`` (requires ``prefill_chunk``) admits long prompts one chunk
    per :meth:`step` interleaved with decode, bounding the prefill
    work any single tick can add — the chunked-prefill TPOT
    guarantee.
    """

    def __init__(self, params, cfg: TransformerConfig, *,
                 max_batch: int, max_len: int,
                 temperature: float = 0.0, top_k: int | None = None,
                 top_p: float | None = None, eos_id: int | None = None,
                 kv_quantized: bool = False, mesh=None,
                 ep_axis: str = "ep", pad_to: int = 64, key=None,
                 draft_params=None, draft_cfg=None, gamma: int = 4,
                 prefill_chunk: int | None = None,
                 kv_block_tokens: int | None = None,
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
        if (draft_params is None) != (draft_cfg is None):
            raise ValueError("pass both draft_params and draft_cfg, "
                             "or neither")
        if kv_block_tokens is not None and kv_block_tokens < 1:
            raise ValueError(f"kv_block_tokens must be >= 1, got "
                             f"{kv_block_tokens}")
        if kv_block_tokens is None and kv_blocks is not None:
            raise ValueError("kv_blocks needs kv_block_tokens (paged "
                             "mode is enabled by the block size)")
        if kv_block_tokens is not None and draft_cfg is not None:
            # A speculative round writes gamma+1 positions per step;
            # the paged step writes exactly one token per slot and
            # attends one query.  Compose them with a multi-token
            # paged write and verify, not by silently corrupting
            # cross-block rounds.
            raise ValueError("paged KV serving does not compose with "
                             "speculative decoding yet")
        if interleave_prefill and prefill_chunk is None:
            raise ValueError("interleave_prefill needs prefill_chunk "
                             "(the per-step prefill work bound)")
        if draft_cfg is not None:
            if draft_cfg.vocab_size != cfg.vocab_size:
                raise ValueError("target and draft must share a "
                                 "vocabulary")
            if gamma < 1:
                raise ValueError(f"gamma must be >= 1, got {gamma}")
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
        from .mla import LatentMoEConfig
        self._routed = isinstance(cfg, LatentMoEConfig)
        self._params = params
        self._cfg = cfg
        self._mesh = mesh
        self._ep_axis = ep_axis
        self._kv_quantized = kv_quantized
        self._B = max_batch
        self._T = max_len
        self._pad_to = pad_to
        self._prefill_chunk = prefill_chunk
        self._temperature = temperature
        self._top_k = top_k
        self._top_p = top_p
        self._eos = eos_id
        self._key = key if key is not None else jax.random.PRNGKey(0)

        # Paged mode (ISSUE 17): the cache pool is (L, n_blocks+1,
        # Hkv, block_tokens, D) physical blocks instead of per-slot
        # max_len rows; self._cache holds the pool either way (it is
        # donated through the same jitted programs).
        if kv_block_tokens is not None:
            from .paged_kv import (PagedKVCache, make_paged_pool,
                                   reads_in_place)
            if kv_blocks is None:
                # Derived default: exactly the dense pool's capacity,
                # so paging with no explicit budget never refuses a
                # request the dense server would have taken.
                kv_blocks = max_batch * (
                    -(-max_len // kv_block_tokens))
            self._paged = PagedKVCache(
                slots=max_batch, max_len=max_len, n_blocks=kv_blocks,
                block_tokens=kv_block_tokens)
            self._cache = make_paged_pool(
                cfg, kv_blocks, kv_block_tokens, mesh=mesh,
                quantized=kv_quantized)
            # One page of K and V over all layers, in bytes: what a
            # step's attention fetches per live page of a slot
            # (``step`` sums them into ``kv_read_bytes_total``).
            self._page_bytes = sum(
                c.nbytes // c.shape[1]
                for c in jax.tree_util.tree_leaves(self._cache))
            # Bytes a decode step gathers from the pool into dense
            # views, all layers: 0 where the kernel reads the pool in
            # place, else every slot's whole block table once a layer
            # (the fallback's cost per step; a count from shapes).
            self.kv_view_bytes = (
                0 if reads_in_place(cfg, mesh) else
                self._page_bytes * self._paged.max_blocks * max_batch)
        else:
            self._paged = None
            self.kv_view_bytes = 0
            self._cache = init_kv_cache(cfg, max_batch, max_len,
                                        mesh=mesh,
                                        quantized=kv_quantized)
        self._lens = jnp.zeros((max_batch,), jnp.int32)
        self._last = jnp.zeros((max_batch,), jnp.int32)
        self._active = jnp.zeros((max_batch,), bool)

        # Speculative mode: a draft model proposes gamma tokens per
        # step, the target verifies them in ONE batched forward —
        # every step emits 1..gamma+1 tokens per active slot.
        self._draft_params = draft_params
        self._draft_cfg = draft_cfg
        self._gamma = gamma
        if draft_cfg is not None:
            self._cache_d = init_kv_cache(draft_cfg, max_batch,
                                          max_len, mesh=mesh,
                                          quantized=kv_quantized)
            self._lens_d = jnp.zeros((max_batch,), jnp.int32)
            self._prefill_d = self._make_prefill(draft_cfg)
            self._spec_fn = self._jit_spec_step()
            self._spec_many_fn = self._jit_spec_many()

        # Prefix cache: shared prompt prefixes prefilled ONCE into
        # dedicated 1-slot KV blocks; admission copies the block
        # (HBM-to-HBM, zero FLOPs) and prefills only the suffix.
        self._prefixes: dict[int, tuple] = {}    # pid -> (tokens, ...)
        self._next_pid = 0
        self._absorb_fn = jax.jit(
            lambda cache, pfx, slot: jax.tree_util.tree_map(
                lambda c, p: jax.lax.dynamic_update_slice(
                    c, p, (0, slot) + (0,) * (c.ndim - 2)),
                cache, pfx),
            donate_argnums=(0,))

        # Host-side bookkeeping.
        self._free = list(range(max_batch))
        self._slot_req: dict[int, int] = {}      # slot -> request id
        self._budget: dict[int, int] = {}        # request id -> remaining
        self._pending: list[tuple[int, list[int], int]] = []
        self._next_id = 0
        self.outputs: dict[int, list[int]] = {}
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
        # Utilization telemetry (ISSUE 18): cumulative prompt tokens
        # written by prefill vs tokens emitted by decode — the worker
        # differences successive snapshots to report each tick's
        # prefill/decode token split to the serving observatory.
        self.prefill_tokens_total = 0
        self.decode_tokens_total = 0
        # Paged pool: cumulative bytes of K and V pages the decode
        # steps' attention fetched and the steps that ran, counted on
        # the host from the active slots' lengths (the worker reports
        # each tick's deltas, as for the token counters).
        self.kv_read_bytes_total = 0
        self.decode_steps_total = 0
        # Paged pool: cumulative keys the prefill chunk programs
        # attended (live pages x block, from each chunk's ``start`` and
        # ``length``) and the chunk programs run.
        self.prefill_keys_total = 0
        self.prefill_chunks_total = 0
        # Routing load of the decode steps since :meth:`take_moe_load`
        # (a config whose experts report one: ``_routed``), fetched
        # with each step's tokens: experts touched summed over steps
        # (each the mean over the expert layers), the most rows one
        # expert took in a step, rows routed a layer summed over steps.
        self.moe_load = [0.0, 0.0, 0.0]
        # Cumulative seconds per phase of step() (and of submit()'s
        # admission, which is prefill), on this process's
        # perf_counter; the worker's serve_step handler reports each
        # tick's deltas.  ``tick`` is the gateway's sequence number of
        # the tick being served: it rides the phases' spans and
        # profiler annotations and changes nothing else.
        self.phase_s = dict.fromkeys(STEP_PHASES, 0.0)
        self.tick: int | None = None

        if self._paged is not None:
            self._prefill_fn = self._make_prefill_paged()
            self._step_fn = self._jit_step_paged()
            self._step_many_fn = None
        else:
            self._prefill_fn = self._make_prefill()
            self._step_fn = self._jit_step()
            self._step_many_fn = self._jit_step_many()

    # ---- jitted programs -------------------------------------------------

    def _make_prefill(self, cfg=None):
        cfg = cfg if cfg is not None else self._cfg
        mesh, ep_axis = self._mesh, self._ep_axis

        def nbd_prefill(params, cache, prompt, slot, start, length):
            """prompt (1, s_pad) right-padded; writes the slot's cache
            rows at offset ``start`` and returns (updated cache,
            logits at the segment's last REAL token).  ``start`` is 0
            for whole-prompt (bucketed) admission; chunked admission
            streams fixed-size segments at increasing offsets through
            this one compiled shape.  token_mask keeps the pad
            positions out of MoE expert dispatch (they would consume
            capacity slots and could evict real prompt tokens);
            last_index gathers the hidden state at the last REAL token
            before the lm_head, so pads never touch the
            (d_model x vocab) matmul either."""
            row = jax.tree_util.tree_map(
                lambda c: jax.lax.dynamic_slice_in_dim(c, slot, 1, 1),
                cache)
            s_pad = prompt.shape[1]
            mask = (jnp.arange(s_pad)[None, :] < length)
            logits, row = forward_with_cache(
                params, prompt, row, start, cfg, mesh=mesh,
                ep_axis=ep_axis, token_mask=mask,
                last_index=(length - 1)[None])
            cache = jax.tree_util.tree_map(
                lambda c, r: jax.lax.dynamic_update_slice_in_dim(
                    c, r, slot, 1), cache, row)
            return cache, logits[0, 0]                 # (V,)

        # The cache pool is donated: admission updates it in place
        # instead of copying (L, B, Hkv, max_len, D) per request.
        # One jit serves every prompt bucket — jax.jit retraces (and
        # caches) per input shape, so padding to pad_to multiples
        # bounds the compile count.
        return jax.jit(nbd_prefill, donate_argnums=(1,))

    def _make_step(self):
        cfg, mesh, ep_axis = self._cfg, self._mesh, self._ep_axis
        temperature, top_k, top_p = (self._temperature, self._top_k,
                                     self._top_p)

        # Every jitted serving program carries a name that says what
        # it is (``jit_nbd_decode_step*`` / ``jit_nbd_prefill*``): the
        # profile's "XLA Modules" line splits device time by it.
        routed = self._routed

        def nbd_decode_step(params, cache, lens, last, active, key,
                            table=None):
            """-> (cache, lens, next tokens, the step's routing load
            where the config's experts report one, else None)."""
            logits, cache, *load = forward_with_cache(
                params, last[:, None], cache, lens, cfg, mesh=mesh,
                ep_axis=ep_axis, row_mask=active, block_table=table,
                with_moe_load=routed)
            with jax.named_scope("sample"):
                nxt = _sample(logits[:, -1], temperature, key, top_k,
                              top_p)
            nxt = jnp.where(active, nxt, last)
            lens = lens + active.astype(lens.dtype)
            return cache, lens, nxt, (load[0] if routed else None)

        return nbd_decode_step

    def _jit_step(self):
        # Donated cache: the decode step rewrites the pool in place.
        return jax.jit(self._make_step(), donate_argnums=(1,))

    def _make_prefill_paged(self):
        """Paged prefill, shaped like the dense one so
        :meth:`_run_prefill` (bucketing + chunk streaming) drives both:
        one forward over the pool itself, with the slot's one-row block
        table.  Each layer writes the chunk's new entries into the
        row's pages where they lie and attends through the table over
        the keys the row holds (:class:`~.paged_kv.PagedKV`): what a
        chunk costs goes with ``start + length``, not with ``max_len``,
        and both are data, so one compile a chunk shape serves every
        slot, every (re)allocation and every offset.  The wrapper
        resolves the slot's table host-side and counts the keys the
        program attends (:attr:`prefill_keys_total`)."""
        cfg, mesh, ep_axis = self._cfg, self._mesh, self._ep_axis

        def nbd_prefill_paged(params, pool, row_ids, prompt, start,
                              length):
            s_pad = prompt.shape[1]
            mask = (jnp.arange(s_pad)[None, :] < length)
            logits, pool = forward_with_cache(
                params, prompt, pool, start, cfg, mesh=mesh,
                ep_axis=ep_axis, token_mask=mask,
                last_index=(length - 1)[None],
                block_table=row_ids[None])
            return pool, logits[0, 0]                  # (V,)

        jit_fn = jax.jit(nbd_prefill_paged, donate_argnums=(1,))

        def wrapper(params, pool, prompt, slot, start, length):
            self.prefill_keys_total += self._chunk_keys(int(start),
                                                        int(length))
            self.prefill_chunks_total += 1
            return jit_fn(params, pool,
                          self._paged.device_row(int(slot)), prompt,
                          start, length)

        wrapper.program = jit_fn    # to lower it without a live slot
        return wrapper

    def _chunk_keys(self, start: int, length: int) -> int:
        """Keys a prefill chunk program attends: whole pages, from the
        page of its first token's window to the page of its last real
        token (as :meth:`_step_kv_read_bytes` counts a step's)."""
        bt = self._paged.block_tokens
        last = (start + length - 1) // bt
        return (last - self._first_live_page(start) + 1) * bt

    def _first_live_page(self, pos: int) -> int:
        """The first page a query at ``pos`` attends: its window's."""
        window = getattr(self._cfg, "sliding_window", None)
        if not window:
            return 0
        return max(0, pos + 1 - window) // self._paged.block_tokens

    def _jit_step_paged(self):
        """The paged decode step: the SAME step computation over the
        physical pool, which it consumes where it lies (each layer
        writes its one new token per slot into its page — inactive
        slots into the trash block — and attends through the block
        table; see :class:`~.paged_kv.PagedKV`).  The pool is donated
        and updated in place."""
        step = self._make_step()

        def nbd_decode_step_paged(params, pool, table, lens, last,
                                  active, key):
            return step(params, pool, lens, last, active, key, table)

        return jax.jit(nbd_decode_step_paged, donate_argnums=(1,))

    def _jit_step_many(self):
        step = self._make_step()

        def nbd_decode_step_many(params, cache, lens, last, active,
                                 keys):
            def body(carry, k):
                cache, lens, last = carry
                cache, lens, nxt, _load = step(params, cache, lens,
                                               last, active, k)
                return (cache, lens, nxt), nxt

            (cache, lens, last), toks = jax.lax.scan(
                body, (cache, lens, last), keys)
            return cache, lens, last, toks        # toks (n, B)

        return jax.jit(nbd_decode_step_many, donate_argnums=(1,))

    def _jit_spec_many(self):
        from .speculative import spec_round

        cfg, dcfg = self._cfg, self._draft_cfg
        gamma, temperature = self._gamma, self._temperature
        mesh, ep_axis = self._mesh, self._ep_axis
        top_k, top_p = self._top_k, self._top_p
        T = self._T

        def nbd_decode_step_spec_many(params, draft_params, cache_t,
                                      lens_t, cache_d, lens_d, last,
                                      active, keys):
            def body(carry, key):
                cache_t, lens_t, cache_d, lens_d, last = carry
                # Self-freeze before the cache could overflow: a round
                # writes at positions < lens + gamma + 1.  submit()
                # guarantees prompt + budget + gamma + 1 <= max_len,
                # so a stream always reaches its budget before
                # freezing here (the freeze only stops budget-overrun
                # rounds whose tokens the host discards anyway).
                act = active & (lens_t + gamma + 1 <= T)
                (cache_t, lens_t, cache_d, lens_d, _k, cand, n_acc,
                 new_last) = spec_round(
                    params, draft_params, cfg, dcfg, gamma=gamma,
                    temperature=temperature, cache_t=cache_t,
                    len_t=lens_t, cache_d=cache_d, len_d=lens_d,
                    last_tok=last, key=key, active=act, mesh=mesh,
                    ep_axis=ep_axis, top_k=top_k, top_p=top_p)
                return ((cache_t, lens_t, cache_d, lens_d, new_last),
                        (cand, n_acc, act))

            carry = (cache_t, lens_t, cache_d, lens_d, last)
            (cache_t, lens_t, cache_d, lens_d, last), \
                (cands, n_accs, acts) = jax.lax.scan(body, carry, keys)
            return (cache_t, lens_t, cache_d, lens_d, last, cands,
                    n_accs, acts)

        return jax.jit(nbd_decode_step_spec_many,
                       donate_argnums=(2, 4))

    def _jit_spec_step(self):
        from .speculative import spec_round

        cfg, dcfg = self._cfg, self._draft_cfg
        gamma, temperature = self._gamma, self._temperature
        mesh, ep_axis = self._mesh, self._ep_axis
        top_k, top_p = self._top_k, self._top_p

        def nbd_decode_step_spec(params, draft_params, cache_t, lens_t,
                                 cache_d, lens_d, last, active, key):
            (cache_t, lens_t, cache_d, lens_d, key, cand, n_acc,
             new_last) = spec_round(
                params, draft_params, cfg, dcfg, gamma=gamma,
                temperature=temperature, cache_t=cache_t,
                len_t=lens_t, cache_d=cache_d, len_d=lens_d,
                last_tok=last, key=key, active=active, mesh=mesh,
                ep_axis=ep_axis, top_k=top_k, top_p=top_p)
            return cache_t, lens_t, cache_d, lens_d, cand, n_acc, \
                new_last

        # Both cache pools donated (updated in place each round).
        return jax.jit(nbd_decode_step_spec, donate_argnums=(2, 4))

    def step_kernels(self) -> int:
        """Compiled Pallas (Mosaic) kernels in the decode-step program
        :meth:`step` runs, lowered at the live pool's shapes (paged:
        the row-masked step over the pool) — 0 where kernels are
        interpreted (the CPU) or the step fell back to the einsum
        path.  Lowering only traces, so the donated pool is untouched;
        a speculative server's rounds are not counted."""
        table = (() if self._paged is None
                 else (self._paged.device_table(),))
        return self._step_fn.lower(
            self._params, self._cache, *table, self._lens, self._last,
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
        need = len(prompt) + max_new_tokens
        if self._draft_cfg is not None:
            # A final speculative round can write up to gamma + 1
            # cache slots past the budget before the slot finishes.
            need += self._gamma + 1
        if need > self._T:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens "
                f"({max_new_tokens})"
                + (f" + speculative headroom ({self._gamma + 1})"
                   if self._draft_cfg is not None else "")
                + f" exceeds max_len {self._T}")
        rid = self._next_id
        self._next_id += 1
        self.prompts[rid] = prompt
        self.outputs[rid] = []
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

    def _run_prefill(self, prefill_fn, params, cache, prompt: list,
                     slot: int, start: int = 0):
        """Prefill one slot; returns (cache, last-real-token logits).

        Default: one bucketed whole-prompt forward (compile count
        bounded by distinct buckets).  With ``prefill_chunk`` and a
        longer prompt: fixed-size segments stream through ONE compiled
        (1, chunk) program at increasing cache offsets — admission
        activation memory drops from O(S_prompt) to O(chunk) and long
        prompts stop minting per-bucket compiles.  The final segment
        (padded to the chunk) carries the logits; a causal forward
        makes chunked and single-shot prefill the same computation
        (same argument as :func:`~.generate.prefill_chunked`).

        ``start``: cache offset of the first token — 0 for whole
        prompts; the prefix length for suffix-only admission after a
        :meth:`cache_prefix` hit (the attention machinery already
        supports arbitrary offsets for chunked admission)."""
        L = len(prompt)
        ck = self._prefill_chunk
        if ck is None or L <= ck:
            s_pad = min(self._bucket(L), self._T - start)
            padded = jnp.asarray(prompt + [0] * (s_pad - L),
                                 jnp.int32)[None, :]
            return prefill_fn(params, cache, padded, jnp.int32(slot),
                              jnp.int32(start), jnp.int32(L))
        n_full = L // ck
        if L % ck == 0:
            n_full -= 1        # keep the last full chunk as the tail
        for i in range(n_full):
            seg = jnp.asarray(prompt[i * ck:(i + 1) * ck],
                              jnp.int32)[None, :]
            cache, _ = prefill_fn(params, cache, seg, jnp.int32(slot),
                                  jnp.int32(start + i * ck),
                                  jnp.int32(ck))
        tail = prompt[n_full * ck:]
        # Clamp the tail's pad so the padded write never reaches past
        # max_len (dynamic_update_slice would CLAMP the start index
        # and silently shift the write onto earlier cache rows).
        seg_len = min(ck, self._T - start - n_full * ck)
        seg = jnp.asarray(tail + [0] * (seg_len - len(tail)),
                          jnp.int32)[None, :]
        return prefill_fn(params, cache, seg, jnp.int32(slot),
                          jnp.int32(start + n_full * ck),
                          jnp.int32(len(tail)))

    def cache_prefix(self, tokens) -> int:
        """Prefill a shared prompt prefix ONCE into a dedicated 1-slot
        KV block; returns a prefix id.  Subsequent :meth:`submit`
        calls whose prompt starts with these tokens admit by COPYING
        the block into their slot (one HBM-to-HBM
        ``dynamic_update_slice``, zero FLOPs) and prefilling only the
        suffix — the standard continuous-batching treatment of shared
        system prompts.  Exactness is free: causal attention makes a
        position's K/V depend only on tokens at or before it, and RoPE
        positions are absolute, so the copied rows are bit-identical
        to a full prefill's.

        Not for capacity-based expert dispatch: capacity is
        shape-derived, so a suffix-length prefill would change which
        tokens drop vs a solo run (the same reason it rejects
        ``prefill_chunk``).
        """
        if _capacity_dispatch(self._cfg, self._mesh, self._ep_axis):
            raise ValueError(
                "prefix caching needs dense layers or dropless "
                "experts: capacity-based expert dispatch derives "
                "capacity from the shape, so suffix prefill would "
                "differ from a solo run and change which tokens drop")
        if self._paged is not None:
            raise ValueError(
                "prefix caching is not paged yet: the absorb copy "
                "assumes contiguous per-slot cache rows — register "
                "prefixes on a dense server")
        toks = [int(t) for t in tokens]
        if not toks:
            raise ValueError("empty prefix")
        if len(toks) >= self._T:
            raise ValueError(f"prefix ({len(toks)}) must leave room "
                             f"under max_len {self._T}")
        # Shard the prefix buffer like the pool along the KV-head (tp)
        # axis so the prefill forward and the absorb copy keep the
        # mesh layout; batch (size 1) and tokens stay replicated — a
        # 1-slot buffer can't split over dp, and its bucket length
        # need not divide sp (GSPMD localizes the copy into the
        # sp-sharded pool).
        rules = None
        if self._mesh is not None:
            rules = kv_cache_shardings(
                dp_axis=None,
                tp_axis="tp" if "tp" in self._mesh.shape else None,
                sp_axis=None, quantized=self._kv_quantized)

        def build(cfg, params, prefill_fn):
            # Size the scratch buffer for the PADDED writes (bucketed
            # or chunk-aligned), not just the real rows — an
            # undersized buffer would make dynamic_update_slice clamp
            # the write offset and shift rows.
            ck = self._prefill_chunk
            t_buf = self._bucket(len(toks))
            if ck is not None and len(toks) > ck:
                t_buf = max(t_buf, -(-len(toks) // ck) * ck)
            buf = init_kv_cache(cfg, 1, min(t_buf, self._T),
                                mesh=self._mesh, rules=rules,
                                quantized=self._kv_quantized)
            buf, last_logits = self._run_prefill(prefill_fn, params,
                                                 buf, toks, 0)
            # Keep only the real rows: the copy into a slot must not
            # drag pad garbage past the suffix's overwrite range.
            buf = jax.tree_util.tree_map(
                lambda c: c[:, :, :, :len(toks)], buf)
            return buf, last_logits

        buf_t, last_logits = build(self._cfg, self._params,
                                   self._prefill_fn)
        buf_d = (build(self._draft_cfg, self._draft_params,
                       self._prefill_d)[0]
                 if self._draft_cfg is not None else None)
        pid = self._next_pid
        self._next_pid += 1
        self._prefixes[pid] = (toks, buf_t, buf_d, last_logits)
        return pid

    def drop_prefix(self, pid: int) -> None:
        """Free a cached prefix's KV block (in-flight requests that
        already absorbed it are unaffected — the copy is by value)."""
        if pid not in self._prefixes:
            raise KeyError(f"unknown prefix id {pid}")
        del self._prefixes[pid]

    def _match_prefix(self, prompt: list):
        """Longest registered prefix the prompt starts with, or None."""
        best = None
        for pid, (toks, *_rest) in self._prefixes.items():
            n = len(toks)
            if n <= len(prompt) and prompt[:n] == toks:
                if best is None or n > len(self._prefixes[best][0]):
                    best = pid
        return best

    def _admit_pending(self) -> None:
        while self._pending and self._free:
            rid, prompt, budget = self._pending[0]
            slot = self._free[0]
            if self._paged is not None:
                # Worst-case block reservation at admission, so a
                # stream can never stall mid-decode on allocation.
                # Exhaustion leaves the request PENDING — it admits
                # when finishing streams free blocks.  The gateway's
                # accounting allocator normally prevents reaching
                # this; it is the worker-side backstop.
                from ..serving_fast.paging import BlocksExhausted
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
                # chunk; lens tracks the written offset so the decode
                # step's frozen-position write for this inactive row
                # always lands exactly where the NEXT chunk will
                # write (dense pool; the paged step's write redirects
                # inactive rows to trash anyway).
                self._prefilling[slot] = [rid, prompt, budget, 0]
                self._lens = self._lens.at[slot].set(0)
                continue
            self._admit_now(slot, rid, prompt, budget)

    def _admit_now(self, slot: int, rid: int, prompt: list[int],
                   budget: int) -> None:
        pid = self._match_prefix(prompt)
        if pid is not None:
            ptoks, buf_t, buf_d, plogits = self._prefixes[pid]
            n_pfx = len(ptoks)
            suffix = prompt[n_pfx:]
            self._cache = self._absorb_fn(self._cache, buf_t,
                                          jnp.int32(slot))
            if suffix:
                self._cache, last_logits = self._run_prefill(
                    self._prefill_fn, self._params, self._cache,
                    suffix, slot, start=n_pfx)
            else:
                last_logits = plogits
            if self._draft_cfg is not None:
                self._cache_d = self._absorb_fn(
                    self._cache_d, buf_d, jnp.int32(slot))
                if suffix:
                    self._cache_d, _ = self._run_prefill(
                        self._prefill_d, self._draft_params,
                        self._cache_d, suffix, slot, start=n_pfx)
        else:
            self._cache, last_logits = self._run_prefill(
                self._prefill_fn, self._params, self._cache,
                prompt, slot)
            if self._draft_cfg is not None:
                # Draft cache prefills the same prompt (its seed
                # logits are discarded — the target seeds the
                # stream).
                self._cache_d, _ = self._run_prefill(
                    self._prefill_d, self._draft_params,
                    self._cache_d, prompt, slot)
        tok = int(_sample(last_logits[None], self._temperature,
                          self._sample_key(), self._top_k,
                          self._top_p)[0])
        self.outputs[rid].append(tok)
        self.prefill_tokens_total += len(prompt)
        self._lens = self._lens.at[slot].set(len(prompt))
        self._last = self._last.at[slot].set(tok)
        if self._draft_cfg is not None:
            self._lens_d = self._lens_d.at[slot].set(len(prompt))
        done = (budget == 1
                or (self._eos is not None and tok == self._eos))
        if done:
            self._finish(slot, rid)
        else:
            self._slot_req[slot] = rid
            self._budget[rid] = budget - 1
            self._active = self._active.at[slot].set(True)

    def _finish(self, slot: int, rid: int) -> None:
        self._finished.add(rid)
        self._slot_req.pop(slot, None)
        self._budget.pop(rid, None)
        self._active = self._active.at[slot].set(False)
        self._free.append(slot)
        if self._paged is not None:
            self._paged.free(slot)

    def _advance_prefill(self) -> None:
        """Advance AT MOST ONE chunk of the oldest mid-prefill prompt
        — the per-tick prefill work bound that keeps long prompts from
        starving active streams' TPOT.  The final (possibly partial)
        chunk samples the first token and activates the slot; the
        segmentation matches :meth:`_run_prefill` exactly (full chunks,
        then a tail run at its real length), so the stream is
        bit-identical to a monolithic admission."""
        if not self._prefilling:
            return
        slot, st = next(iter(self._prefilling.items()))
        rid, prompt, budget, written = st
        ck = self._prefill_chunk
        remaining = len(prompt) - written
        if remaining > ck:
            seg = jnp.asarray(prompt[written:written + ck],
                              jnp.int32)[None, :]
            self._cache, _ = self._prefill_fn(
                self._params, self._cache, seg, jnp.int32(slot),
                jnp.int32(written), jnp.int32(ck))
            st[3] = written + ck
            self.prefill_tokens_total += ck
            # Keep lens at the written frontier: the decode step's
            # frozen-position write for this inactive row lands where
            # the next chunk will overwrite it (dense pool).
            self._lens = self._lens.at[slot].set(st[3])
            return
        # Final segment: pad to the chunk shape, clamp so the padded
        # write never reaches past max_len (same rule as
        # _run_prefill's tail).
        tail = prompt[written:]
        seg_len = min(ck, self._T - written)
        seg = jnp.asarray(tail + [0] * (seg_len - len(tail)),
                          jnp.int32)[None, :]
        self._cache, last_logits = self._prefill_fn(
            self._params, self._cache, seg, jnp.int32(slot),
            jnp.int32(written), jnp.int32(len(tail)))
        del self._prefilling[slot]
        self.prefill_tokens_total += len(tail)
        tok = int(_sample(last_logits[None], self._temperature,
                          self._sample_key(), self._top_k,
                          self._top_p)[0])
        self.outputs[rid].append(tok)
        self._lens = self._lens.at[slot].set(len(prompt))
        self._last = self._last.at[slot].set(tok)
        if budget == 1 or (self._eos is not None
                           and tok == self._eos):
            self._finish(slot, rid)
        else:
            self._slot_req[slot] = rid
            self._budget[rid] = budget - 1
            self._active = self._active.at[slot].set(True)

    def cancel(self, rid: int) -> bool:
        """Abort an in-flight request NOW: drop it from the pending
        queue, the prefill stream, or its active slot, freeing the
        slot and (paged mode) its KV blocks.  Returns False for
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
        """One decode step for every active slot; returns
        {request_id: tokens emitted this step} — one token per step in
        plain mode, 1..gamma+1 in speculative mode.  Admits pending
        requests first, then advances at most one mid-prefill chunk
        (interleave mode).  Each phase (:data:`STEP_PHASES`) adds its
        seconds to :attr:`phase_s`; the step's phases telescope."""
        ph, tick = self.phase_s, self.tick
        t0 = time.perf_counter()
        with obs_spans.phase("serve/step/prefill", tick):
            self._admit_pending()
            self._advance_prefill()
        t1 = time.perf_counter()
        ph["prefill"] += t1 - t0
        if not self._slot_req:
            return {}
        with obs_spans.phase("serve/step/dispatch", tick):
            # Returns before the chip is done.
            out = self._dispatch_step()
        t2 = time.perf_counter()
        ph["dispatch"] += t2 - t1
        with obs_spans.phase("serve/step/sync", tick):
            # The host blocked on the chip: one fetch per step.
            out = jax.device_get(out)
        t3 = time.perf_counter()
        ph["sync"] += t3 - t2
        with obs_spans.phase("serve/step/emit", tick):
            if self._routed and self._draft_cfg is None:
                touched, most, rows = (float(v) for v in out[1])
                self.moe_load[0] += touched
                self.moe_load[1] = max(self.moe_load[1], most)
                self.moe_load[2] += rows
            emitted: dict[int, list[int]] = {}
            for slot, rid in list(self._slot_req.items()):
                emitted[rid] = self._emit(slot, rid,
                                          self._step_tokens(out, slot))
        t4 = time.perf_counter()
        ph["emit"] += t4 - t3
        if self._pending:
            self._admit_as_prefill(t4)
        return emitted

    def _dispatch_step(self):
        """Enqueue one decode step (plain, paged or one speculative
        round) and return the device arrays the host has to fetch."""
        if self._draft_cfg is not None:
            # Draft proposes gamma tokens per slot, ONE target forward
            # verifies all slots' candidates.  Per-slot acceptance
            # lengths diverge freely; budget/EOS cut a stream
            # mid-round by truncating its emission and finishing the
            # slot (its device-side cache state beyond the cut is
            # stale but dies with the slot — re-admission prefills
            # from 0).
            (self._cache, self._lens, self._cache_d, self._lens_d,
             cand, n_acc, self._last) = self._spec_fn(
                self._params, self._draft_params, self._cache,
                self._lens, self._cache_d, self._lens_d, self._last,
                self._active, self._sample_key())
            return cand, n_acc
        table = ()
        if self._paged is not None:
            table = (self._paged.device_table(),)
            self.kv_read_bytes_total += self._step_kv_read_bytes()
        self.decode_steps_total += 1
        self._cache, self._lens, self._last, load = self._step_fn(
            self._params, self._cache, *table, self._lens, self._last,
            self._active, self._sample_key())
        return self._last if load is None else (self._last, load)

    def _step_kv_read_bytes(self) -> int:
        """Bytes of K and V pages the next decode step's attention
        fetches, all layers: for every active slot the pages from the
        window's first to the one its new token lands in."""
        bt = self._paged.block_tokens
        pages = 0
        for rid in self._slot_req.values():
            pos = len(self.prompts[rid]) + len(self.outputs[rid]) - 1
            pages += pos // bt - self._first_live_page(pos) + 1
        return pages * self._page_bytes

    def _step_tokens(self, out, slot: int) -> list[int]:
        """One slot's tokens of a fetched step (see
        :meth:`_dispatch_step`)."""
        if self._draft_cfg is not None:
            cand, n_acc = out
            return [int(t) for t in cand[slot][: int(n_acc[slot]) + 1]]
        toks = out[0] if self._routed else out
        return [int(toks[slot])]

    def _emit(self, slot: int, rid: int, toks: list[int]) -> list[int]:
        """Budget-then-EOS truncation + bookkeeping for a multi-token
        emission — the ONE definition of the cut semantics, shared by
        the speculative round and step_many (both can overshoot
        device-side; the surplus is discarded here and the slot's
        stale device state dies with the slot)."""
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

    def step_many(self, n: int) -> dict[int, list[int]]:
        """Run ``n`` plain decode steps in ONE device program
        (``lax.scan``) and apply budget/EOS host-side afterwards.

        Amortizes the per-step host sync of single-step serving:
        tokens stream back every ``n`` steps instead of every step.  Trade-offs, by construction: pending
        requests admit only at scan boundaries (up to ``n`` steps of
        admission latency), and a slot whose stream hits EOS or its
        budget mid-scan keeps computing to the boundary (its surplus
        tokens are discarded host-side; its surplus cache state is
        stale-but-dead exactly like a mid-round speculative cut).
        The emitted tokens are bit-identical to ``n`` successive
        :meth:`step` calls in greedy mode.  Plain mode only —
        speculative serving already emits multiple tokens per step.
        """
        if n < 1:
            raise ValueError(f"n must be >= 1, got {n}")
        if self._draft_cfg is not None:
            raise ValueError("step_many is for plain serving; use "
                             "spec_step_many on a speculative server")
        if self._paged is not None:
            raise ValueError(
                "step_many is a dense-pool fast path; paged serving "
                "steps host-side per tick (the serve_step driver "
                "loops step())")
        self._admit_pending()
        if not self._slot_req:
            return {}
        keys = jax.random.split(self._sample_key(), n)
        (self._cache, self._lens, self._last,
         toks) = self._step_many_fn(
            self._params, self._cache, self._lens, self._last,
            self._active, keys)
        toks_h = jax.device_get(toks)              # (n, B)
        emitted: dict[int, list[int]] = {}
        for slot, rid in list(self._slot_req.items()):
            emitted[rid] = self._emit(
                slot, rid, [int(t) for t in toks_h[:, slot]])
        self._admit_pending()
        return emitted

    def spec_step_many(self, n: int) -> dict[int, list[int]]:
        """Run ``n`` speculative rounds in ONE device program
        (``lax.scan`` over :func:`~.speculative.spec_round`) — up to
        ``n·(gamma+1)`` tokens per slot per host sync.

        The speculative analog of :meth:`step_many`, with the same
        trade-offs: admission only at scan boundaries, and budget/EOS
        cuts applied host-side after the scan (surplus rounds'
        tokens are discarded; surplus cache state is stale-but-dead).
        Rows additionally self-freeze device-side when another round
        could write past ``max_len`` — that bound only triggers past
        the stream's budget, so emissions are bit-identical to ``n``
        successive :meth:`step` calls in greedy mode."""
        if n < 1:
            raise ValueError(f"n must be >= 1, got {n}")
        if self._draft_cfg is None:
            raise ValueError("spec_step_many needs a speculative "
                             "server (draft_params/draft_cfg); use "
                             "step_many for plain serving")
        self._admit_pending()
        if not self._slot_req:
            return {}
        keys = jax.random.split(self._sample_key(), n)
        (self._cache, self._lens, self._cache_d, self._lens_d,
         self._last, cands, n_accs, acts) = self._spec_many_fn(
            self._params, self._draft_params, self._cache, self._lens,
            self._cache_d, self._lens_d, self._last, self._active,
            keys)
        cands_h, accs_h, acts_h = jax.device_get(
            (cands, n_accs, acts))                 # (n,B,g+1),(n,B),(n,B)
        emitted: dict[int, list[int]] = {}
        for slot, rid in list(self._slot_req.items()):
            toks: list[int] = []
            for r in range(n):
                if acts_h[r, slot]:
                    toks.extend(
                        int(t) for t in
                        cands_h[r, slot][: int(accs_h[r, slot]) + 1])
            emitted[rid] = self._emit(slot, rid, toks)
        self._admit_pending()
        return emitted

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
        self.prompts.pop(rid, None)
        self._finished.discard(rid)
        return toks

    def done(self) -> bool:
        return (not self._slot_req and not self._pending
                and not self._prefilling)

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

    def take_moe_load(self) -> list[float]:
        """:attr:`moe_load` since the last call, which it resets (the
        worker reports it once a tick beside the steps that ran)."""
        load, self.moe_load = self.moe_load, [0.0, 0.0, 0.0]
        return load

    def kv_snapshot(self) -> dict | None:
        """Paged-mode block occupancy (``{"blocks", "block_tokens",
        "used", "free", "owners"}``), None on a dense server — the
        worker's heartbeat telemetry and status surfaces read this."""
        return (self._paged.snapshot() if self._paged is not None
                else None)
