"""Speculative decoding (Leviathan et al. 2022, arXiv:2211.17192).

A small draft model proposes ``gamma`` tokens autoregressively; the
target model scores all of them in ONE batched forward (prefill-shaped
work, MXU-friendly), and the longest valid prefix is accepted.  Decode
latency is bounded by target-model *forwards per accepted token*, which
drops from 1 to ~1/(mean accepted + 1) — and TPU-native here because
both the proposal loop and the verify pass reuse the static-shape
KV-cache machinery (models/generate.py: fixed-length caches,
position-masked attention).

**Batched streams share every forward.**  All B streams ride one
(B, gamma+1) verify call and one (B, 1) draft call per proposal step —
the verify matmuls grow along the batch axis, which is exactly how the
MXU wants them (a B=8 verify is ~the cost of a B=1 verify at these
sizes, so speculation's win multiplies across streams).  Streams accept
different prefix lengths per round, so each row keeps its own logical
cache pointer: ``forward_with_cache`` takes a per-row ``(B,)``
``cache_len``, positions are masked per row (``t <= pos_b``), and cache
writes land at per-row offsets.  Rollback is free by construction:
rejecting tokens just moves a row's pointer back — stale slots are
position-masked until overwritten.

Finished streams freeze: their advance is masked to zero and their
(recomputed, identical) writes land in slots beyond the output slice,
so the while-loop runs until the *slowest* stream reaches
``max_new_tokens`` without any stream overshooting its committed
output.

Stream independence holds exactly for the dense family (asserted
bit-identical to solo runs in the tests).  For MoE configs, frozen
streams are *masked out of expert dispatch* (``row_mask`` →
``moe_ffn(token_mask=...)``): their discarded recomputation takes no
capacity slot, so finishing early never perturbs a live stream.  The
remaining (inherent) qualification: capacity-based expert dispatch
pools all *live* rows' tokens into one capacity buffer, so under
tight capacity batched MoE decode can drop tokens a solo run would
keep — batched speculative MoE matches batched MoE decode semantics.

Greedy mode reproduces the target model's own greedy decode (verified
bit-identical against :func:`~.generate.generate` in the fp32 tests) —
with the usual batched-vs-stepwise numerics caveat: the verify pass
scores gamma+1 tokens in one forward while ``generate`` decodes S=1 at
a time, so in bf16 a near-tied top-2 logit can round differently and
flip an argmax.  Sampled mode implements the modified rejection scheme
per stream: accept draft token d_i with probability
``min(1, p_t(d_i)/p_d(d_i))``; on the first rejection resample from
``normalize(max(0, p_t - p_d))``; if all gamma survive, sample the
bonus token from the target's next-position distribution.  The output
distribution equals sampling from the target alone, independently per
stream.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .generate import forward_with_cache, init_kv_cache, truncate_logits
from .transformer import TransformerConfig


def _greedy_tok(logits):
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def spec_round(params, draft_params, cfg, draft_cfg, *, gamma: int,
               temperature: float, cache_t, len_t, cache_d, len_d,
               last_tok, key, active, mesh=None, ep_axis: str = "ep",
               top_k: int | None = None, top_p: float | None = None):
    """ONE draft-propose / target-verify round for B streams — the
    engine of :func:`speculative_generate`'s closed loop.

    State contract (the lag-one cache discipline): both caches hold
    exactly the committed tokens' K/V below their pointers, and
    ``last_tok`` is the newest committed token, NOT yet written to
    either cache — each model re-feeds it first, which is why both
    pointers advance by ``n_acc + 1``.

    Returns ``(cache_t, len_t, cache_d, len_d, key, cand, n_acc,
    new_last)``: ``cand`` (B, gamma+1) holds each row's candidate
    tokens (accepted prefix + correction/bonus at index ``n_acc``;
    later entries stale), ``n_acc`` (B,) the accepted draft counts,
    ``new_last`` the per-row newest committed token.  Rows with
    ``active=False`` freeze: pointers do not advance (callers mask),
    and ``row_mask`` keeps them out of MoE expert capacity.
    """
    B = last_tok.shape[0]

    def draft_step(carry, i):
        cache_d, len_d, tok, key = carry
        lg, cache_d = forward_with_cache(
            draft_params, tok[:, None], cache_d, len_d, draft_cfg,
            row_mask=active, mesh=mesh, ep_axis=ep_axis)
        key, ks = jax.random.split(key)
        nxt = _sample_1(lg[:, -1], temperature, ks, top_k, top_p)  # (B,)
        return (cache_d, len_d + 1, nxt, key), (nxt, lg[:, -1])

    (cache_d, _, _, key), (drafts, draft_logits) = \
        jax.lax.scan(draft_step, (cache_d, len_d, last_tok, key),
                     jnp.arange(gamma))
    # drafts: (gamma, B) int32; draft_logits: (gamma, B, V)
    # The scan wrote K/V for [newest, d_1..d_{gamma-1}] — d_gamma's
    # K/V is still missing, and the n_acc == gamma round needs it
    # (the pointer then advances past its slot).  One more write
    # (logits discarded) keeps the lag-one invariant for every
    # n_acc; the slot is stale-and-masked when d_gamma is rejected.
    _, cache_d = forward_with_cache(
        draft_params, drafts[-1][:, None], cache_d,
        len_d + gamma, draft_cfg, row_mask=active, mesh=mesh,
        ep_axis=ep_axis)

    # --- target verifies the newest token + all proposals ------
    # ONE forward shared by every stream: (B, gamma+1) — this
    # batched verify is the speedup's engine room.
    verify_in = jnp.concatenate([last_tok[:, None], drafts.T],
                                axis=1)              # (B, g+1)
    logits_v, cache_t = forward_with_cache(
        params, verify_in, cache_t, len_t, cfg,
        row_mask=active, mesh=mesh, ep_axis=ep_axis)  # (B, g+1, V)

    key, kacc, kfix = jax.random.split(key, 3)
    # top_k/top_p bind via partial (static ints for lax.top_k — they
    # must not pass through vmap as mapped operands).
    n_acc, next_tok = jax.vmap(
        functools.partial(_accept, top_k=top_k, top_p=top_p),
        in_axes=(1, 1, 0, None, 0, 0))(
        drafts, draft_logits, logits_v, temperature,
        jax.random.split(kacc, B), jax.random.split(kfix, B))

    cand = jnp.concatenate(
        [drafts.T, jnp.zeros((B, 1), jnp.int32)], axis=1)
    cand = cand.at[jnp.arange(B), n_acc].set(next_tok)
    adv = jnp.where(active, n_acc + 1, 0)
    new_last = jnp.where(active, next_tok, last_tok)
    return (cache_t, len_t + adv, cache_d, len_d + adv, key, cand,
            n_acc, new_last)


def speculative_generate(params: dict, draft_params: dict,
                         prompt, cfg: TransformerConfig,
                         draft_cfg: TransformerConfig,
                         max_new_tokens: int, *, gamma: int = 4,
                         temperature: float = 0.0, key=None,
                         top_k: int | None = None,
                         top_p: float | None = None,
                         max_len: int | None = None,
                         kv_quantized: bool = False,
                         mesh=None, ep_axis: str = "ep"):
    """Generate ``max_new_tokens`` continuations of ``prompt`` (B, S0)
    with draft-proposed, target-verified decoding.

    Both models must share the vocabulary.  Greedy when
    ``temperature == 0`` — each stream's output reproduces the target's
    own greedy decode (see the module docstring for the
    batched-vs-stepwise numerics caveat); otherwise the
    rejection-sampling scheme preserves the target's sampling
    distribution per stream (``key`` required).  ``top_k``/``top_p``
    compose with sampling via truncation-aware acceptance (draft
    proposals and the rejection test both use the truncated
    distributions — see :func:`_accept`): the output distribution
    equals ``generate(..., top_k=, top_p=)``'s.

    Returns (tokens (B, S0 + max_new_tokens), mean_accepted) — the
    second value is the average number of draft tokens accepted per
    verify round per active stream (max ``gamma``), the quantity that
    sets the speedup.

    With ``mesh``, both KV caches are created sharded (batch over
    ``dp``, KV heads over ``tp``) and every forward routes through the
    mesh-aware decode path (``_flash_decode_on_mesh`` for the S=1
    draft steps; MoE expert all-to-alls over ``ep_axis``) — pass
    target/draft params sharded by ``param_shardings``.
    """
    B = prompt.shape[0]
    if B < 1:
        raise ValueError(f"need at least one stream, got batch {B}")
    if prompt.shape[1] == 0:
        raise ValueError("cannot generate from an empty prompt "
                         "(S == 0)")
    if cfg.vocab_size != draft_cfg.vocab_size:
        raise ValueError("target and draft must share a vocabulary")
    if gamma < 1:
        raise ValueError(f"gamma must be >= 1, got {gamma}")
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got "
                         f"{max_new_tokens}")
    if temperature != 0.0 and key is None:
        raise ValueError("sampling (temperature > 0) requires a PRNG key")
    if top_k is not None and not 1 <= top_k <= cfg.vocab_size:
        raise ValueError(f"top_k must be in [1, vocab_size="
                         f"{cfg.vocab_size}], got {top_k}")
    if top_p is not None and not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    if key is None:
        key = jax.random.PRNGKey(0)

    S0 = prompt.shape[1]
    # The token buffer over-allocates one whole round (gamma + 1) so a
    # final round can write past the target count; the result is
    # sliced to exactly max_new_tokens.
    buf_len = S0 + max_new_tokens + gamma + 1
    T = max_len if max_len is not None else buf_len
    if T < buf_len:
        raise ValueError(f"max_len {T} < required {buf_len} "
                         f"(prompt + max_new_tokens + gamma + 1)")
    # int8 caches compose transparently: forward_with_cache dispatches
    # on the cache keys, and rollback-by-pointer works identically.
    cache_t = init_kv_cache(cfg, B, T, mesh=mesh,
                            quantized=kv_quantized)
    cache_d = init_kv_cache(draft_cfg, B, T, mesh=mesh,
                            quantized=kv_quantized)

    # Prefill both models on the prompt (streams still aligned, so the
    # pointer is a shared scalar 0 here); the target's last-position
    # logits seed the first accepted token of every stream.
    logits_t, cache_t = forward_with_cache(params, prompt, cache_t, 0,
                                           cfg, last_only=True,
                                           mesh=mesh, ep_axis=ep_axis)
    _, cache_d = forward_with_cache(draft_params, prompt, cache_d, 0,
                                    draft_cfg, last_only=True,
                                    mesh=mesh, ep_axis=ep_axis)

    key, k0 = jax.random.split(key)
    first = _sample_1(logits_t[:, -1], temperature, k0,
                      top_k, top_p)                          # (B,)

    toks = jnp.zeros((B, buf_len), jnp.int32)
    toks = jax.lax.dynamic_update_slice(toks, prompt, (0, 0))
    toks = toks.at[:, S0].set(first)

    # Carried state: token buffer, per-stream #generated (>=1 after the
    # seed), both caches with their per-stream logical lengths (prompt
    # is in both), rng, and the accept-count accumulators.  The caches
    # MUST ride the loop carry — accepted tokens' K/V written in round
    # r are read in every later round.
    ones = jnp.ones((B,), jnp.int32)
    state = (toks, ones, cache_t, S0 * ones, cache_d, S0 * ones, key,
             jnp.float32(0.0), jnp.float32(0.0))

    def cond(state):
        return jnp.any(state[1] < max_new_tokens)

    def body(state):
        (toks, n, cache_t, len_t, cache_d, len_d, key, acc_sum,
         active_rounds) = state
        done = n >= max_new_tokens                       # (B,)
        pos_last = S0 + n - 1          # buffer index of newest token
        last_tok = jnp.take_along_axis(
            toks, pos_last[:, None], axis=1)[:, 0]       # (B,)
        active = ~done  # frozen rows: no expert-capacity footprint

        (cache_t, len_t, cache_d, len_d, key, upd, n_acc, _) = \
            spec_round(params, draft_params, cfg, draft_cfg,
                       gamma=gamma, temperature=temperature,
                       cache_t=cache_t, len_t=len_t, cache_d=cache_d,
                       len_d=len_d, last_tok=last_tok, key=key,
                       active=active, mesh=mesh, ep_axis=ep_axis,
                       top_k=top_k, top_p=top_p)

        # --- commit ------------------------------------------------
        # Write all gamma+1 candidate slots per row; only the first
        # n_acc + 1 are real — the counter never reaches the stale
        # tail before a later round overwrites it.  Finished rows
        # advance by 0; their (frozen-pointer) writes land at or past
        # S0 + max_new_tokens, outside the output slice — dynamic
        # slice clamping keeps even the overshoot case in that region.
        toks = jax.vmap(
            lambda row, u, s: jax.lax.dynamic_update_slice(row, u,
                                                           (s,)))(
            toks, upd, pos_last + 1)
        n = n + jnp.where(done, 0, n_acc + 1)
        acc_sum = acc_sum + jnp.sum(
            jnp.where(done, 0.0, n_acc.astype(jnp.float32)))
        active_rounds = active_rounds + jnp.sum(
            (~done).astype(jnp.float32))
        return (toks, n, cache_t, len_t, cache_d, len_d, key,
                acc_sum, active_rounds)

    toks, n, _, _, _, _, _, acc_sum, active_rounds = jax.lax.while_loop(
        cond, body, state)
    out = jax.lax.dynamic_slice(
        toks, (0, 0), (B, S0 + max_new_tokens))
    mean_acc = acc_sum / jnp.maximum(active_rounds, 1.0)
    return out, mean_acc


def _sample_1(logits, temperature: float, key,
              top_k: int | None = None, top_p: float | None = None):
    """(B, V) or (V,) logits -> (B,) int32 tokens (independent rows).
    ``top_k``/``top_p`` truncate the distribution before sampling
    (see :func:`~.generate.truncate_logits`)."""
    if temperature == 0.0:
        return _greedy_tok(jnp.atleast_2d(logits))
    logits = truncate_logits(jnp.atleast_2d(logits) / temperature,
                             top_k, top_p)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


def _accept(drafts, draft_logits, verify_logits, temperature: float,
            kacc, kfix, *, top_k: int | None = None,
            top_p: float | None = None):
    """Acceptance rule for one round of one stream (vmapped over B).

    drafts: (g,) proposed tokens; draft_logits: (g, V) the draft's
    logits at each proposal; verify_logits: (g+1, V) the target's
    logits at [newest, d_1..d_g] — position i scores d_{i+1}.
    Returns (n_acc in [0, g], next token after the accepted prefix).

    ``top_k``/``top_p`` implement truncation-aware speculative
    sampling: BOTH distributions are filtered with the same knobs
    before the rejection test.  The accept/resample lemma holds for
    any (p, q) pair, so the emitted distribution equals sampling from
    the *truncated target* — exactly what ``generate(top_k=, top_p=)``
    samples.  The draft proposals must be drawn from the same
    truncated draft distribution (:func:`_sample_1` with the same
    knobs), which also keeps ``q(d_i) > 0`` for every proposal.
    """
    g = drafts.shape[0]
    if temperature == 0.0:
        # Greedy: accept while the target's argmax equals the draft
        # (truncation never changes an argmax: top-k keeps the k
        # largest, nucleus always keeps the top-1 token).
        tgt = _greedy_tok(verify_logits)             # (g+1,)
        match = tgt[:g] == drafts
        n_acc = jnp.argmin(jnp.concatenate(
            [match, jnp.zeros((1,), bool)])).astype(jnp.int32)
        # next token: target's argmax at the divergence position
        # (== bonus position when everything matched).
        return n_acc, tgt[n_acc]

    pt = jax.nn.softmax(truncate_logits(
        verify_logits / temperature, top_k, top_p), axis=-1)  # (g+1,V)
    pd = jax.nn.softmax(truncate_logits(
        draft_logits / temperature, top_k, top_p), axis=-1)   # (g,V)
    pt_i = jnp.take_along_axis(pt[:g], drafts[:, None], axis=-1)[:, 0]
    pd_i = jnp.take_along_axis(pd, drafts[:, None], axis=-1)[:, 0]
    u = jax.random.uniform(kacc, (g,))
    ok = u < jnp.minimum(1.0, pt_i / jnp.maximum(pd_i, 1e-20))
    n_acc = jnp.argmin(jnp.concatenate(
        [ok, jnp.zeros((1,), bool)])).astype(jnp.int32)

    # Residual distribution at the rejection position; at the bonus
    # position (all accepted) the residual is just p_t itself.
    pt_at = pt[n_acc]
    pd_at = jnp.where(n_acc < g, pd[jnp.minimum(n_acc, g - 1)], 0.0)
    resid = jnp.maximum(pt_at - pd_at, 0.0)
    resid = resid / jnp.maximum(jnp.sum(resid), 1e-20)
    nxt = jax.random.choice(kfix, resid.shape[-1], p=resid)
    return n_acc, nxt.astype(jnp.int32)
