"""Continuous-batching decode server: staggered admission must be
bit-identical per request to standalone generate(), slots must recycle,
EOS must cut streams, and MoE configs must serve through row_mask."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nbdistributed_tpu.models import generate, init_params, tiny_config
from nbdistributed_tpu.models.serving import DecodeServer

# Heavy interpret-mode kernel/model tests: excluded from the
# fast product-path tier (`pytest -m "not slow"`).
pytestmark = [pytest.mark.unit, pytest.mark.slow]


@pytest.fixture(scope="module")
def setup():
    cfg = tiny_config(dtype=jnp.float32, use_flash=False)
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def solo(params, cfg, prompt, n):
    out = generate(params, jnp.asarray(prompt, jnp.int32)[None], cfg, n)
    return [int(t) for t in np.asarray(out)[0][len(prompt):]]


def test_staggered_admission_matches_solo_generate(setup):
    """Three requests of different lengths admitted at different times
    into a 2-slot pool: every request's greedy tokens must equal its
    standalone generate() run — occupancy and admission order must be
    invisible to the numerics."""
    cfg, params = setup
    reqs = [([5, 9, 2], 7), ([7, 1, 3, 11, 4], 5), ([2, 2], 6)]
    srv = DecodeServer(params, cfg, max_batch=2, max_len=64, pad_to=4)

    r0 = srv.submit(*reqs[0])
    srv.step()
    r1 = srv.submit(*reqs[1])          # fills the second slot
    srv.step()
    r2 = srv.submit(*reqs[2])          # queues until a slot frees
    srv.run_until_done(max_steps=100)

    for rid, (prompt, n) in zip((r0, r1, r2), reqs):
        assert srv.outputs[rid] == solo(params, cfg, prompt, n), rid


def test_slots_recycle_and_outputs_complete(setup):
    """More requests than slots: all finish, each with exactly its
    token budget (no EOS in play for random-init logits over a tiny
    vocab is not guaranteed — so disable EOS)."""
    cfg, params = setup
    srv = DecodeServer(params, cfg, max_batch=2, max_len=32, pad_to=4)
    rids = [srv.submit([i + 1, i + 2], 4) for i in range(5)]
    srv.run_until_done(max_steps=200)
    assert srv.done() and srv.n_active == 0
    for rid in rids:
        assert len(srv.outputs[rid]) == 4
    assert srv.finished == set(rids)


def test_eos_frees_slot_early(setup):
    """A request whose next greedy token IS the eos id must finish on
    that step with the eos included, freeing the slot."""
    cfg, params = setup
    prompt, n = [5, 9, 2], 8
    toks = solo(params, cfg, prompt, n)
    eos = toks[2]                       # force an early cut at step 3
    srv = DecodeServer(params, cfg, max_batch=1, max_len=64,
                       pad_to=4, eos_id=eos)
    rid = srv.submit(prompt, n)
    srv.run_until_done(max_steps=50)
    got = srv.outputs[rid]
    assert got == toks[:got.index(eos) + 1]
    assert got[-1] == eos and len(got) <= n


def test_single_token_budget_finishes_at_admission(setup):
    cfg, params = setup
    srv = DecodeServer(params, cfg, max_batch=1, max_len=32, pad_to=4)
    rid = srv.submit([3, 1, 4], 1)
    assert srv.done()
    assert srv.outputs[rid] == solo(params, cfg, [3, 1, 4], 1)


def test_validation_errors(setup):
    cfg, params = setup
    srv = DecodeServer(params, cfg, max_batch=1, max_len=16, pad_to=4)
    with pytest.raises(ValueError, match="empty"):
        srv.submit([], 4)
    with pytest.raises(ValueError, match=">= 1"):
        srv.submit([1], 0)
    with pytest.raises(ValueError, match="exceeds max_len"):
        srv.submit([1] * 10, 10)


def test_sampled_mode_runs_and_respects_budget(setup):
    cfg, params = setup
    srv = DecodeServer(params, cfg, max_batch=2, max_len=32, pad_to=4,
                       temperature=1.0, top_k=8,
                       key=jax.random.PRNGKey(7))
    rids = [srv.submit([4, 2], 5), srv.submit([9], 3)]
    srv.run_until_done(max_steps=50)
    assert [len(srv.outputs[r]) for r in rids] == [5, 3]
    for r in rids:
        assert all(0 <= t < cfg.vocab_size for t in srv.outputs[r])


def test_int8_cache_serving_matches_int8_generate(setup):
    """kv_quantized serving must equal kv_quantized generate per
    request (same quantized-cache numerics path)."""
    cfg, params = setup
    prompt, n = [5, 9, 2, 7], 6
    ref = generate(params, jnp.asarray(prompt, jnp.int32)[None], cfg,
                   n, kv_quantized=True)
    ref = [int(t) for t in np.asarray(ref)[0][len(prompt):]]
    srv = DecodeServer(params, cfg, max_batch=2, max_len=32, pad_to=4,
                       kv_quantized=True)
    rid = srv.submit(prompt, n)
    srv.run_until_done(max_steps=50)
    assert srv.outputs[rid] == ref


def test_int4_params_serving_matches_int4_generate(setup):
    """Nibble-packed int4 weights serve through DecodeServer exactly
    as through standalone generate (the qlinear packed path under the
    server's slot-pooled cache)."""
    from nbdistributed_tpu.models import quantize_params4
    cfg, params = setup
    q4 = quantize_params4(params)
    prompt, n = [5, 9, 2, 7], 6
    ref = generate(q4, jnp.asarray(prompt, jnp.int32)[None], cfg,
                   n, kv_quantized=True)
    ref = [int(t) for t in np.asarray(ref)[0][len(prompt):]]
    srv = DecodeServer(q4, cfg, max_batch=2, max_len=32, pad_to=4,
                       kv_quantized=True)
    rid = srv.submit(prompt, n)
    srv.run_until_done(max_steps=50)
    assert srv.outputs[rid] == ref


def test_token_mask_keeps_pads_out_of_expert_capacity():
    """forward_with_cache's token_mask: right-pad tokens routed
    through a tight-capacity MoE flood an expert's segment and evict
    real tokens' second-choice slots — with the mask, the padded
    prefill's last-real-token logits equal the unpadded run's; without
    it (seed pair pinned by a scan) they provably differ."""
    from nbdistributed_tpu.models import init_moe_model, tiny_moe_config
    from nbdistributed_tpu.models.generate import (forward_with_cache,
                                                   init_kv_cache)
    cfg = tiny_moe_config(dtype=jnp.float32, use_flash=False,
                          capacity_factor=1.0)
    params = init_moe_model(jax.random.PRNGKey(4), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(100), (5,), 1,
                                cfg.vocab_size)
    L, s_pad = 5, 64
    padded = jnp.concatenate(
        [prompt, jnp.zeros((s_pad - L,), jnp.int32)])[None]
    mask = (jnp.arange(s_pad)[None] < L)
    idx = jnp.asarray([L - 1])

    ref, _ = forward_with_cache(params, prompt[None],
                                init_kv_cache(cfg, 1, 80), 0, cfg,
                                last_index=idx)
    masked, _ = forward_with_cache(params, padded,
                                   init_kv_cache(cfg, 1, 80), 0, cfg,
                                   token_mask=mask, last_index=idx)
    unmasked, _ = forward_with_cache(params, padded,
                                     init_kv_cache(cfg, 1, 80), 0, cfg,
                                     last_index=idx)
    # Masked pads change nothing vs the unpadded run (no real-token
    # drops at this size on either side)...
    np.testing.assert_allclose(np.asarray(masked), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)
    # ...while unmasked pads provably perturb the real tokens.
    assert float(jnp.max(jnp.abs(unmasked - ref))) > 0.1


def test_moe_long_prompt_exact_length_admission():
    """MoE expert capacity is shape-derived, so bucket padding would
    inflate it past a solo generate() run's (20 real tokens: solo
    capacity 16 vs a 64-bucket's 32) and change which tokens drop.
    The server admits MoE prompts at exact length — a 20-token prompt
    must match solo generate even with pad_to=64 requested."""
    from nbdistributed_tpu.models import init_moe_model, tiny_moe_config
    cfg = tiny_moe_config(dtype=jnp.float32, use_flash=False,
                          capacity_factor=1.0)
    params = init_moe_model(jax.random.PRNGKey(4), cfg)
    prompt = [int(t) for t in jax.random.randint(
        jax.random.PRNGKey(101), (20,), 1, cfg.vocab_size)]
    n = 4
    ref = generate(params, jnp.asarray(prompt, jnp.int32)[None], cfg, n)
    ref = [int(t) for t in np.asarray(ref)[0][len(prompt):]]
    srv = DecodeServer(params, cfg, max_batch=1, max_len=80, pad_to=64)
    rid = srv.submit(prompt, n)
    srv.run_until_done(max_steps=50)
    assert srv.outputs[rid] == ref


def test_release_evicts_and_guards_in_flight(setup):
    cfg, params = setup
    srv = DecodeServer(params, cfg, max_batch=1, max_len=32, pad_to=4)
    rid = srv.submit([3, 1], 3)
    with pytest.raises(ValueError, match="in flight"):
        srv.release(rid)
    srv.run_until_done(max_steps=20)
    toks = srv.release(rid)
    assert len(toks) == 3
    assert rid not in srv.outputs and rid not in srv.prompts
    assert rid not in srv.finished
    with pytest.raises(KeyError, match="already-released"):
        srv.release(rid)
    with pytest.raises(KeyError, match="unknown"):
        srv.release(9999)


def test_moe_family_serves():
    """The MoE family drives the same server (row_mask keeps empty
    slots out of expert capacity); tokens match MoE generate when the
    pool runs a single request (capacity pooling across live rows is
    batched-decode semantics, so only the solo case is exact)."""
    from nbdistributed_tpu.models import init_moe_model, tiny_moe_config
    cfg = tiny_moe_config(dtype=jnp.float32, use_flash=False,
                          capacity_factor=2.0)
    params = init_moe_model(jax.random.PRNGKey(0), cfg)
    prompt, n = [5, 1, 3], 5
    ref = generate(params, jnp.asarray(prompt, jnp.int32)[None], cfg, n)
    ref = [int(t) for t in np.asarray(ref)[0][len(prompt):]]
    srv = DecodeServer(params, cfg, max_batch=2, max_len=32, pad_to=4)
    rid = srv.submit(prompt, n)
    srv.run_until_done(max_steps=50)
    assert srv.outputs[rid] == ref


# ---------------------------------------------------------------------
# speculative serving

@pytest.fixture(scope="module")
def spec_setup():
    cfg = tiny_config(dtype=jnp.float32, use_flash=False)
    target = init_params(jax.random.PRNGKey(0), cfg)
    draft = init_params(jax.random.PRNGKey(42), cfg)  # a WORSE model
    return cfg, target, draft


def test_spec_serving_matches_solo_generate_staggered(spec_setup):
    """Greedy speculative serving must reproduce the TARGET's own
    greedy decode per request (the draft only affects speed), under
    staggered admission into a 2-slot pool."""
    cfg, target, draft = spec_setup
    reqs = [([5, 9, 2], 9), ([7, 1, 3, 11], 6), ([2, 2], 7)]
    srv = DecodeServer(target, cfg, max_batch=2, max_len=64, pad_to=4,
                       draft_params=draft, draft_cfg=cfg, gamma=3)
    r0 = srv.submit(*reqs[0])
    srv.step()
    r1 = srv.submit(*reqs[1])
    srv.step()
    r2 = srv.submit(*reqs[2])
    srv.run_until_done(max_steps=100)
    for rid, (prompt, n) in zip((r0, r1, r2), reqs):
        assert srv.outputs[rid] == solo(target, cfg, prompt, n), rid
        assert len(srv.outputs[rid]) == n


def test_spec_serving_emits_multiple_tokens_per_step(spec_setup):
    """A self-draft accepts everything: each round must emit
    gamma + 1 tokens for the slot (the mechanics of batched verify)."""
    cfg, target, _ = spec_setup
    srv = DecodeServer(target, cfg, max_batch=1, max_len=64, pad_to=4,
                       draft_params=target, draft_cfg=cfg, gamma=3)
    rid = srv.submit([5, 9, 2], 13)
    out = srv.step()
    assert out[rid] and len(out[rid]) == 4   # gamma + 1 accepted
    srv.run_until_done(max_steps=20)
    assert len(srv.outputs[rid]) == 13
    assert srv.outputs[rid] == solo(target, cfg, [5, 9, 2], 13)


def test_spec_serving_eos_cuts_mid_round(spec_setup):
    cfg, target, draft = spec_setup
    prompt, n = [5, 9, 2], 10
    toks = solo(target, cfg, prompt, n)
    eos = toks[4]
    srv = DecodeServer(target, cfg, max_batch=1, max_len=64, pad_to=4,
                       eos_id=eos, draft_params=draft, draft_cfg=cfg,
                       gamma=3)
    rid = srv.submit(prompt, n)
    srv.run_until_done(max_steps=50)
    got = srv.outputs[rid]
    assert got[-1] == eos
    assert got == toks[: got.index(eos) + 1]


def test_spec_serving_top_k1_matches_solo_greedy(spec_setup):
    """Speculative serving with sampling + top_k=1 (deterministic
    truncation) must reproduce the target's greedy decode per request
    — the truncation-aware acceptance path through the server."""
    from nbdistributed_tpu.models import generate

    cfg, target, draft = spec_setup
    srv = DecodeServer(target, cfg, max_batch=2, max_len=64, pad_to=4,
                       temperature=0.8, top_k=1,
                       draft_params=draft, draft_cfg=cfg, gamma=3,
                       key=jax.random.PRNGKey(11))
    reqs = [([5, 9, 2], 8), ([7, 1, 3, 11], 6)]
    rids = [srv.submit(*r) for r in reqs]
    srv.run_until_done(max_steps=100)
    for rid, (prompt, n) in zip(rids, reqs):
        solo = generate(target, jnp.asarray([prompt], jnp.int32),
                        cfg, n)
        assert srv.outputs[rid] == [int(t) for t in
                                    solo[0, len(prompt):]]


def test_spec_serving_validation(spec_setup):
    cfg, target, draft = spec_setup
    with pytest.raises(ValueError, match="both draft_params"):
        DecodeServer(target, cfg, max_batch=1, max_len=32,
                     draft_params=draft)
    with pytest.raises(ValueError, match="top_k"):
        DecodeServer(target, cfg, max_batch=1, max_len=32, top_k=0)
    with pytest.raises(ValueError, match="top_p"):
        DecodeServer(target, cfg, max_batch=1, max_len=32, top_p=0.0)
    srv = DecodeServer(target, cfg, max_batch=1, max_len=16, pad_to=4,
                       draft_params=draft, draft_cfg=cfg, gamma=3)
    with pytest.raises(ValueError, match="speculative headroom"):
        srv.submit([1, 2, 3, 4], 9)   # 4 + 9 + 4 > 16


def test_step_many_matches_single_steps(setup):
    """step_many(n) must emit exactly what n successive step() calls
    emit (greedy), amortizing the host sync without changing tokens."""
    cfg, params = setup
    reqs = [([5, 9, 2], 9), ([7, 1, 3, 11], 7)]
    a = DecodeServer(params, cfg, max_batch=2, max_len=64, pad_to=4)
    b = DecodeServer(params, cfg, max_batch=2, max_len=64, pad_to=4)
    ra = [a.submit(*r) for r in reqs]
    rb = [b.submit(*r) for r in reqs]
    for _ in range(8):
        a.step()
    b.step_many(4)
    b.step_many(4)
    for x, y in zip(ra, rb):
        assert a.outputs[x] == b.outputs[y]
    a.run_until_done(max_steps=20)
    b.run_until_done(max_steps=20)
    for x, y, (prompt, n) in zip(ra, rb, reqs):
        assert b.outputs[y] == solo(params, cfg, prompt, n)


def test_step_many_truncates_budget_and_eos(setup):
    cfg, params = setup
    prompt, n = [5, 9, 2], 6
    toks = solo(params, cfg, prompt, n)
    # Budget cut mid-scan: ask for 6, scan 8 past the end.
    srv = DecodeServer(params, cfg, max_batch=1, max_len=64, pad_to=4)
    rid = srv.submit(prompt, n)
    out = srv.step_many(8)
    assert out[rid] == toks[1:]          # seed emitted at admission
    assert srv.done() and len(srv.outputs[rid]) == n
    # EOS cut mid-scan.
    eos = toks[3]
    srv = DecodeServer(params, cfg, max_batch=1, max_len=64, pad_to=4,
                       eos_id=eos)
    rid = srv.submit(prompt, 8)
    srv.step_many(8)
    got = srv.outputs[rid]
    assert got[-1] == eos and got == toks[: got.index(eos) + 1]


def test_step_many_admits_at_boundaries(setup):
    """A request queued while a scan runs is admitted at the next
    boundary and still matches its solo decode."""
    cfg, params = setup
    srv = DecodeServer(params, cfg, max_batch=1, max_len=64, pad_to=4)
    r0 = srv.submit([5, 9, 2], 5)
    r1 = srv.submit([7, 1], 4)           # queued: one slot
    srv.step_many(4)                     # finishes r0, admits r1
    srv.run_until_done(max_steps=20)
    assert srv.outputs[r0] == solo(params, cfg, [5, 9, 2], 5)
    assert srv.outputs[r1] == solo(params, cfg, [7, 1], 4)


def test_step_many_validation(setup, spec_setup):
    cfg, params = setup
    srv = DecodeServer(params, cfg, max_batch=1, max_len=32, pad_to=4)
    with pytest.raises(ValueError, match=">= 1"):
        srv.step_many(0)
    _, target, draft = spec_setup
    ssrv = DecodeServer(target, cfg, max_batch=1, max_len=32, pad_to=4,
                        draft_params=draft, draft_cfg=cfg)
    with pytest.raises(ValueError, match="plain serving"):
        ssrv.step_many(2)


# ---------------------------------------------------------------------
# chunked prefill admission

@pytest.mark.parametrize("L", [7, 12, 13])
def test_chunked_prefill_matches_solo(setup, L):
    """Chunked admission (chunk=4: exact-multiple, tail, and
    shorter-than-chunk prompts) must be invisible to the numerics —
    outputs equal solo generate and bucketed admission."""
    cfg, params = setup
    prompt = [int(t) for t in jax.random.randint(
        jax.random.PRNGKey(40 + L), (L,), 1, cfg.vocab_size)]
    n = 5
    ref = solo(params, cfg, prompt, n)
    srv = DecodeServer(params, cfg, max_batch=2, max_len=64, pad_to=4,
                       prefill_chunk=4)
    rid = srv.submit(prompt, n)
    srv.run_until_done(max_steps=30)
    assert srv.outputs[rid] == ref


def test_chunked_prefill_single_compile_shape(setup):
    """Every chunk segment shares one (1, chunk) program: admitting
    prompts of different lengths > chunk adds ONE prefill executable,
    where bucketed admission would mint one per bucket."""
    cfg, params = setup
    srv = DecodeServer(params, cfg, max_batch=2, max_len=64, pad_to=4,
                       prefill_chunk=4)
    if not hasattr(srv._prefill_fn, "_cache_size"):
        pytest.skip("jit cache introspection unavailable")
    r0 = srv.submit([int(t) for t in range(1, 10)], 2)    # L=9
    r1 = srv.submit([int(t) for t in range(1, 14)], 2)    # L=13
    srv.run_until_done(max_steps=20)
    assert srv._prefill_fn._cache_size() == 1
    assert len(srv.outputs[r0]) == 2 and len(srv.outputs[r1]) == 2


def test_chunked_prefill_speculative(spec_setup):
    """Chunked admission composes with speculative serving: both
    caches prefill chunk-wise; greedy output equals the target's."""
    from nbdistributed_tpu.models import generate

    cfg, target, draft = spec_setup
    prompt = [5, 9, 2, 7, 1, 3, 11, 4, 6]                 # L=9
    n = 6
    srv = DecodeServer(target, cfg, max_batch=1, max_len=64, pad_to=4,
                       draft_params=draft, draft_cfg=cfg, gamma=3,
                       prefill_chunk=4)
    rid = srv.submit(prompt, n)
    srv.run_until_done(max_steps=30)
    solo_toks = generate(target, jnp.asarray([prompt], jnp.int32),
                         cfg, n)
    assert srv.outputs[rid] == [int(t) for t in
                                solo_toks[0, len(prompt):]]


def test_chunked_prefill_rejected_for_moe():
    from nbdistributed_tpu.models import init_moe_model, tiny_moe_config
    cfg = tiny_moe_config(dtype=jnp.float32, use_flash=False)
    params = init_moe_model(jax.random.PRNGKey(4), cfg)
    with pytest.raises(ValueError, match="capacity-based"):
        DecodeServer(params, cfg, max_batch=1, max_len=32,
                     prefill_chunk=8)
    with pytest.raises(ValueError, match="prefill_chunk"):
        DecodeServer(params, tiny_config(dtype=jnp.float32,
                                         use_flash=False),
                     max_batch=1, max_len=32, prefill_chunk=0)


# ---------------------------------------------------------------------
# spec_step_many: device-side multi-round speculation

def test_spec_step_many_matches_single_steps(spec_setup):
    """spec_step_many(n) must emit exactly what n successive step()
    calls emit (greedy speculative), and both must equal solo
    generate."""
    cfg, target, draft = spec_setup
    reqs = [([5, 9, 2], 9), ([7, 1, 3, 11], 7)]
    mk = lambda: DecodeServer(target, cfg, max_batch=2, max_len=64,
                              pad_to=4, draft_params=draft,
                              draft_cfg=cfg, gamma=3)
    a, b = mk(), mk()
    ra = [a.submit(*r) for r in reqs]
    rb = [b.submit(*r) for r in reqs]
    for _ in range(4):
        a.step()
    b.spec_step_many(2)
    b.spec_step_many(2)
    for x, y in zip(ra, rb):
        assert a.outputs[x] == b.outputs[y]
    while not b.done():
        b.spec_step_many(2)
    for y, (prompt, n) in zip(rb, reqs):
        assert b.outputs[y] == solo(target, cfg, prompt, n)


def test_spec_step_many_freezes_at_max_len(spec_setup):
    """A stream at the tightest legal max_len (prompt + budget +
    gamma + 1): surplus rounds self-freeze device-side instead of
    overflowing the cache, and the output is exactly the budget."""
    cfg, target, draft = spec_setup
    prompt, n, gamma = [5, 9, 2], 6, 3
    T = len(prompt) + n + gamma + 1                  # == 13
    srv = DecodeServer(target, cfg, max_batch=1, max_len=T, pad_to=4,
                       draft_params=draft, draft_cfg=cfg, gamma=gamma)
    rid = srv.submit(prompt, n)
    while not srv.done():
        srv.spec_step_many(4)                        # overshoots freely
    assert srv.outputs[rid] == solo(target, cfg, prompt, n)


def test_spec_step_many_eos_cut(spec_setup):
    """EOS discovered mid-scan truncates host-side exactly like the
    single-round path."""
    cfg, target, draft = spec_setup
    prompt, n = [5, 9, 2], 8
    toks = solo(target, cfg, prompt, n)
    eos = toks[3]
    srv = DecodeServer(target, cfg, max_batch=1, max_len=64, pad_to=4,
                       draft_params=draft, draft_cfg=cfg, gamma=3,
                       eos_id=eos)
    rid = srv.submit(prompt, n)
    while not srv.done():
        srv.spec_step_many(3)
    got = srv.outputs[rid]
    assert got == toks[: toks.index(eos) + 1]


def test_spec_step_many_validation(setup, spec_setup):
    cfg, params = setup
    srv = DecodeServer(params, cfg, max_batch=1, max_len=32, pad_to=4)
    with pytest.raises(ValueError, match="speculative server"):
        srv.spec_step_many(2)
    _, target, draft = spec_setup
    ssrv = DecodeServer(target, cfg, max_batch=1, max_len=32, pad_to=4,
                        draft_params=draft, draft_cfg=cfg)
    with pytest.raises(ValueError, match=">= 1"):
        ssrv.spec_step_many(0)


# ---------------------------------------------------------------------
# prefix caching (cache_prefix / drop_prefix): shared system prompts
# admit by copying a prefilled KV block + suffix-only prefill

def test_prefix_cache_matches_solo_generate(setup):
    """N requests sharing a system prefix, admitted via cache_prefix:
    every request's greedy tokens must equal its standalone generate()
    run — the copied KV rows are bit-identical to a full prefill's
    (causal attention + absolute RoPE), so solo-equality survives."""
    cfg, params = setup
    sys_prefix = [3, 1, 4, 1, 5, 9, 2, 6]
    suffixes = [[5, 3], [8, 8, 8], [1], [9, 7, 9, 7]]
    srv = DecodeServer(params, cfg, max_batch=2, max_len=64, pad_to=4)
    pid = srv.cache_prefix(sys_prefix)
    assert pid == 0
    rids = [srv.submit(sys_prefix + s, 5) for s in suffixes]
    srv.run_until_done(max_steps=200)
    for rid, s in zip(rids, suffixes):
        assert srv.outputs[rid] == solo(params, cfg, sys_prefix + s, 5), \
            (rid, s)


def test_prefix_cache_whole_prompt_hit(setup):
    """A prompt EQUAL to the cached prefix admits with zero prefill
    forwards (the stored last-token logits seed the stream)."""
    cfg, params = setup
    prefix = [2, 7, 1, 8, 2, 8]
    srv = DecodeServer(params, cfg, max_batch=1, max_len=32, pad_to=4)
    srv.cache_prefix(prefix)
    calls = []
    orig = srv._prefill_fn
    srv._prefill_fn = (lambda *a, **k: calls.append(1) or orig(*a, **k))
    rid = srv.submit(prefix, 4)
    srv.run_until_done(max_steps=50)
    assert calls == []          # no prefill forward ran at admission
    assert srv.outputs[rid] == solo(params, cfg, prefix, 4)


def test_prefix_cache_longest_match_and_miss(setup):
    """Longest registered prefix wins; non-matching prompts take the
    plain path; drop_prefix frees and unmatches."""
    cfg, params = setup
    srv = DecodeServer(params, cfg, max_batch=2, max_len=64, pad_to=4)
    p_short = srv.cache_prefix([4, 2])
    p_long = srv.cache_prefix([4, 2, 6, 1])
    assert srv._match_prefix([4, 2, 6, 1, 9]) == p_long
    assert srv._match_prefix([4, 2, 9]) == p_short
    assert srv._match_prefix([9, 9]) is None
    # Both matched and unmatched prompts produce solo-exact streams.
    reqs = [([4, 2, 6, 1, 9], 5), ([9, 9, 3], 5)]
    rids = [srv.submit(p, n) for p, n in reqs]
    srv.run_until_done(max_steps=100)
    for rid, (p, n) in zip(rids, reqs):
        assert srv.outputs[rid] == solo(params, cfg, p, n)
    srv.drop_prefix(p_long)
    assert srv._match_prefix([4, 2, 6, 1, 9]) == p_short
    with pytest.raises(KeyError):
        srv.drop_prefix(p_long)


def test_prefix_cache_saves_prefill_tokens(setup):
    """The admission-cost win: with a cached 16-token prefix, each
    admission's prefill forward sees only the suffix bucket, not the
    whole prompt — count the token positions fed through prefill."""
    cfg, params = setup
    prefix = list(range(1, 17))              # 16 tokens
    suffix = [7, 3]
    fed = {"with": 0, "without": 0}

    def counting(srv, key):
        orig = srv._prefill_fn

        def wrapper(p, cache, prompt, slot, start, length):
            fed[key] += prompt.shape[1]
            return orig(p, cache, prompt, slot, start, length)

        srv._prefill_fn = wrapper

    srv_a = DecodeServer(params, cfg, max_batch=1, max_len=64, pad_to=4)
    pid = srv_a.cache_prefix(prefix)         # one-time prefix prefill
    counting(srv_a, "with")
    srv_b = DecodeServer(params, cfg, max_batch=1, max_len=64, pad_to=4)
    counting(srv_b, "without")
    for srv, key in ((srv_a, "with"), (srv_b, "without")):
        for _ in range(3):
            srv.submit(prefix + suffix, 3)
        srv.run_until_done(max_steps=100)
    assert fed["with"] == 3 * 4              # 3 suffix buckets (pad 4)
    assert fed["without"] == 3 * 20          # 3 whole-prompt buckets
    assert list(srv_a.outputs.values()) == list(srv_b.outputs.values())


def test_prefix_cache_speculative(spec_setup):
    """Prefix admission composes with speculative serving: target AND
    draft caches absorb the prefix block; greedy streams match the
    plain server's."""
    cfg, params, dparams = spec_setup
    prefix = [5, 1, 5, 1, 5, 1]
    reqs = [(prefix + [2, 6], 6), (prefix + [9], 6)]
    srv = DecodeServer(params, cfg, max_batch=2, max_len=64, pad_to=4,
                       draft_params=dparams, draft_cfg=cfg, gamma=2)
    srv.cache_prefix(prefix)
    rids = [srv.submit(p, n) for p, n in reqs]
    srv.run_until_done(max_steps=100)
    for rid, (p, n) in zip(rids, reqs):
        assert srv.outputs[rid] == solo(params, cfg, p, n)


def test_prefix_cache_chunked_prefill_compose(setup):
    """A long prefix built through chunked prefill + chunked suffix
    admission still reproduces solo generate()."""
    cfg, params = setup
    prefix = [(i * 7) % 50 + 1 for i in range(37)]   # > chunk
    suffix = [3, 3, 9, 27, 5]
    srv = DecodeServer(params, cfg, max_batch=1, max_len=128, pad_to=4,
                       prefill_chunk=16)
    srv.cache_prefix(prefix)
    rid = srv.submit(prefix + suffix, 6)
    srv.run_until_done(max_steps=100)
    assert srv.outputs[rid] == solo(params, cfg, prefix + suffix, 6)


def test_prefix_cache_int8_kv(setup):
    """Prefix blocks copy through the quantized cache's int8+scale
    leaves; streams match the int8 solo run."""
    cfg, params = setup
    prefix = [6, 2, 8, 4]
    srv = DecodeServer(params, cfg, max_batch=1, max_len=32, pad_to=4,
                       kv_quantized=True)
    srv.cache_prefix(prefix)
    rid = srv.submit(prefix + [1, 3], 4)
    srv.run_until_done(max_steps=50)
    out = generate(params,
                   jnp.asarray(prefix + [1, 3], jnp.int32)[None], cfg,
                   4, kv_quantized=True)
    want = [int(t) for t in np.asarray(out)[0][6:]]
    assert srv.outputs[rid] == want


def test_prefix_cache_rejected_for_moe():
    from nbdistributed_tpu.models import (init_moe_model,
                                          tiny_moe_config)
    cfg = tiny_moe_config(dtype=jnp.float32, use_flash=False)
    params = init_moe_model(jax.random.PRNGKey(0), cfg)
    srv = DecodeServer(params, cfg, max_batch=1, max_len=32)
    with pytest.raises(ValueError, match="capacity-based"):
        srv.cache_prefix([1, 2, 3])


def test_prefix_cache_validation(setup):
    cfg, params = setup
    srv = DecodeServer(params, cfg, max_batch=1, max_len=16)
    with pytest.raises(ValueError, match="empty"):
        srv.cache_prefix([])
    with pytest.raises(ValueError, match="max_len"):
        srv.cache_prefix(list(range(16)))


def test_prefix_cache_on_mesh(setup):
    """Prefix admission over a dp×tp mesh: the prefix buffer is
    tp-sharded like the pool (batch/token replicated — a 1-slot
    buffer can't split over dp), the absorb copy preserves the pool's
    layout through donation, and streams stay solo-exact."""
    from nbdistributed_tpu.models import param_shardings
    from nbdistributed_tpu.parallel import mesh as mesh_mod
    from nbdistributed_tpu.parallel.tensor_parallel import \
        apply_shardings
    cfg, params = setup
    mesh = mesh_mod.make_mesh({"dp": 2, "tp": 2},
                              devices=jax.devices()[:4])
    ps = apply_shardings(params, mesh, param_shardings(cfg))
    prefix = [3, 1, 4, 1, 5, 9]
    reqs = [(prefix + [2, 6], 5), (prefix + [8], 5), ([9, 9], 5)]
    srv = DecodeServer(ps, cfg, max_batch=2, max_len=32, pad_to=4,
                       mesh=mesh)
    srv.cache_prefix(prefix)
    rids = [srv.submit(p, n) for p, n in reqs]
    srv.run_until_done(max_steps=100)
    for rid, (p, n) in zip(rids, reqs):
        assert srv.outputs[rid] == solo(params, cfg, p, n), (rid, p)
