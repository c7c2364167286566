"""KV-cache generation: exactness vs full re-forward decoding, sampling
determinism, and tensor-parallel cache sharding."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nbdistributed_tpu.models import (forward, forward_with_cache,
                                      generate, init_kv_cache,
                                      init_params, kv_cache_shardings,
                                      make_generate_fn, param_shardings,
                                      tiny_config)

# Heavy interpret-mode kernel/model tests: excluded from the
# fast product-path tier (`pytest -m "not slow"`).
pytestmark = [pytest.mark.unit, pytest.mark.slow]


@pytest.fixture(scope="module")
def setup():
    cfg = tiny_config(dtype=jnp.float32, use_flash=False)
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def full_forward_greedy(params, prompt, cfg, n_new):
    """Reference decoder: re-run the whole sequence each step, no cache."""
    toks = prompt
    for _ in range(n_new):
        logits = forward(params, toks, cfg)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        toks = jnp.concatenate([toks, nxt[:, None]], axis=1)
    return toks


def test_prefill_logits_match_forward(setup):
    cfg, params = setup
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 11), 0,
                                cfg.vocab_size)
    cache = init_kv_cache(cfg, 2, 32)
    logits, _ = forward_with_cache(params, prompt, cache, 0, cfg)
    ref = forward(params, prompt, cfg)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


def test_cached_greedy_matches_full_reforward(setup):
    cfg, params = setup
    prompt = jax.random.randint(jax.random.PRNGKey(2), (2, 7), 0,
                                cfg.vocab_size)
    got = generate(params, prompt, cfg, max_new_tokens=12)
    ref = full_forward_greedy(params, prompt, cfg, 12)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_single_new_token(setup):
    cfg, params = setup
    prompt = jax.random.randint(jax.random.PRNGKey(3), (1, 5), 0,
                                cfg.vocab_size)
    got = generate(params, prompt, cfg, max_new_tokens=1)
    ref = full_forward_greedy(params, prompt, cfg, 1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_sampling_deterministic_per_key_and_in_vocab(setup):
    cfg, params = setup
    prompt = jax.random.randint(jax.random.PRNGKey(4), (2, 4), 0,
                                cfg.vocab_size)
    key = jax.random.PRNGKey(7)
    a = generate(params, prompt, cfg, 8, temperature=0.8, key=key)
    b = generate(params, prompt, cfg, 8, temperature=0.8, key=key)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(jnp.max(a)) < cfg.vocab_size and int(jnp.min(a)) >= 0


def test_sampling_requires_key(setup):
    cfg, params = setup
    prompt = jnp.zeros((1, 3), jnp.int32)
    with pytest.raises(ValueError, match="PRNG key"):
        generate(params, prompt, cfg, 2, temperature=0.5)


def test_max_len_too_small_raises(setup):
    cfg, params = setup
    prompt = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(ValueError, match="max_len"):
        generate(params, prompt, cfg, 8, max_len=10)


def test_jitted_generate_fn(setup):
    cfg, params = setup
    prompt = jax.random.randint(jax.random.PRNGKey(5), (2, 6), 0,
                                cfg.vocab_size)
    fn = make_generate_fn(cfg, 5)
    got = fn(params, prompt)
    ref = full_forward_greedy(params, prompt, cfg, 5)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_tensor_parallel_generate_matches(setup):
    """Greedy decode with params + cache sharded over a tp mesh equals
    the unsharded decode."""
    from nbdistributed_tpu.parallel import mesh as mesh_mod
    from nbdistributed_tpu.parallel import tensor_parallel

    cfg, params = setup  # tiny: n_heads=4, n_kv_heads=2 -> tp=2 fits
    mesh = mesh_mod.make_mesh({"dp": 2, "tp": 2},
                              devices=jax.devices()[:4])
    rules = param_shardings(cfg)
    p = tensor_parallel.apply_shardings(params, mesh, rules)
    prompt = jax.random.randint(jax.random.PRNGKey(6), (2, 5), 0,
                                cfg.vocab_size)
    ref = generate(params, prompt, cfg, 6)
    # mesh= also shards the KV cache (batch over dp, KV heads over tp).
    got = generate(p, prompt, cfg, 6, mesh=mesh)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_sharded_cache_layout_is_applied(setup):
    from jax.sharding import PartitionSpec as P
    from nbdistributed_tpu.parallel import mesh as mesh_mod

    cfg, _ = setup
    mesh = mesh_mod.make_mesh({"dp": 2, "tp": 2},
                              devices=jax.devices()[:4])
    cache = init_kv_cache(cfg, 2, 16, mesh=mesh)
    assert cache["k"].sharding.spec == P(None, "dp", "tp", None, None)
    assert len(cache["k"].sharding.device_set) == 4


def test_zero_new_tokens_returns_prompt(setup):
    cfg, params = setup
    prompt = jnp.ones((2, 5), jnp.int32)
    out = generate(params, prompt, cfg, 0)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(prompt))
    with pytest.raises(ValueError, match=">= 0"):
        generate(params, prompt, cfg, -1)


def test_moe_cached_greedy_matches_full_reforward():
    """The MoE family decodes through the same cached forward; lossless
    capacity (factor 2 >= n_experts/top_k) makes batched prefill and
    step-wise decode route identically, so tokens must match exactly."""
    from nbdistributed_tpu.models import (init_moe_model, moe_forward,
                                          tiny_moe_config)

    cfg = tiny_moe_config(dtype=jnp.float32, use_flash=False,
                          capacity_factor=2.0)
    params = init_moe_model(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(2), (2, 6), 0,
                                cfg.vocab_size)
    got = generate(params, prompt, cfg, max_new_tokens=8)
    toks = prompt
    for _ in range(8):
        logits, _aux = moe_forward(params, toks, cfg)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        toks = jnp.concatenate([toks, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(toks))


def test_cache_sharding_spec_shape(setup):
    cfg, _ = setup
    spec = kv_cache_shardings()
    cache = init_kv_cache(cfg, 2, 16)
    assert len(spec["k"]) == cache["k"].ndim

def test_top_k_restricts_support(setup):
    """With top_k=1, sampling at any temperature must equal greedy."""
    cfg, params = setup
    prompt = jax.random.randint(jax.random.PRNGKey(8), (2, 5), 0,
                                cfg.vocab_size)
    greedy = generate(params, prompt, cfg, 8)
    sampled = generate(params, prompt, cfg, 8, temperature=1.5,
                       top_k=1, key=jax.random.PRNGKey(9))
    np.testing.assert_array_equal(np.asarray(sampled), np.asarray(greedy))


def test_top_k_unit_sampler_support():
    """Directly check _sample only ever emits tokens inside the top-k
    set of each row."""
    from nbdistributed_tpu.models.generate import _sample
    logits = jax.random.normal(jax.random.PRNGKey(0), (4, 64))
    topk_sets = np.argsort(np.asarray(logits), axis=-1)[:, -8:]
    for seed in range(5):
        tok = _sample(logits, 1.0, jax.random.PRNGKey(seed), 8, None)
        for b in range(4):
            assert int(tok[b]) in topk_sets[b]


def test_top_p_keeps_top_token_and_restricts():
    """Nucleus sampling with a tiny top_p degenerates to greedy; with
    top_p=1.0 it must match unfiltered categorical exactly."""
    from nbdistributed_tpu.models.generate import _sample
    logits = jax.random.normal(jax.random.PRNGKey(1), (4, 64)) * 3
    key = jax.random.PRNGKey(2)
    # Tiny nucleus -> only the argmax survives.
    tok = _sample(logits, 1.0, key, None, 1e-6)
    np.testing.assert_array_equal(np.asarray(tok),
                                  np.argmax(np.asarray(logits), axis=-1))
    # Full nucleus -> identical distribution (same key) as no filter.
    a = _sample(logits, 0.7, key, None, 1.0)
    b = _sample(logits, 0.7, key, None, None)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_top_p_excludes_tail():
    """A spiked distribution with two dominant tokens: top_p=0.9 must
    never sample outside those two."""
    from nbdistributed_tpu.models.generate import _sample
    logits = np.full((1, 32), -10.0, np.float32)
    logits[0, 3] = 5.0
    logits[0, 17] = 4.5
    logits = jnp.asarray(logits)
    for seed in range(20):
        tok = _sample(logits, 1.0, jax.random.PRNGKey(seed), None, 0.9)
        assert int(tok[0]) in (3, 17)


def test_generate_validates_sampler_args(setup):
    cfg, params = setup
    prompt = jnp.zeros((1, 3), jnp.int32)
    with pytest.raises(ValueError, match="top_k"):
        generate(params, prompt, cfg, 2, temperature=1.0, top_k=0,
                 key=jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="top_p"):
        generate(params, prompt, cfg, 2, temperature=1.0, top_p=0.0,
                 key=jax.random.PRNGKey(0))
    # top_k above the vocabulary must fail at the argument, not as an
    # opaque lax.top_k trace error.
    with pytest.raises(ValueError, match="vocab_size"):
        generate(params, prompt, cfg, 2, temperature=1.0,
                 top_k=cfg.vocab_size + 1, key=jax.random.PRNGKey(0))


def test_empty_prompt_prefill_raises(setup):
    """prefill_chunked(S=0) must not silently return the zero init
    logits (which would seed decode with token 0)."""
    from nbdistributed_tpu.models import init_kv_cache, prefill_chunked
    cfg, params = setup
    cache = init_kv_cache(cfg, 1, 8)
    with pytest.raises(ValueError, match="empty prompt"):
        prefill_chunked(params, jnp.zeros((1, 0), jnp.int32), cache,
                        cfg, chunk=4)
    with pytest.raises(ValueError, match="empty prompt"):
        generate(params, jnp.zeros((1, 0), jnp.int32), cfg, 3)


def test_quantized_cache_with_stale_rules_raises(setup):
    """A caller-supplied rules dict that predates quantization (only
    k/v specs) must fail with a named error, not a KeyError."""
    from nbdistributed_tpu.parallel.mesh import make_mesh
    cfg, _ = setup
    mesh = make_mesh({"tp": 2}, devices=jax.devices()[:2])
    stale = kv_cache_shardings(dp_axis=None, tp_axis="tp",
                               quantized=False)
    with pytest.raises(ValueError, match="k_s"):
        init_kv_cache(cfg, 2, 16, mesh=mesh, rules=stale,
                      quantized=True)


def test_jitted_top_k_top_p(setup):
    """The truncated sampler must scan/jit (static shapes)."""
    cfg, params = setup
    prompt = jax.random.randint(jax.random.PRNGKey(10), (2, 4), 0,
                                cfg.vocab_size)
    fn = make_generate_fn(cfg, 6, temperature=0.9, top_k=10, top_p=0.95)
    out = fn(params, prompt, jax.random.PRNGKey(11))
    assert out.shape == (2, 10)
    assert int(jnp.max(out)) < cfg.vocab_size and int(jnp.min(out)) >= 0


def test_kv_quantized_generation_close_to_fp(setup):
    """Int8-cache generation: single-step logits close to the fp cache
    path, full generation runs, and both caches agree on the argmax
    chain for a short horizon."""
    from nbdistributed_tpu.models import forward_with_cache, init_kv_cache
    cfg, params = setup
    prompt = jax.random.randint(jax.random.PRNGKey(20), (2, 9), 0,
                                cfg.vocab_size)
    # Prefill logits: quantized cache vs fp cache.
    c_fp = init_kv_cache(cfg, 2, 32)
    c_q8 = init_kv_cache(cfg, 2, 32, quantized=True)
    assert c_q8["k"].dtype == jnp.int8 and "k_s" in c_q8
    lf, _ = forward_with_cache(params, prompt, c_fp, 0, cfg)
    lq, cq = forward_with_cache(params, prompt, c_q8, 0, cfg)
    nmse = float(jnp.mean((lq - lf) ** 2) / jnp.mean(lf ** 2))
    assert nmse < 1e-3, nmse
    # One decode step off the quantized cache.
    nxt = jnp.argmax(lq[:, -1:], axis=-1).astype(jnp.int32)
    l2, _ = forward_with_cache(params, nxt, cq, 9, cfg)
    assert l2.shape == (2, 1, cfg.vocab_size)
    # Full generation with the quantized cache.
    got = generate(params, prompt, cfg, max_new_tokens=8,
                   kv_quantized=True)
    ref = generate(params, prompt, cfg, max_new_tokens=8)
    assert got.shape == ref.shape
    agree = float(jnp.mean((got[:, 9:] == ref[:, 9:]).astype(jnp.float32)))
    assert agree > 0.7, agree


def test_kv_quantized_on_tp_mesh(setup):
    """Quantized cache + tp-sharded params through the mesh decode path."""
    from nbdistributed_tpu.models import param_shardings
    from nbdistributed_tpu.parallel.mesh import make_mesh
    from jax.sharding import NamedSharding
    cfg, params = setup
    mesh = make_mesh({"dp": 2, "tp": 2}, devices=jax.devices()[:4])
    p_s = jax.device_put(params, jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), param_shardings(cfg)))
    prompt = jax.random.randint(jax.random.PRNGKey(21), (2, 6), 0,
                                cfg.vocab_size)
    got = generate(p_s, prompt, cfg, max_new_tokens=6, mesh=mesh,
                   kv_quantized=True)
    ref = generate(params, prompt, cfg, max_new_tokens=6,
                   kv_quantized=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_chunked_prefill_matches_single_shot(setup):
    """Chunked prefill must fill the cache identically to one-shot
    prefill and produce the same last-position logits — for fp and
    int8 caches."""
    from nbdistributed_tpu.models import (forward_with_cache,
                                          init_kv_cache,
                                          prefill_chunked)
    cfg, params = setup
    prompt = jax.random.randint(jax.random.PRNGKey(30), (2, 12), 0,
                                cfg.vocab_size)
    for quantized in (False, True):
        c1 = init_kv_cache(cfg, 2, 24, quantized=quantized)
        ref_logits, ref_cache = forward_with_cache(
            params, prompt, c1, 0, cfg, last_only=True)
        c2 = init_kv_cache(cfg, 2, 24, quantized=quantized)
        got_logits, got_cache = jax.jit(
            lambda p, t, c: prefill_chunked(p, t, c, cfg, chunk=4)
        )(params, prompt, c2)
        np.testing.assert_allclose(np.asarray(got_logits),
                                   np.asarray(ref_logits),
                                   atol=1e-4, rtol=1e-4,
                                   err_msg=f"quantized={quantized}")
        for k in ref_cache:
            np.testing.assert_allclose(
                np.asarray(got_cache[k]).astype(np.float32),
                np.asarray(ref_cache[k]).astype(np.float32),
                atol=1e-5, rtol=1e-5, err_msg=f"{k} q={quantized}")
    with pytest.raises(ValueError, match="divisible"):
        prefill_chunked(params, prompt,
                        init_kv_cache(cfg, 2, 24), cfg, chunk=5)
