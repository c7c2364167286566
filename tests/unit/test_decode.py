"""Pallas flash-decode kernel: exact vs the einsum cached-attention
path, GQA grouping, ragged cache lengths, and the generation wiring."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nbdistributed_tpu.ops.decode import flash_decode_attention

# Heavy interpret-mode kernel/model tests: excluded from the
# fast product-path tier (`pytest -m "not slow"`).
pytestmark = [pytest.mark.unit, pytest.mark.slow]


def reference(q, kc, vc, pos):
    B, H, D = q.shape
    Hkv, T = kc.shape[1], kc.shape[2]
    group = H // Hkv
    qg = q.reshape(B, Hkv, group, D).astype(jnp.float32) / np.sqrt(D)
    s = jnp.einsum("bkgd,bktd->bkgt", qg, kc.astype(jnp.float32))
    mask = jnp.arange(T)[None, None, None, :] <= pos[:, None, None, None]
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, -1)
    o = jnp.einsum("bkgt,bktd->bkgd", p, vc.astype(jnp.float32))
    return o.reshape(B, H, D).astype(q.dtype)


# (n_heads, n_kv_heads, head_dim) of the models the tree serves, one
# for each size of query group: the one default block_k (128 keys)
# has to be right for every one of them.
_SERVED_HEADS = [(4, 4, 128),     # group 1: Llama-2-7B's ratio
                 (4, 2, 64),      # group 2: Phi-4-mini-flash
                 (8, 2, 128),     # group 4: Mistral-7B
                 (8, 1, 64),      # group 8: TinyLlama
                 (8, 1, 128),     # group 8: SDAR-30B-A3B
                 (16, 1, 128)]    # group 16: Nemotron-3-Nano


_TOY_HEADS = (8, 4, 16)


@pytest.mark.parametrize("T,pos,heads", [
    (40, [10, 25], _TOY_HEADS), (128, [0, 127], _TOY_HEADS),
    (37, [36, 5], _TOY_HEADS),
    # overlapping final block: T > 128, not a block multiple (the old
    # gcd fallback collapsed these to 1-wide blocks)
    (129, [128, 60], _TOY_HEADS), (200, [199, 130], _TOY_HEADS),
    # T = block_k + 1 with pos at both extremes: first slot only, and
    # the lone slot owned by the final block
    (129, [0, 128], _TOY_HEADS),
    # the served head shapes over a cache that is no multiple of 128
    *[(200, [199, 130], heads) for heads in _SERVED_HEADS]])
def test_decode_matches_reference(T, pos, heads):
    B = 2
    H, Hkv, D = heads
    kc = jax.random.normal(jax.random.PRNGKey(0), (B, Hkv, T, D))
    vc = jax.random.normal(jax.random.PRNGKey(1), (B, Hkv, T, D))
    q = jax.random.normal(jax.random.PRNGKey(2), (B, H, D))
    pos = jnp.asarray(pos, jnp.int32)
    out = flash_decode_attention(q, kc, vc, pos)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(reference(q, kc, vc, pos)),
                               atol=1e-5, rtol=1e-5)


def test_decode_mha_no_grouping():
    B, T, H, D = 1, 64, 4, 32
    kc = jax.random.normal(jax.random.PRNGKey(3), (B, H, T, D))
    vc = jax.random.normal(jax.random.PRNGKey(4), (B, H, T, D))
    q = jax.random.normal(jax.random.PRNGKey(5), (B, H, D))
    pos = jnp.asarray([40], jnp.int32)
    out = flash_decode_attention(q, kc, vc, pos)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(reference(q, kc, vc, pos)),
                               atol=1e-5, rtol=1e-5)


def test_decode_rejects_indivisible_heads():
    kc = jnp.zeros((1, 3, 16, 8))
    with pytest.raises(ValueError, match="divisible"):
        flash_decode_attention(jnp.zeros((1, 8, 8)), kc, kc,
                               jnp.zeros((1,), jnp.int32))


def test_generation_uses_kernel_and_matches_einsum_path(monkeypatch):
    """use_flash=True routes decode through the Pallas kernel; tokens
    must match the einsum path exactly (greedy, fp32).  A spy pins the
    routing so the comparison can't pass vacuously."""
    from nbdistributed_tpu.models import generate, init_params, tiny_config
    from nbdistributed_tpu.ops import decode as decode_mod

    calls = []
    real = decode_mod.flash_decode_attention

    def spy(*a, **k):
        calls.append(1)
        return real(*a, **k)

    monkeypatch.setattr(decode_mod, "flash_decode_attention", spy)

    cfg_ein = tiny_config(dtype=jnp.float32, use_flash=False)
    cfg_flash = tiny_config(dtype=jnp.float32, use_flash=True)
    params = init_params(jax.random.PRNGKey(0), cfg_ein)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 5), 0,
                                cfg_ein.vocab_size)
    a = generate(params, prompt, cfg_ein, max_new_tokens=8)
    assert not calls, "einsum config must not touch the kernel"
    b = generate(params, prompt, cfg_flash, max_new_tokens=8)
    assert calls, "use_flash config must route decode through the kernel"
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_decode_kernel_on_tp_mesh(monkeypatch):
    """The Pallas decode kernel runs under GSPMD on a 4-way tp mesh
    (shard_map over batch/dp and heads/tp): tokens must match the
    einsum mesh path exactly, and the spy pins the kernel routing."""
    from nbdistributed_tpu.models import generate, init_params, tiny_config
    from nbdistributed_tpu.models.transformer import param_shardings
    from nbdistributed_tpu.ops import decode as decode_mod
    from nbdistributed_tpu.parallel import mesh as mesh_mod
    from nbdistributed_tpu.parallel import tensor_parallel

    calls = []
    real = decode_mod.flash_decode_attention

    def spy(*a, **k):
        calls.append(1)
        return real(*a, **k)

    monkeypatch.setattr(decode_mod, "flash_decode_attention", spy)

    mesh = mesh_mod.make_mesh({"tp": 4}, devices=jax.devices()[:4])
    base = tiny_config(dtype=jnp.float32, use_flash=False)
    mk = lambda flash: type(base)(**{**base.__dict__,
                                     "n_heads": 8, "n_kv_heads": 4,
                                     "use_flash": flash})
    cfg_ein, cfg_flash = mk(False), mk(True)
    params = tensor_parallel.apply_shardings(
        init_params(jax.random.PRNGKey(0), cfg_ein), mesh,
        param_shardings(cfg_ein))
    prompt = jnp.array([[5, 9, 2], [7, 1, 3]], jnp.int32)

    te = generate(params, prompt, cfg_ein, max_new_tokens=10, mesh=mesh)
    assert not calls, "einsum path must not touch the kernel"
    tf = generate(params, prompt, cfg_flash, max_new_tokens=10,
                  mesh=mesh)
    assert calls, "flash path must route through the Pallas kernel"
    np.testing.assert_array_equal(np.asarray(te), np.asarray(tf))


@pytest.mark.parametrize("T,pos,window", [(200, [199, 130], 64),
                                          (129, [128, 60], 32),
                                          (64, [63, 10], 16)])
def test_decode_sliding_window(T, pos, window):
    """Windowed decode: only the last `window` cache slots attend;
    out-of-band blocks are skipped in the kernel, not just masked."""
    B, H, Hkv, D = 2, 8, 4, 16
    kc = jax.random.normal(jax.random.PRNGKey(0), (B, Hkv, T, D))
    vc = jax.random.normal(jax.random.PRNGKey(1), (B, Hkv, T, D))
    q = jax.random.normal(jax.random.PRNGKey(2), (B, H, D))
    pos = jnp.asarray(pos, jnp.int32)
    out = flash_decode_attention(q, kc, vc, pos, window=window)

    # Oracle: windowed softmax over the cache.
    group = H // Hkv
    qg = q.reshape(B, Hkv, group, D).astype(jnp.float32) / np.sqrt(D)
    s = jnp.einsum("bkgd,bktd->bkgt", qg, kc.astype(jnp.float32))
    t = jnp.arange(T)
    keep = ((t[None, :] <= pos[:, None])
            & (t[None, :] > pos[:, None] - window))
    s = jnp.where(keep[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, -1)
    ref = jnp.einsum("bkgt,bktd->bkgd", p,
                     vc.astype(jnp.float32)).reshape(B, H, D)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_windowed_generation_flash_matches_einsum(monkeypatch):
    """sliding_window generation must route through the kernel and
    produce the same greedy tokens as the einsum path."""
    from nbdistributed_tpu.models import generate, init_params, tiny_config
    from nbdistributed_tpu.ops import decode as decode_mod

    calls = []
    real = decode_mod.flash_decode_attention

    def spy(*a, **k):
        calls.append(k.get("window"))
        return real(*a, **k)

    monkeypatch.setattr(decode_mod, "flash_decode_attention", spy)
    base = tiny_config(dtype=jnp.float32, use_flash=False)
    mk = lambda flash: type(base)(**{**base.__dict__,
                                     "sliding_window": 24,
                                     "use_flash": flash})
    params = init_params(jax.random.PRNGKey(0), mk(False))
    prompt = jnp.array([[5, 9, 2], [7, 1, 3]], jnp.int32)
    te = generate(params, prompt, mk(False), max_new_tokens=40)
    assert not calls
    tf = generate(params, prompt, mk(True), max_new_tokens=40)
    assert calls and all(w == 24 for w in calls)
    np.testing.assert_array_equal(np.asarray(te), np.asarray(tf))


def test_decode_kernel_int8_cache_matches_dequantized_oracle():
    """The in-kernel scale commute must equal attention over the
    dequantized cache (same math, different association order)."""
    import jax
    import jax.numpy as jnp
    from nbdistributed_tpu.models.generate import (_cached_attention,
                                                   _dequantize_kv,
                                                   _quantize_kv)
    from nbdistributed_tpu.ops.decode import flash_decode_attention

    B, T, H, Hkv, D = 2, 129, 8, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, Hkv, T, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, Hkv, T, D), jnp.float32)
    pos = jnp.asarray([T - 1, 77], jnp.int32)

    k8, k_s = _quantize_kv(k)
    v8, v_s = _quantize_kv(v)
    got = flash_decode_attention(q, k8, v8, pos, k_s=k_s, v_s=v_s)

    # Oracle: dequantize, then exact masked attention.
    kd = _dequantize_kv(k8, k_s)
    vd = _dequantize_kv(v8, v_s)
    scale = 1.0 / np.sqrt(D)
    ref = _cached_attention(q[:, None], kd, vd, pos[:, None],
                            scale).reshape(B, H, D)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_decode_kernel_int8_requires_both_scales():
    import jax.numpy as jnp
    import pytest
    from nbdistributed_tpu.ops.decode import flash_decode_attention
    q = jnp.zeros((1, 4, 8))
    kc = jnp.zeros((1, 2, 16, 8), jnp.int8)
    s = jnp.zeros((1, 2, 16, 1))
    with pytest.raises(ValueError, match="both k_s and v_s"):
        flash_decode_attention(q, kc, kc, jnp.zeros((1,), jnp.int32),
                               k_s=s)


@pytest.mark.parametrize("block_k", [16, 32, 64])
def test_explicit_block_k_changes_nothing(block_k):
    """An explicit block_k wins over the default of 128 keys a block,
    and the block size is a schedule, not a result."""
    B, T, H, Hkv, D = 1, 64, 4, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, D))
    kc = jax.random.normal(ks[1], (B, Hkv, T, D))
    vc = jax.random.normal(ks[2], (B, Hkv, T, D))
    pos = jnp.full((B,), T - 1, jnp.int32)
    default = flash_decode_attention(q, kc, vc, pos)
    explicit = flash_decode_attention(q, kc, vc, pos, block_k=block_k)
    np.testing.assert_allclose(np.asarray(explicit), np.asarray(default),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------
# sequence-parallel decode: the cache's token axis sharded over sp,
# shards combined by log-sum-exp (the flash inter-block combine run
# across chips)

def test_decode_lse_matches_reference(chip_tol):
    """return_lse must equal log-sum-exp of the masked scores, and an
    all-masked query must report NEG_INF with a zero output row."""
    from nbdistributed_tpu.ops.decode import flash_decode_attention

    B, H, Hkv, T, D = 2, 4, 2, 96, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, D))
    kc = jax.random.normal(ks[1], (B, Hkv, T, D))
    vc = jax.random.normal(ks[2], (B, Hkv, T, D))
    pos = jnp.asarray([40, 95], jnp.int32)
    o, lse = flash_decode_attention(q, kc, vc, pos, block_k=32,
                                    return_lse=True)
    np.testing.assert_allclose(
        np.asarray(o),
        np.asarray(flash_decode_attention(q, kc, vc, pos, block_k=32)),
        rtol=1e-6)
    scale = 1.0 / np.sqrt(D)
    for b in range(B):
        for h in range(H):
            kv = h // (H // Hkv)
            s = (np.asarray(q[b, h]) * scale) @ np.asarray(kc[b, kv]).T
            s = s[: int(pos[b]) + 1]
            ref = float(np.log(np.exp(s - s.max()).sum()) + s.max())
            np.testing.assert_allclose(float(lse[b, h]), ref,
                                       rtol=chip_tol(1e-5, 1e-4))
    o3, lse3 = flash_decode_attention(
        q, kc, vc, jnp.asarray([-1, -1], jnp.int32), block_k=32,
        return_lse=True)
    assert float(lse3.max()) < -1e29
    assert float(np.abs(np.asarray(o3)).max()) == 0.0


@pytest.mark.parametrize("window", [None, 48])
def test_sp_sharded_decode_matches_single_device(window):
    """Cache token axis sharded over sp=4: the lse-combined sharded
    kernel must equal the single-device kernel (window composes —
    its bound is offset-invariant in local coordinates)."""
    from nbdistributed_tpu.models.generate import _flash_decode_on_mesh
    from nbdistributed_tpu.ops.decode import flash_decode_attention
    from nbdistributed_tpu.parallel import mesh as mesh_mod

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    B, H, Hkv, T, D = 2, 4, 2, 128, 16
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, H, D))
    kc = jax.random.normal(ks[1], (B, Hkv, T, D))
    vc = jax.random.normal(ks[2], (B, Hkv, T, D))
    pos = jnp.asarray([90, 127], jnp.int32)
    ref = flash_decode_attention(q, kc, vc, pos, window=window)
    mesh = mesh_mod.make_mesh({"sp": 4}, devices=jax.devices()[:4])
    got = jax.jit(lambda: _flash_decode_on_mesh(
        q, kc, vc, pos, mesh, 1.0 / np.sqrt(D), window))()
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_sp_sharded_decode_int8_cache():
    """int8 cache scales shard along the token axis with the cache."""
    from nbdistributed_tpu.models.generate import (_flash_decode_on_mesh,
                                                   _quantize_kv)
    from nbdistributed_tpu.ops.decode import flash_decode_attention
    from nbdistributed_tpu.parallel import mesh as mesh_mod

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    B, H, Hkv, T, D = 2, 4, 2, 128, 16
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (B, H, D))
    k8, k_s = _quantize_kv(jax.random.normal(ks[1], (B, Hkv, T, D)))
    v8, v_s = _quantize_kv(jax.random.normal(ks[2], (B, Hkv, T, D)))
    pos = jnp.asarray([70, 127], jnp.int32)
    ref = flash_decode_attention(q, k8, v8, pos, k_s=k_s, v_s=v_s)
    mesh = mesh_mod.make_mesh({"sp": 4}, devices=jax.devices()[:4])
    got = jax.jit(lambda: _flash_decode_on_mesh(
        q, k8, v8, pos, mesh, 1.0 / np.sqrt(D), None, k_s, v_s))()
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_generate_on_sp_mesh_matches_single_device():
    """End-to-end: generate() with the KV cache sharded dp×tp×sp must
    reproduce the single-device greedy decode (cache writes cross the
    sp shard boundary via GSPMD; reads combine by lse)."""
    from nbdistributed_tpu.models import generate, init_params, tiny_config
    from nbdistributed_tpu.models.transformer import param_shardings
    from nbdistributed_tpu.parallel import mesh as mesh_mod
    from nbdistributed_tpu.parallel import tensor_parallel

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    mesh = mesh_mod.make_mesh({"dp": 2, "tp": 2, "sp": 2},
                              devices=jax.devices()[:8])
    cfg = tiny_config(dtype=jnp.float32, use_flash=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    ps = tensor_parallel.apply_shardings(params, mesh,
                                         param_shardings(cfg))
    prompt = jax.random.randint(jax.random.PRNGKey(3), (2, 6), 0,
                                cfg.vocab_size)
    import dataclasses
    ref = generate(params, prompt,
                   dataclasses.replace(cfg, use_flash=False), 10)
    got = generate(ps, prompt, cfg, 10, mesh=mesh, max_len=32)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_sp_sharded_decode_partial_final_block():
    """Regression (round-4 review): an sp shard's LOCAL position can
    exceed its cache slice length, which used to leave the padded
    tail of a partial final block unmasked (valid > seq_k → NaN from
    Pallas block padding).  t_loc=192 with block_k=128 forces a
    partial final block; pos=380 overshoots shard 0 by 188."""
    from nbdistributed_tpu.models.generate import _flash_decode_on_mesh
    from nbdistributed_tpu.ops.decode import flash_decode_attention
    from nbdistributed_tpu.parallel import mesh as mesh_mod

    if len(jax.devices()) < 2:
        pytest.skip("needs 2 virtual devices")
    B, H, Hkv, T, D = 1, 2, 1, 384, 16
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (B, H, D))
    kc = jax.random.normal(ks[1], (B, Hkv, T, D))
    vc = jax.random.normal(ks[2], (B, Hkv, T, D))
    pos = jnp.asarray([380], jnp.int32)
    ref = flash_decode_attention(q, kc, vc, pos, block_k=128)
    mesh = mesh_mod.make_mesh({"sp": 2}, devices=jax.devices()[:2])
    got = jax.jit(lambda: _flash_decode_on_mesh(
        q, kc, vc, pos, mesh, 1.0 / np.sqrt(D)))()
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)
    # Windowed variant at the same geometry (window bound must stay
    # on the unclamped local position).
    ref_w = flash_decode_attention(q, kc, vc, pos, block_k=128,
                                   window=100)
    got_w = jax.jit(lambda: _flash_decode_on_mesh(
        q, kc, vc, pos, mesh, 1.0 / np.sqrt(D), 100))()
    np.testing.assert_allclose(np.asarray(got_w), np.asarray(ref_w),
                               atol=1e-5, rtol=1e-5)


def test_speculative_on_sp_mesh_matches_greedy():
    """Batched speculative decoding with the KV caches sharded over
    dp×sp: greedy spec must reproduce the target's greedy decode (the
    S=1 draft steps ride the sp-sharded kernel; the verify forward
    runs the einsum cache path under GSPMD)."""
    from nbdistributed_tpu.models import (generate, init_params,
                                          speculative_generate,
                                          tiny_config)
    from nbdistributed_tpu.models.transformer import param_shardings
    from nbdistributed_tpu.parallel import mesh as mesh_mod
    from nbdistributed_tpu.parallel import tensor_parallel

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    mesh = mesh_mod.make_mesh({"dp": 2, "tp": 2, "sp": 2},
                              devices=jax.devices()[:8])
    cfg = tiny_config(dtype=jnp.float32, use_flash=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    ps = tensor_parallel.apply_shardings(params, mesh,
                                         param_shardings(cfg))
    prompt = jax.random.randint(jax.random.PRNGKey(7), (2, 6), 0,
                                cfg.vocab_size)
    import dataclasses
    ref = generate(params, prompt,
                   dataclasses.replace(cfg, use_flash=False), 8)
    got, acc = speculative_generate(ps, ps, prompt, cfg, cfg, 8,
                                    gamma=3, mesh=mesh)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    assert float(acc) == 3.0


def test_decode_server_on_sp_mesh():
    """DecodeServer over a dp×tp×sp mesh: outputs match solo decode
    (the paged pool's KV heads are tp-sharded; each layer's gathered
    view is attended sp-sharded, reads combined by lse)."""
    from nbdistributed_tpu.models import generate, init_params, tiny_config
    from nbdistributed_tpu.models.serving import DecodeServer
    from nbdistributed_tpu.models.transformer import param_shardings
    from nbdistributed_tpu.parallel import mesh as mesh_mod
    from nbdistributed_tpu.parallel import tensor_parallel

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    mesh = mesh_mod.make_mesh({"dp": 2, "tp": 2, "sp": 2},
                              devices=jax.devices()[:8])
    cfg = tiny_config(dtype=jnp.float32, use_flash=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    ps = tensor_parallel.apply_shardings(params, mesh,
                                         param_shardings(cfg))
    srv = DecodeServer(ps, cfg, max_batch=2, max_len=32, pad_to=4,
                       mesh=mesh)
    import dataclasses
    cfg_ref = dataclasses.replace(cfg, use_flash=False)
    reqs = [([5, 9, 2], 6), ([7, 1, 3, 11], 5)]
    rids = [srv.submit(*r) for r in reqs]
    srv.run_until_done(max_steps=40)
    for rid, (prompt, n) in zip(rids, reqs):
        solo = generate(params, jnp.asarray([prompt], jnp.int32),
                        cfg_ref, n)
        assert srv.outputs[rid] == [int(t) for t in
                                    solo[0, len(prompt):]]


def test_sp_sharded_decode_window_entirely_past_shard():
    """Round-4 review band: with a sliding window, an sp shard whose
    entire slice lies BELOW the window (lo >= valid_k) must contribute
    nothing — the block guard must skip it outright rather than run an
    empty-mask block whose garbage only underflow discards.  T=384,
    sp=2, window=100, pos=300: shard 0's keys [0,192) are all below
    lo=201."""
    from nbdistributed_tpu.models.generate import _flash_decode_on_mesh
    from nbdistributed_tpu.ops.decode import flash_decode_attention
    from nbdistributed_tpu.parallel import mesh as mesh_mod

    if len(jax.devices()) < 2:
        pytest.skip("needs 2 virtual devices")
    B, H, Hkv, T, D = 1, 2, 1, 384, 16
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    q = jax.random.normal(ks[0], (B, H, D))
    kc = jax.random.normal(ks[1], (B, Hkv, T, D))
    vc = jax.random.normal(ks[2], (B, Hkv, T, D))
    mesh = mesh_mod.make_mesh({"sp": 2}, devices=jax.devices()[:2])
    for p in (300, 291, 355):          # across the hazardous band
        pos = jnp.asarray([p], jnp.int32)
        ref = flash_decode_attention(q, kc, vc, pos, block_k=128,
                                     window=100)
        got = jax.jit(lambda pos=pos: _flash_decode_on_mesh(
            q, kc, vc, pos, mesh, 1.0 / np.sqrt(D), 100))()
        assert np.isfinite(np.asarray(got)).all()
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)
