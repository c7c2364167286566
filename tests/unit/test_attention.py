"""Flash attention vs reference oracle (interpret mode on CPU exercises
the identical kernel code path that compiles on TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nbdistributed_tpu.ops import attention_reference, flash_attention

# Heavy interpret-mode kernel/model tests: excluded from the
# fast product-path tier (`pytest -m "not slow"`).
pytestmark = [pytest.mark.unit, pytest.mark.slow]


def rand(shape, key, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(key), shape, dtype)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_reference(causal):
    B, S, H, D = 2, 128, 4, 64
    q, k, v = (rand((B, S, H, D), i) for i in range(3))
    out = flash_attention(q, k, v, causal)
    ref = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("seq", [65, 100, 192, 255])
def test_flash_non_divisible_seq_lengths(causal, seq):
    """Sequence lengths that don't divide the block size must be exact —
    dynamic-slice clamping once silently double-counted keys here."""
    B, H, D = 1, 2, 32
    q, k, v = (rand((B, seq, H, D), i + 20) for i in range(3))
    out = flash_attention(q, k, v, causal, None, 64, 64)
    ref = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_multiblock_seq():
    """Sequence longer than one block exercises the online-softmax
    recurrence across k-blocks."""
    B, S, H, D = 1, 256, 2, 32
    q, k, v = (rand((B, S, H, D), i + 10) for i in range(3))
    out = flash_attention(q, k, v, True, None, 64, 64)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_gqa():
    B, S, H, Hkv, D = 1, 64, 8, 2, 32
    q = rand((B, S, H, D), 0)
    k = rand((B, S, Hkv, D), 1)
    v = rand((B, S, Hkv, D), 2)
    out = flash_attention(q, k, v)
    ref = attention_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_bfloat16():
    B, S, H, D = 1, 64, 2, 64
    q, k, v = (rand((B, S, H, D), i, jnp.bfloat16) for i in range(3))
    out = flash_attention(q, k, v)
    ref = attention_reference(q, k, v)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=3e-2, rtol=3e-2)


def test_flash_gradients_match_reference():
    B, S, H, D = 1, 64, 2, 32
    q, k, v = (rand((B, S, H, D), i + 5) for i in range(3))

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)


def test_flash_causality_enforced():
    """Output at position t must not depend on inputs after t."""
    B, S, H, D = 1, 64, 1, 16
    q, k, v = (rand((B, S, H, D), i) for i in range(3))
    out1 = flash_attention(q, k, v, True)
    k2 = k.at[:, -1].set(999.0)
    v2 = v.at[:, -1].set(999.0)
    out2 = flash_attention(q, k2, v2, True)
    np.testing.assert_allclose(np.asarray(out1[:, :-1]),
                               np.asarray(out2[:, :-1]), atol=1e-5)


def test_flash_jit_compatible():
    B, S, H, D = 1, 64, 2, 32
    q, k, v = (rand((B, S, H, D), i) for i in range(3))
    jitted = jax.jit(lambda q, k, v: flash_attention(q, k, v))
    np.testing.assert_allclose(
        np.asarray(jitted(q, k, v)),
        np.asarray(attention_reference(q, k, v)), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("H,Hkv", [(2, 2), (8, 2)])
def test_flash_bwd_blockwise_gqa(causal, H, Hkv):
    """The Pallas backward (dq/dk/dv kernels off the saved logsumexp)
    must match reference grads for causal x GQA combinations."""
    B, S, D = 2, 128, 32
    q = rand((B, S, H, D), 30)
    k = rand((B, S, Hkv, D), 31)
    v = rand((B, S, Hkv, D), 32)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal, None, 64, 64)
                       * jnp.cos(jnp.arange(D)))

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=causal)
                       * jnp.cos(jnp.arange(D)))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gf, gr):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-4, rtol=2e-4,
            err_msg=f"d{name} mismatch (causal={causal}, "
                    f"H={H}, Hkv={Hkv})")


@pytest.mark.parametrize("Sq,Sk", [(65, 100), (100, 65), (128, 255)])
def test_flash_bwd_ragged_and_cross_lengths(Sq, Sk):
    """Non-block-multiple and unequal Sq/Sk: padded rows/keys must
    contribute exactly zero gradient."""
    B, H, D = 1, 2, 32
    q = rand((B, Sq, H, D), 40)
    k = rand((B, Sk, H, D), 41)
    v = rand((B, Sk, H, D), 42)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, False, None, 64, 64) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=False) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gf, gr):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-4, rtol=2e-4,
            err_msg=f"d{name} mismatch (Sq={Sq}, Sk={Sk})")


class TestSlidingWindow:
    """Mistral-style sliding-window attention: both passes prune
    out-of-band blocks and must stay exact vs the windowed oracle."""

    def _oracle(self, q, k, v, window):
        """Windowed softmax attention from first principles."""
        B, S, H, D = q.shape
        Hkv = k.shape[2]
        kk = jnp.repeat(k, H // Hkv, axis=2)
        vv = jnp.repeat(v, H // Hkv, axis=2)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / np.sqrt(D)
        qi = jnp.arange(S)[:, None]
        ki = jnp.arange(S)[None, :]
        keep = (ki <= qi) & (ki > qi - window)
        logits = jnp.where(keep, logits, -1e30)
        p = jax.nn.softmax(logits, -1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, vv)

    @pytest.mark.parametrize("window", [16, 64, 100])
    def test_reference_matches_oracle(self, window):
        B, S, H, D = 1, 128, 2, 32
        q, k, v = (rand((B, S, H, D), i + 70) for i in range(3))
        got = attention_reference(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(self._oracle(q, k, v, window)),
            atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("window,S", [(16, 128), (64, 200), (128, 256)])
    def test_flash_matches_reference(self, window, S):
        """Windows crossing block boundaries, non-multiple lengths."""
        B, H, Hkv, D = 1, 4, 2, 32
        q = rand((B, S, H, D), 80)
        k = rand((B, S, Hkv, D), 81)
        v = rand((B, S, Hkv, D), 82)
        got = flash_attention(q, k, v, True, None, 64, 64, window)
        ref = attention_reference(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_flash_window_gradients(self):
        B, S, H, Hkv, D, W = 1, 128, 4, 2, 32, 48
        q = rand((B, S, H, D), 90)
        k = rand((B, S, Hkv, D), 91)
        v = rand((B, S, Hkv, D), 92)

        def loss_f(q, k, v):
            return jnp.sum(
                flash_attention(q, k, v, True, None, 64, 64, W) ** 2)

        def loss_r(q, k, v):
            return jnp.sum(attention_reference(
                q, k, v, causal=True, window=W) ** 2)

        gf = jax.grad(loss_f, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip("qkv", gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4, rtol=2e-4,
                                       err_msg=f"d{name} mismatch")

    def test_window_requires_causal(self):
        q = rand((1, 32, 2, 16), 0)
        with pytest.raises(ValueError, match="causal"):
            flash_attention(q, q, q, False, None, 32, 32, 16)
        with pytest.raises(ValueError, match="causal"):
            attention_reference(q, q, q, causal=False, window=16)


# (Sq, Sk, D, group): the training cell's call, an 89-row sequence, a
# ring hop's chunk against a longer K/V, and plain multi-head.
DERIVED_SHAPES = [(4096, 4096, 128, 4), (89, 89, 64, 2),
                  (1024, 2048, 128, 4), (2048, 2048, 128, 1)]


@pytest.mark.parametrize("kernel", ["fwd", "dq", "dkv"])
@pytest.mark.parametrize("Sq,Sk,D,group", DERIVED_SHAPES)
def test_flash_derived_tiles(Sq, Sk, D, group, kernel):
    """block_q/block_k=None: each kernel's tile comes from the call's
    shapes alone — whole 128s, never padding a sequence further than a
    128-row tile pads it (so the three kernels pad alike and the saved
    logsumexp fits the backward), within the VMEM budget by the
    function's own arithmetic, no larger than the sweep's caps."""
    from nbdistributed_tpu.ops import attention as att

    bq, bk = att._block_sizes(None, None, Sq, Sk, D, group,
                              interpret=False, kernel=kernel)
    assert bq % 128 == 0 and bk % 128 == 0
    pad = att._round_up
    assert pad(Sq, bq) == pad(Sq, 128) and pad(Sk, bk) == pad(Sk, 128)
    need = att._vmem_need(kernel, bq, bk, pad(Sq, bq), pad(Sk, bk), D,
                          group, 2)
    assert need <= att._VMEM_BUDGET < att._VMEM_MOST
    cap_q, cap_k = att._TILE_CAP
    assert bq <= cap_q and bk <= cap_k
    # an 89-row sequence is one 128-row tile; the cell's takes more
    # than the old 128 x 128 fallback
    if Sq == 89:
        assert (bq, bk) == (128, 128)
    if Sq == 4096:
        assert bq * bk > 128 * 128
    # explicit sizes still win, rounded to what Mosaic accepts
    assert att._block_sizes(200, 64, Sq, Sk, D, group, interpret=False,
                            kernel=kernel) == (256, 128)


OWN_TILES = {"fwd": (32, 64), "dq": (32, 32), "dkv": (64, 32)}


@pytest.mark.parametrize("window", [None, 48])
@pytest.mark.parametrize("packed", [False, True])
@pytest.mark.parametrize("S", [128, 100])
def test_flash_kernels_at_their_own_tiles(S, packed, window):
    """The three kernels at three different rectangular tiles, as far
    as they may differ (each pads the sequence to the same length, so
    the forward's logsumexp fits both backward kernels): at a length
    all of them divide and at one they pad, forward and all three
    gradients agree with the reference."""
    from nbdistributed_tpu.ops import attention as att

    B, H, Hkv, D = 1, 4, 2, 16
    q = rand((B, S, H, D), 31)
    k, v = (rand((B, S, Hkv, D), 32 + i) for i in range(2))
    w = rand((B, S, H, D), 34)
    seg = None
    if packed:
        seg = jnp.asarray([[0] * 40 + [1] * 37 + [2] * (S - 77)])
    common = dict(causal=True, scale=D ** -0.5, interpret=True,
                  window=window, segment_ids=seg, kv_segment_ids=seg)
    (bq, bk), (dq_bq, dq_bk) = OWN_TILES["fwd"], OWN_TILES["dq"]
    out, lse = att._flash_forward(q, k, v, block_q=bq, block_k=bk,
                                  **common)
    grads = att._flash_backward(q, k, v, out, lse, w, block_q=dq_bq,
                                block_k=dq_bk,
                                dkv_blocks=OWN_TILES["dkv"], **common)
    want, vjp = jax.vjp(lambda q, k, v: attention_reference(
        q, k, v, causal=True, window=window, segment_ids=seg), q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    for got, ref in zip(grads, vjp(w)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)


def test_flash_backward_refuses_tiles_that_pad_apart():
    """A dK/dV tile that pads the queries to another length than the
    saved logsumexp has is refused, not run on a misaligned plane."""
    from nbdistributed_tpu.ops import attention as att

    q = rand((1, 96, 2, 16), 41)
    common = dict(causal=True, scale=0.25, interpret=True)
    out, lse = att._flash_forward(q, q, q, block_q=32, block_k=32, **common)
    with pytest.raises(ValueError, match="do not pad"):
        att._flash_backward(q, q, q, out, lse, q, block_q=32, block_k=32,
                            dkv_blocks=(64, 32), **common)


class TestSegmentIds:
    """Packed-document masking: queries attend only same-segment keys,
    in the flash kernel (both passes) and the reference."""

    def _inputs(self, B=2, S=96, H=4, Hkv=2, D=16, n_docs=3, seed=0):
        ks = jax.random.split(jax.random.PRNGKey(seed), 4)
        q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
        k = jax.random.normal(ks[1], (B, S, Hkv, D), jnp.float32)
        v = jax.random.normal(ks[2], (B, S, Hkv, D), jnp.float32)
        # Random doc boundaries -> non-decreasing segment ids.
        bounds = jax.random.randint(ks[3], (B, S), 0, n_docs)
        seg = jnp.sort(bounds, axis=1)
        return q, k, v, seg

    def test_reference_equals_per_document_attention(self):
        """The packed reference must equal attending each document
        independently and concatenating — the ground-truth semantics
        of segment masking."""
        q, k, v, _ = self._inputs(B=1, S=48)
        seg = jnp.asarray([[0] * 20 + [1] * 17 + [2] * 11])
        packed = attention_reference(q, k, v, causal=True,
                                     segment_ids=seg)
        parts = []
        for lo, hi in ((0, 20), (20, 37), (37, 48)):
            parts.append(attention_reference(
                q[:, lo:hi], k[:, lo:hi], v[:, lo:hi], causal=True))
        np.testing.assert_allclose(np.asarray(packed),
                                   np.asarray(jnp.concatenate(parts, 1)),
                                   atol=1e-5, rtol=1e-5)

    @pytest.mark.parametrize("causal", [True, False])
    def test_flash_matches_reference(self, causal):
        q, k, v, seg = self._inputs()
        out = flash_attention(q, k, v, causal, None, 32, 32,
                              segment_ids=seg)
        ref = attention_reference(q, k, v, causal=causal,
                                  segment_ids=seg)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    def test_flash_non_multiple_seq(self):
        q, k, v, seg = self._inputs(S=77)
        out = flash_attention(q, k, v, True, None, 32, 32,
                              segment_ids=seg)
        ref = attention_reference(q, k, v, causal=True,
                                  segment_ids=seg)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    def test_flash_gradients_match_reference(self, chip_tol):
        """dq/dk/dv through both Pallas backward kernels must match
        autodiff through the masked reference."""
        q, k, v, seg = self._inputs(S=64)

        def loss_f(q, k, v):
            return jnp.sum(flash_attention(q, k, v, True, None, 32, 32,
                                           segment_ids=seg) ** 2)

        def loss_r(q, k, v):
            return jnp.sum(attention_reference(
                q, k, v, causal=True, segment_ids=seg) ** 2)

        gf = jax.grad(loss_f, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
        # On the chip a handful of dk entries land 2e-4 off.
        for a, b, name in zip(gf, gr, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=chip_tol(1e-4, 5e-4),
                                       rtol=1e-4, err_msg=f"d{name}")

    def test_no_cross_document_leak(self):
        """Perturbing document 0's keys/values must not change
        document 1's outputs at all — the leak pack_tokens windows had
        without segment masking."""
        q, k, v, _ = self._inputs(B=1, S=64)
        seg = jnp.asarray([[0] * 32 + [1] * 32])
        base = flash_attention(q, k, v, True, None, 32, 32,
                               segment_ids=seg)
        k2 = k.at[:, :32].add(7.0)
        v2 = v.at[:, :32].add(-3.0)
        pert = flash_attention(q, k2, v2, True, None, 32, 32,
                               segment_ids=seg)
        np.testing.assert_array_equal(np.asarray(base[:, 32:]),
                                      np.asarray(pert[:, 32:]))
        assert np.abs(np.asarray(base[:, :32])
                      - np.asarray(pert[:, :32])).max() > 1e-3

    def test_rejects_cross_length(self):
        q, k, v, seg = self._inputs(S=64)
        with pytest.raises(ValueError, match="Sq == Sk"):
            flash_attention(q[:, :32], k, v, True, None, 32, 32,
                            segment_ids=seg[:, :32])

    def test_negative_segment_ids_are_ordinary_values(self):
        """User ids may be any integers (equality defines membership):
        ids colliding with the pad sentinels must behave identically —
        padded keys are excluded by the validity mask, not by the
        sentinel values (S=77 forces real key padding)."""
        q, k, v, _ = self._inputs(B=1, S=77)
        seg_pos = jnp.asarray([[0] * 40 + [1] * 37])
        seg_neg = jnp.asarray([[-2] * 40 + [-1] * 37])  # same structure
        a = flash_attention(q, k, v, True, None, 32, 32,
                            segment_ids=seg_pos)
        b = flash_attention(q, k, v, True, None, 32, 32,
                            segment_ids=seg_neg)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_segments_compose_with_sliding_window(self):
        """window AND segment masks AND together: both kernel passes
        must match the reference with both constraints active."""
        q, k, v, seg = self._inputs(S=96)
        W = 24
        out = flash_attention(q, k, v, True, None, 32, 32, W,
                              segment_ids=seg)
        ref = attention_reference(q, k, v, causal=True, window=W,
                                  segment_ids=seg)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)
        g = jax.grad(lambda q_: jnp.sum(flash_attention(
            q_, k, v, True, None, 32, 32, W,
            segment_ids=seg) ** 2))(q)
        gr = jax.grad(lambda q_: jnp.sum(attention_reference(
            q_, k, v, causal=True, window=W,
            segment_ids=seg) ** 2))(q)
        np.testing.assert_allclose(np.asarray(g), np.asarray(gr),
                                   atol=1e-4, rtol=1e-4)

