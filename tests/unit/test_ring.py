"""Ring attention vs full attention on the 8-device virtual mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nbdistributed_tpu.ops import attention_reference
from nbdistributed_tpu.parallel import mesh as mesh_mod
from nbdistributed_tpu.parallel.ring import ring_attention

# Heavy interpret-mode kernel/model tests: excluded from the
# fast product-path tier (`pytest -m "not slow"`).
pytestmark = [pytest.mark.unit, pytest.mark.slow]


def rand(shape, key):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32)


@pytest.fixture(scope="module")
def sp_mesh():
    return mesh_mod.make_mesh({"sp": 8})


@pytest.mark.parametrize("causal", [True, False])
def test_ring_matches_full_attention(sp_mesh, causal):
    B, S, H, D = 2, 64, 2, 16  # S shards into 8 chunks of 8
    q, k, v = (rand((B, S, H, D), i) for i in range(3))
    out = ring_attention(q, k, v, sp_mesh, causal=causal)
    ref = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_ring_output_stays_sequence_sharded(sp_mesh):
    B, S, H, D = 1, 64, 2, 16
    q, k, v = (rand((B, S, H, D), i + 3) for i in range(3))
    out = ring_attention(q, k, v, sp_mesh)
    assert len(out.sharding.device_set) == 8


def test_ring_long_sequence(sp_mesh):
    """Longer-than-VMEM-friendly sequence: the point of the exercise."""
    B, S, H, D = 1, 512, 2, 32
    q, k, v = (rand((B, S, H, D), i + 7) for i in range(3))
    out = ring_attention(q, k, v, sp_mesh, causal=True)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("use_flash", [False, True])
def test_ring_gqa_native(sp_mesh, causal, use_flash):
    """K/V circulate the ring at n_kv_heads (no pre-expansion) — exact
    vs the full-attention oracle, einsum and Pallas inner paths."""
    B, S, H, Hkv, D = 1, 64, 8, 2, 16
    q = rand((B, S, H, D), 20)
    k = rand((B, S, Hkv, D), 21)
    v = rand((B, S, Hkv, D), 22)
    out = ring_attention(q, k, v, sp_mesh, causal=causal,
                         use_flash=use_flash)
    ref = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_flash_matches(sp_mesh, causal):
    """MHA through the Pallas hop kernel (chunk-offset causal mask)."""
    B, S, H, D = 2, 64, 2, 16
    q, k, v = (rand((B, S, H, D), i + 30) for i in range(3))
    out = ring_attention(q, k, v, sp_mesh, causal=causal, use_flash=True)
    ref = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("use_flash", [False, True])
def test_ring_gradients_match_reference(sp_mesh, use_flash):
    """Both inner paths must differentiate exactly: einsum via plain
    autodiff, flash via the ring custom-VJP over the blockwise Pallas
    backward (dk/dv accumulators ride the ring home)."""
    B, S, H, Hkv, D = 1, 64, 4, 2, 16
    q = rand((B, S, H, D), 40)
    k = rand((B, S, Hkv, D), 41)
    v = rand((B, S, Hkv, D), 42)

    def loss_r(q, k, v):
        return jnp.sum(ring_attention(q, k, v, sp_mesh, causal=True,
                                      use_flash=use_flash) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=True) ** 2)

    gr_ring = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    gr_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gr_ring, gr_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4,
                                   err_msg=f"d{name} mismatch "
                                           f"(use_flash={use_flash})")


def test_zigzag_order_is_permutation():
    from nbdistributed_tpu.parallel.ring import zigzag_order
    order = zigzag_order(64, 8)
    assert sorted(order.tolist()) == list(range(64))
    # device 0's shard = first 8 entries = chunks 0 and 15
    assert order[:8].tolist() == [0, 1, 2, 3, 60, 61, 62, 63]


def test_zigzag_shard_roundtrip():
    from nbdistributed_tpu.parallel.ring import (zigzag_shard,
                                                 zigzag_unshard)
    x = jnp.arange(2 * 64 * 3).reshape(2, 64, 3)
    np.testing.assert_array_equal(
        np.asarray(zigzag_unshard(zigzag_shard(x, 8), 8)), np.asarray(x))


@pytest.mark.parametrize("H,Hkv", [(2, 2), (4, 2)])
def test_zigzag_matches_full_attention(sp_mesh, H, Hkv):
    """Zigzag-scheduled causal ring == full attention after undoing the
    zigzag ordering (the load-balanced schedule must stay exact)."""
    from nbdistributed_tpu.parallel.ring import (zigzag_shard,
                                                 zigzag_unshard)
    B, S, D, n = 1, 64, 16, 8
    q = rand((B, S, H, D), 50)
    k = rand((B, S, Hkv, D), 51)
    v = rand((B, S, Hkv, D), 52)
    out_zz = ring_attention(zigzag_shard(q, n), zigzag_shard(k, n),
                            zigzag_shard(v, n), sp_mesh, causal=True,
                            use_flash=True, schedule="zigzag")
    out = zigzag_unshard(out_zz, n)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_zigzag_gradients_match_reference(sp_mesh):
    from nbdistributed_tpu.parallel.ring import (zigzag_shard,
                                                 zigzag_unshard)
    B, S, H, Hkv, D, n = 1, 64, 4, 2, 16, 8
    q = rand((B, S, H, D), 60)
    k = rand((B, S, Hkv, D), 61)
    v = rand((B, S, Hkv, D), 62)

    def loss_zz(q, k, v):
        out = ring_attention(zigzag_shard(q, n), zigzag_shard(k, n),
                             zigzag_shard(v, n), sp_mesh, causal=True,
                             use_flash=True, schedule="zigzag")
        return jnp.sum(zigzag_unshard(out, n) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=True) ** 2)

    gz = jax.grad(loss_zz, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gz, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4,
                                   err_msg=f"d{name} mismatch")


def test_zigzag_rejects_bad_configs(sp_mesh):
    q = rand((1, 64, 2, 16), 0)
    with pytest.raises(ValueError, match="use_flash"):
        ring_attention(q, q, q, sp_mesh, causal=True, use_flash=False,
                       schedule="zigzag")
    with pytest.raises(ValueError, match="causal"):
        ring_attention(q, q, q, sp_mesh, causal=False, use_flash=True,
                       schedule="zigzag")
    q65 = rand((1, 40, 2, 16), 0)
    with pytest.raises(ValueError, match="divisible"):
        ring_attention(q65, q65, q65, sp_mesh, causal=True,
                       use_flash=True, schedule="zigzag")


def test_ring_sliding_window_exact_and_grads():
    """Windowed ring attention (einsum and Pallas paths) vs the
    windowed reference, forward and gradients."""
    from nbdistributed_tpu.ops import attention_reference
    from nbdistributed_tpu.parallel import mesh as mesh_mod
    from nbdistributed_tpu.parallel.ring import ring_attention

    mesh = mesh_mod.make_mesh({"sp": 4}, devices=jax.devices()[:4])
    B, S, H, Hkv, D, W = 1, 32, 4, 2, 16, 9
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, Hkv, D))
    v = jax.random.normal(ks[2], (B, S, Hkv, D))
    ref = attention_reference(q, k, v, causal=True, window=W)
    for use_flash in (False, True):
        got = ring_attention(q, k, v, mesh, axis="sp", causal=True,
                             use_flash=use_flash, window=W)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5,
                                   err_msg=f"flash={use_flash}")
    # Full-argnum grads for BOTH inner paths: dK/dV exercise the
    # windowed backward accumulation riding the pruned hop plan (flash
    # custom-VJP and autodiff-through-unrolled-einsum alike).
    g_ref = jax.grad(lambda q_, k_, v_: jnp.sum(attention_reference(
        q_, k_, v_, causal=True, window=W) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for use_flash in (False, True):
        g = jax.grad(lambda q_, k_, v_: jnp.sum(ring_attention(
            q_, k_, v_, mesh, axis="sp", causal=True,
            use_flash=use_flash, window=W) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        for a, b, nm in zip(g, g_ref, "qkv"):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4,
                err_msg=f"{nm} flash={use_flash}")


def test_ring_window_cross_length_exact():
    """Sq != Sk (queries sharded shorter than keys): the hop plan must
    size Q and K intervals independently — a plan computed from the
    K-chunk size alone would skip contributing hops for the later
    query chunks.  Parameters chosen so the correct cross-length plan
    both PRUNES (exercising the unrolled jump path and its backward)
    and DIFFERS from the k-size-only plan (the regression)."""
    from nbdistributed_tpu.parallel import mesh as mesh_mod
    from nbdistributed_tpu.parallel.ring import hop_plan

    mesh = mesh_mod.make_mesh({"sp": 4}, devices=jax.devices()[:4])
    B, Sq, Sk, H, Hkv, D, W = 1, 16, 32, 4, 2, 16, 3
    assert hop_plan(4, Sq // 4, W, sk_local=Sk // 4) == (0, 1, 2)
    assert hop_plan(4, Sk // 4, W) == (0, 1)  # the regression's plan
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (B, Sq, H, D))
    k = jax.random.normal(ks[1], (B, Sk, Hkv, D))
    v = jax.random.normal(ks[2], (B, Sk, Hkv, D))
    ref = attention_reference(q, k, v, causal=True, window=W)
    for use_flash in (False, True):
        got = ring_attention(q, k, v, mesh, axis="sp", causal=True,
                             use_flash=use_flash, window=W)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5,
                                   err_msg=f"flash={use_flash}")
    # Cross-length backward through the pruned plan (incl. the dk/dv
    # homing jump).
    g = jax.grad(lambda q_, k_, v_: jnp.sum(ring_attention(
        q_, k_, v_, mesh, axis="sp", causal=True, use_flash=True,
        window=W) ** 2), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(lambda q_, k_, v_: jnp.sum(attention_reference(
        q_, k_, v_, causal=True, window=W) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b, nm in zip(g, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4, err_msg=nm)


def test_ring_zigzag_sliding_window_exact():
    from nbdistributed_tpu.ops import attention_reference
    from nbdistributed_tpu.parallel import mesh as mesh_mod
    from nbdistributed_tpu.parallel.ring import (ring_attention,
                                                 zigzag_shard,
                                                 zigzag_unshard)

    n = 4
    mesh = mesh_mod.make_mesh({"sp": n}, devices=jax.devices()[:n])
    B, S, H, Hkv, D, W = 1, 8 * n, 4, 2, 16, 11
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, Hkv, D))
    v = jax.random.normal(ks[2], (B, S, Hkv, D))
    ref = attention_reference(q, k, v, causal=True, window=W)
    out = ring_attention(zigzag_shard(q, n), zigzag_shard(k, n),
                         zigzag_shard(v, n), mesh, axis="sp",
                         causal=True, use_flash=True,
                         schedule="zigzag", window=W)
    np.testing.assert_allclose(np.asarray(zigzag_unshard(out, n)),
                               np.asarray(ref), atol=2e-5, rtol=2e-5)
    # Windowed zigzag gradients for ALL inputs (sum-of-squares is
    # permutation-invariant so the reference grad applies directly):
    # dK/dV specifically exercise the pruned plan's accumulator-homing
    # jump in the zigzag backward.
    g = jax.grad(lambda q_, k_, v_: jnp.sum(ring_attention(
        zigzag_shard(q_, n), zigzag_shard(k_, n), zigzag_shard(v_, n),
        mesh, axis="sp", causal=True, use_flash=True,
        schedule="zigzag", window=W) ** 2), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(lambda q_, k_, v_: jnp.sum(attention_reference(
        q_, k_, v_, causal=True, window=W) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b, nm in zip(g, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4, err_msg=nm)


def test_hop_plan_shapes_and_coverage():
    """The static hop plan must (a) shrink to O(window/chunk) hops,
    (b) cover every mask-visible (q-chunk, k-chunk) device pair —
    checked exhaustively over a grid of (n, chunk, window)."""
    from nbdistributed_tpu.parallel.ring import hop_plan

    # No window -> every step.
    assert hop_plan(8, 16, None) == tuple(range(8))
    # Plain: prefix of 1 + ceil((w-1)/C) steps.
    assert hop_plan(8, 16, 16) == (0, 1)
    assert hop_plan(8, 16, 1) == (0,)
    assert hop_plan(8, 16, 17) == (0, 1)
    assert hop_plan(8, 16, 18) == (0, 1, 2)
    # Zigzag: short prefix + suffix (window neighbors of the high
    # half-chunk arrive at ring distance n-1, n-2, ...).
    zz = hop_plan(8, 16, 8, "zigzag")
    assert 0 in zz and len(zz) < 8 and max(zz) == 7

    # Exhaustive sufficiency: every visible pair is planned.  Plain
    # covers cross-length (Ck != Cq) plans too; zigzag requires equal.
    for n in (2, 4, 8):
        for C in (4, 8):
            for w in (1, 3, C, C + 1, 2 * C, 3 * C + 1):
                for schedule, Ck in (("plain", C // 2), ("plain", C),
                                     ("plain", 2 * C), ("zigzag", C)):
                    if schedule == "zigzag":
                        plan = set(hop_plan(n, 2 * C, w, schedule))
                    else:
                        plan = set(hop_plan(n, C, w, sk_local=Ck))
                    for my in range(n):
                        for s in range(n):
                            src = (my - s) % n
                            if schedule == "zigzag":
                                q_iv = [(my * C, (my + 1) * C),
                                        ((2 * n - 1 - my) * C,
                                         (2 * n - my) * C)]
                                k_iv = [(src * C, (src + 1) * C),
                                        ((2 * n - 1 - src) * C,
                                         (2 * n - src) * C)]
                            else:
                                q_iv = [(my * C, (my + 1) * C)]
                                k_iv = [(src * Ck, (src + 1) * Ck)]
                            # discrete ground truth for this pair
                            visible = any(
                                k0 <= qi and ki <= qi and ki > qi - w
                                for q0, q1 in q_iv
                                for k0, k1 in k_iv
                                for qi in range(q0, q1)
                                for ki in range(k0, k1))
                            if visible:
                                assert s in plan, (n, C, w, schedule,
                                                   my, s)


def test_windowed_ring_skips_hops():
    """SWA x SP must not pay all n hops.  Count
    ppermute equations in the traced program — windowed rings must
    issue strictly fewer collectives than the full causal ring, for
    forward and backward, einsum, flash, and zigzag paths."""
    from nbdistributed_tpu.parallel import mesh as mesh_mod
    from nbdistributed_tpu.parallel.ring import ring_attention

    n = 8
    mesh = mesh_mod.make_mesh({"sp": n})
    B, S, H, Hkv, D, W = 1, 64, 4, 2, 16, 8  # chunk 8, plan (0, 1)

    def _subjaxprs(v):
        vals = v if isinstance(v, (list, tuple)) else [v]
        for x in vals:
            if hasattr(x, "jaxpr") and hasattr(x.jaxpr, "eqns"):
                yield x.jaxpr
            elif hasattr(x, "eqns"):
                yield x

    def _count(jaxpr, mult):
        total = 0
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name == "ppermute":
                total += mult
                continue
            sub = mult
            if name == "while":
                sub = mult * n   # the ring hop loop runs n trips
            elif name == "scan":
                sub = mult * eqn.params.get("length", n)
            for v in eqn.params.values():
                for sj in _subjaxprs(v):
                    total += _count(sj, sub)
        return total

    def executed_ppermutes(fn, *args):
        """ppermutes EXECUTED per call: walk the jaxpr, multiplying
        collectives inside while/scan bodies by the trip count (the
        full ring keeps its per-array ppermute inside the n-trip hop
        fori_loop; the windowed plan path is fully unrolled)."""
        return _count(jax.make_jaxpr(fn)(*args).jaxpr, 1)

    q = rand((B, S, H, D), 40)
    k = rand((B, S, Hkv, D), 41)
    v = rand((B, S, Hkv, D), 42)

    for use_flash in (False, True):
        def fwd(q, k, v, w=None, uf=use_flash):
            return ring_attention(q, k, v, mesh, axis="sp",
                                  causal=True, use_flash=uf, window=w)

        full = executed_ppermutes(fwd, q, k, v)
        win = executed_ppermutes(lambda q, k, v: fwd(q, k, v, W),
                                 q, k, v)
        # plan (0, 1): one k/v jump -> 2 collectives vs 2n in full.
        assert win == 2 and full == 2 * n, (use_flash, win, full)

        def loss(q, k, v, w):
            return jnp.sum(ring_attention(
                q, k, v, mesh, axis="sp", causal=True,
                use_flash=use_flash, window=w) ** 2)

        full_g = executed_ppermutes(
            jax.grad(lambda q, k, v: loss(q, k, v, None),
                     argnums=(0, 1, 2)), q, k, v)
        win_g = executed_ppermutes(
            jax.grad(lambda q, k, v: loss(q, k, v, W),
                     argnums=(0, 1, 2)), q, k, v)
        assert win_g < full_g, (use_flash, win_g, full_g)

    # Zigzag: windowed plan still beats the full ring on collectives.
    def zz(q, k, v, w):
        return ring_attention(q, k, v, mesh, axis="sp", causal=True,
                              use_flash=True, schedule="zigzag",
                              window=w)

    full_zz = executed_ppermutes(lambda q, k, v: zz(q, k, v, None),
                                 q, k, v)
    win_zz = executed_ppermutes(lambda q, k, v: zz(q, k, v, W),
                                q, k, v)
    assert win_zz < full_zz, (win_zz, full_zz)


def test_ring_window_validation():
    from nbdistributed_tpu.parallel import mesh as mesh_mod
    from nbdistributed_tpu.parallel.ring import ring_attention
    from nbdistributed_tpu.parallel.ulysses import ulysses_attention

    mesh = mesh_mod.make_mesh({"sp": 2}, devices=jax.devices()[:2])
    x = jnp.zeros((1, 8, 2, 8))
    with pytest.raises(ValueError, match="causal"):
        ring_attention(x, x, x, mesh, axis="sp", causal=False, window=4)
    with pytest.raises(ValueError, match="window"):
        ring_attention(x, x, x, mesh, axis="sp", window=0)
    with pytest.raises(ValueError, match="causal"):
        ulysses_attention(x, x, x, mesh, axis="sp", causal=False,
                          window=4)


class TestRingSegments:
    """Packed-document masking through the ring: the K-side segment
    chunk rides the ring; every hop masks in both kernel passes."""

    def _inputs(self, B=1, S=64, H=4, Hkv=2, D=16, seed=0):
        import jax
        import jax.numpy as jnp
        ks = jax.random.split(jax.random.PRNGKey(seed), 4)
        q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
        k = jax.random.normal(ks[1], (B, S, Hkv, D), jnp.float32)
        v = jax.random.normal(ks[2], (B, S, Hkv, D), jnp.float32)
        seg = jnp.sort(jax.random.randint(ks[3], (B, S), 0, 3), axis=1)
        return q, k, v, seg

    @pytest.mark.parametrize("use_flash", [False, True])
    def test_matches_reference(self, use_flash):
        import jax
        import numpy as np

        from nbdistributed_tpu.ops import attention_reference
        from nbdistributed_tpu.parallel import mesh as mesh_mod
        from nbdistributed_tpu.parallel.ring import ring_attention
        q, k, v, seg = self._inputs()
        mesh = mesh_mod.make_mesh({"sp": 4}, devices=jax.devices()[:4])
        out = ring_attention(q, k, v, mesh, causal=True,
                             use_flash=use_flash, segment_ids=seg)
        ref = attention_reference(q, k, v, causal=True,
                                  segment_ids=seg)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    @pytest.mark.parametrize("use_flash", [False, True])
    def test_gradients_match_reference(self, use_flash):
        import jax
        import jax.numpy as jnp
        import numpy as np

        from nbdistributed_tpu.ops import attention_reference
        from nbdistributed_tpu.parallel import mesh as mesh_mod
        from nbdistributed_tpu.parallel.ring import ring_attention
        q, k, v, seg = self._inputs()
        mesh = mesh_mod.make_mesh({"sp": 4}, devices=jax.devices()[:4])

        def loss_r(q_, k_, v_):
            return jnp.sum(ring_attention(
                q_, k_, v_, mesh, causal=True, use_flash=use_flash,
                segment_ids=seg) ** 2)

        def loss_ref(q_, k_, v_):
            return jnp.sum(attention_reference(
                q_, k_, v_, causal=True, segment_ids=seg) ** 2)

        gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
        ge = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(gr, ge, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-4,
                                       err_msg=f"d{name}")

    def test_zigzag_segments_match_reference(self):
        """Zigzag + segments: the segment array rides the ring in
        zigzag order like K/V; exact vs the masked reference in fwd
        and q/k/v grads."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from nbdistributed_tpu.ops import attention_reference
        from nbdistributed_tpu.parallel import mesh as mesh_mod
        from nbdistributed_tpu.parallel.ring import (ring_attention,
                                                     zigzag_shard,
                                                     zigzag_unshard)
        q, k, v, seg = self._inputs()
        n = 4
        mesh = mesh_mod.make_mesh({"sp": n}, devices=jax.devices()[:n])
        out_zz = ring_attention(
            zigzag_shard(q, n), zigzag_shard(k, n), zigzag_shard(v, n),
            mesh, causal=True, use_flash=True, schedule="zigzag",
            segment_ids=zigzag_shard(seg, n))
        ref = attention_reference(q, k, v, causal=True,
                                  segment_ids=seg)
        np.testing.assert_allclose(
            np.asarray(zigzag_unshard(out_zz, n)), np.asarray(ref),
            atol=1e-5, rtol=1e-5)

        def loss_zz(q_, k_, v_):
            o = ring_attention(
                zigzag_shard(q_, n), zigzag_shard(k_, n),
                zigzag_shard(v_, n), mesh, causal=True, use_flash=True,
                schedule="zigzag", segment_ids=zigzag_shard(seg, n))
            return jnp.sum(zigzag_unshard(o, n) ** 2)

        def loss_ref(q_, k_, v_):
            return jnp.sum(attention_reference(
                q_, k_, v_, causal=True, segment_ids=seg) ** 2)

        gz = jax.grad(loss_zz, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(gz, gr, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4, rtol=2e-4,
                                       err_msg=f"d{name}")

    def test_model_sp_packed_matches_plain_packed(self):
        """Full train-loss parity: the sp-ring packed loss equals the
        single-device packed loss."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from nbdistributed_tpu.models import (SeqParallel, init_params,
                                              loss_fn, tiny_config)
        from nbdistributed_tpu.parallel import mesh as mesh_mod

        cfg = tiny_config(dtype=jnp.float32, use_flash=False)
        params = init_params(jax.random.PRNGKey(0), cfg)
        mesh = mesh_mod.make_mesh({"sp": 4}, devices=jax.devices()[:4])
        S = 32
        tok = jax.random.randint(jax.random.PRNGKey(1), (2, S), 0,
                                 cfg.vocab_size)
        seg = jnp.sort(jax.random.randint(jax.random.PRNGKey(2),
                                          (2, S), 0, 3), axis=1)
        batch = {"tokens": tok, "segments": seg}
        ref = float(loss_fn(params, batch, cfg))
        sp = SeqParallel(mesh=mesh, axis="sp", method="ring",
                         use_flash=False)
        got = float(loss_fn(params, batch, cfg, sp=sp))
        np.testing.assert_allclose(got, ref, rtol=1e-5)
