"""Transformer model tests on the 8-device virtual CPU mesh: forward
shapes/determinism, DDP equivalence, tensor-parallel equivalence."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from nbdistributed_tpu.models import (forward, init_params, loss_fn,
                                      make_train_step, param_shardings,
                                      tiny_config)
from nbdistributed_tpu.parallel import data_parallel, mesh as mesh_mod
from nbdistributed_tpu.parallel import tensor_parallel

# Heavy interpret-mode kernel/model tests: excluded from the
# fast product-path tier (`pytest -m "not slow"`).
pytestmark = [pytest.mark.unit, pytest.mark.slow]

CFG = tiny_config(dtype=jnp.float32, use_flash=False)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


@pytest.fixture(scope="module")
def batch():
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 33), 0,
                                CFG.vocab_size)
    return {"tokens": tokens}


def test_forward_shape_and_dtype(params):
    tokens = jnp.zeros((2, 16), jnp.int32)
    logits = forward(params, tokens, CFG)
    assert logits.shape == (2, 16, CFG.vocab_size)
    assert logits.dtype == jnp.float32


def test_param_count_formula(params):
    actual = sum(int(np.prod(p.shape))
                 for p in jax.tree_util.tree_leaves(params))
    assert actual == CFG.num_params()


def test_causality(params):
    """Changing token t must not affect logits before t."""
    t1 = jnp.zeros((1, 16), jnp.int32)
    t2 = t1.at[0, 10].set(7)
    l1 = forward(params, t1, CFG)
    l2 = forward(params, t2, CFG)
    np.testing.assert_allclose(np.asarray(l1[0, :10]),
                               np.asarray(l2[0, :10]), atol=1e-5)
    assert not np.allclose(np.asarray(l1[0, 10:]), np.asarray(l2[0, 10:]))


def test_loss_decreases_under_training(params, batch):
    opt = optax.adam(1e-2)
    step = make_train_step(CFG, opt)
    p = params
    state = opt.init(p)
    jstep = jax.jit(step)
    first = None
    for _ in range(5):
        p, state, loss = jstep(p, state, batch)
        first = first if first is not None else float(loss)
    assert float(loss) < first


def test_ddp_matches_single_device(params, batch):
    """DDP over 8 virtual devices must be numerically equivalent to
    single-device training (same global batch): loss, updated weights
    and optimizer state."""
    opt = optax.sgd(1e-2, momentum=0.9)
    loss = lambda p, b: loss_fn(p, b, CFG)

    # single device
    def single_step(p, s, b):
        lval, g = jax.value_and_grad(loss)(p, b)
        u, s = opt.update(g, s, p)
        return optax.apply_updates(p, u), s, lval

    p1, s1, l1 = jax.jit(single_step)(params, opt.init(params), batch)

    # DDP over the mesh
    m = mesh_mod.make_mesh({"dp": 8})
    step = data_parallel.make_ddp_step(loss, opt, m, donate=False)
    p_r, s_r = data_parallel.ddp_init(params, opt.init(params), m)
    b_r = mesh_mod.shard_batch(batch, m)
    p2, s2, l2 = step(p_r, s_r, b_r)

    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves((p1, s1)),
                    jax.tree_util.tree_leaves((p2, s2))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("dp", [1, 4])
def test_ddp_step_program(params, batch, dp, monkeypatch):
    """What the compiled DDP step holds.  Over several shards: the
    reduce half of every leaf the shards divide and that is large
    enough goes by asynchronous sends (the layers' inside the backward
    scan), and no all-reduce returns such a leaf.  On a ``dp`` = 1 mesh: the
    program before ISSUE 36, op for op — no send, no manual region,
    and the plain step's count of every op."""
    from nbdistributed_tpu.parallel import overlap
    # (the size from which a leaf is sent is set for Mistral-7B's)
    monkeypatch.setattr(overlap, "EXCHANGE_MIN_SIZE", 1 << 16)
    cfg = tiny_config(dtype=jnp.float32, use_flash=False, d_model=256,
                      d_ff=512, n_heads=4, n_kv_heads=2)
    params = init_params(jax.random.PRNGKey(0), cfg)
    opt = optax.adamw(1e-3)
    loss = lambda p, b: loss_fn(p, b, cfg)
    m = mesh_mod.make_mesh({"dp": dp}, devices=jax.devices()[:dp])
    step = data_parallel.make_ddp_step(loss, opt, m, donate=False)
    p_r, s_r = data_parallel.ddp_init(params, opt.init(params), m)
    b_r = mesh_mod.shard_batch(batch, m)
    lowered = step.lower(p_r, s_r, b_r)
    counts = data_parallel.collectives_of(lowered.compile())
    if dp > 1:
        # a layer's leaves are one leaf of the scan's body
        leaves = ([params["embed"], params["final_norm"],
                   params["lm_head"]]
                  + [x[0] for x in jax.tree.leaves(params["layers"])])
        sent = [x for x in leaves if x.shape[0] % dp == 0
                and x.size >= overlap.EXCHANGE_MIN_SIZE]
        assert len(sent) >= 7
        assert counts["async_sends"] == (dp - 1) * len(sent)
        assert counts["blocking_all_gather_bytes"] == sum(
            x.nbytes for x in sent)
        assert counts["blocking_all_reduce_bytes"] <= 8 + sum(
            x.nbytes for x in leaves if not any(x is y for y in sent))
        body = lowered.as_text().split("stablehlo.while", 1)[1]
        assert "collective_permute" in body
        return
    assert set(counts.values()) == {0}

    def plain(p, s, b):        # the step as it was written before
        lval, g = jax.value_and_grad(loss)(p, b)
        u, s = opt.update(g, s, p)
        return optax.apply_updates(p, u), s, lval

    def ops(text):
        import collections
        import re
        return collections.Counter(re.findall(r"= (?:stablehlo|sdy|func)"
                                              r"\.([a-z_.]+)", text))

    want = ops(jax.jit(plain).lower(params, opt.init(params),
                                    batch).as_text())
    got = ops(lowered.as_text())
    assert got == want and sum(got.values()) > 500
    assert "manual_computation" not in lowered.as_text()


def test_tensor_parallel_matches_replicated(params, batch):
    """tp=4 sharded forward must equal the unsharded forward — XLA
    inserts the Megatron all-reduces from the sharding rules."""
    m = mesh_mod.make_mesh({"dp": 2, "tp": 4})
    rules = param_shardings(CFG)
    p_sharded = tensor_parallel.apply_shardings(params, m, rules)
    tokens = batch["tokens"]

    ref = forward(params, tokens, CFG)
    out = jax.jit(lambda p, t: forward(p, t, CFG))(p_sharded, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


def test_tp_train_step_runs_and_learns(params, batch):
    m = mesh_mod.make_mesh({"dp": 2, "tp": 4})
    rules = param_shardings(CFG)
    opt = optax.adam(1e-2)
    loss = lambda p, b: loss_fn(p, b, CFG)
    step = tensor_parallel.make_tp_train_step(loss, opt, m, rules,
                                              donate=False)
    p = tensor_parallel.apply_shardings(params, m, rules)
    s = opt.init(p)
    b = mesh_mod.shard_batch(batch, m)
    losses = []
    for _ in range(3):
        p, s, lval = step(p, s, b)
        losses.append(float(lval))
    assert losses[-1] < losses[0]


def test_mesh_builder_wildcard():
    m = mesh_mod.make_mesh({"dp": -1, "tp": 2})
    assert m.shape == {"dp": 4, "tp": 2}


def test_mesh_builder_rejects_bad_shapes():
    with pytest.raises(ValueError):
        mesh_mod.make_mesh({"dp": 3})
    with pytest.raises(ValueError):
        mesh_mod.make_mesh({"dp": -1, "tp": -1})


def test_gradient_accumulation_matches_full_batch():
    """accum_steps=2 must give the same update as the full batch (mean
    loss over equal microbatches == full-batch mean)."""
    import optax
    from jax.sharding import PartitionSpec as P
    from nbdistributed_tpu.parallel import mesh as mesh_mod
    from nbdistributed_tpu.parallel import tensor_parallel

    cfg = tiny_config(dtype=jnp.float32, use_flash=False)
    params = init_params(jax.random.PRNGKey(0), cfg)
    opt = optax.adamw(1e-3)
    mesh = mesh_mod.make_mesh({"dp": 2}, devices=jax.devices()[:2])
    rules = jax.tree_util.tree_map(
        lambda spec: P(*[None for _ in spec]), param_shardings(cfg),
        is_leaf=lambda x: isinstance(x, P))
    loss = lambda p, b: loss_fn(p, b, cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 17), 0,
                                cfg.vocab_size)

    outs = {}
    for accum in (1, 2, 4):
        step = tensor_parallel.make_tp_train_step(
            loss, opt, mesh, rules, donate=False, accum_steps=accum)
        p = tensor_parallel.apply_shardings(params, mesh, rules)
        s = opt.init(p)
        b = mesh_mod.shard_batch({"tokens": tokens}, mesh)
        p, s, l = step(p, s, b)
        outs[accum] = (p, float(l))
    for accum in (2, 4):
        np.testing.assert_allclose(outs[accum][1], outs[1][1], rtol=1e-6)
        # fp32 summation order differs (microbatch accumulation vs one
        # batched reduction) and compounds through the adamw update;
        # observed drift ~4e-5 after the full-S logits-shift loss, so
        # the bound is 1e-4.
        for a, b_ in zip(jax.tree_util.tree_leaves(outs[accum][0]),
                         jax.tree_util.tree_leaves(outs[1][0])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       atol=1e-4, rtol=1e-4)


def test_gradient_accumulation_rejects_indivisible():
    import optax
    import pytest
    from jax.sharding import PartitionSpec as P
    from nbdistributed_tpu.parallel import mesh as mesh_mod
    from nbdistributed_tpu.parallel import tensor_parallel

    cfg = tiny_config(dtype=jnp.float32, use_flash=False)
    params = init_params(jax.random.PRNGKey(0), cfg)
    mesh = mesh_mod.make_mesh({"dp": 2}, devices=jax.devices()[:2])
    step = tensor_parallel.make_tp_train_step(
        lambda p, b: loss_fn(p, b, cfg), optax.sgd(1e-3), mesh, None,
        donate=False, accum_steps=3)
    p = jax.device_put(params,
                       jax.sharding.NamedSharding(mesh, P()))
    s = optax.sgd(1e-3).init(p)
    tokens = jnp.zeros((8, 17), jnp.int32)  # 8 % 3 != 0
    with pytest.raises(ValueError, match="not divisible"):
        step(p, s, mesh_mod.shard_batch({"tokens": tokens}, mesh))


def test_remat_matches_no_remat():
    """jax.checkpoint changes memory, never math: loss and grads must
    be bitwise-comparable between remat on/off (fp32, same inputs)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from nbdistributed_tpu.models import init_params, loss_fn, tiny_config

    cfg = tiny_config(dtype=jnp.float32, use_flash=False)
    cfg_r = type(cfg)(**{**cfg.__dict__, "remat": True})
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens}

    l0, g0 = jax.value_and_grad(lambda p: loss_fn(p, batch, cfg))(params)
    l1, g1 = jax.value_and_grad(lambda p: loss_fn(p, batch, cfg_r))(params)
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(g0),
                    jax.tree_util.tree_leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6, rtol=1e-6)

    # The "dots" policy (save matmul outputs, recompute only cheap
    # ops) is also math-neutral; an unknown policy must fail loudly.
    import pytest

    cfg_d = type(cfg)(**{**cfg.__dict__, "remat": True,
                         "remat_policy": "dots"})
    l2, g2 = jax.value_and_grad(lambda p: loss_fn(p, batch, cfg_d))(params)
    np.testing.assert_allclose(float(l0), float(l2), rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(g0),
                    jax.tree_util.tree_leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6, rtol=1e-6)
    # Structured partial policies: checkpoint one sub-block, keep the
    # other's activations — still math-neutral.
    for pol in ("attn_only", "mlp_only"):
        cfg_p = type(cfg)(**{**cfg.__dict__, "remat": True,
                             "remat_policy": pol})
        lp, gp = jax.value_and_grad(
            lambda p: loss_fn(p, batch, cfg_p))(params)
        np.testing.assert_allclose(float(l0), float(lp), rtol=1e-6)
        for a, b in zip(jax.tree_util.tree_leaves(g0),
                        jax.tree_util.tree_leaves(gp)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-6, rtol=1e-6)
    cfg_bad = type(cfg)(**{**cfg.__dict__, "remat": True,
                           "remat_policy": "everything"})
    with pytest.raises(ValueError, match="remat_policy"):
        loss_fn(params, batch, cfg_bad)
    # A policy without remat=True would be silently ignored — reject.
    cfg_off = type(cfg)(**{**cfg.__dict__, "remat": False,
                           "remat_policy": "dots"})
    with pytest.raises(ValueError, match="remat=False"):
        loss_fn(params, batch, cfg_off)


def test_sliding_window_model_paths_agree():
    """sliding_window through the full model: the flash and reference
    attention paths must produce identical logits, and generation with
    a window must match the windowed batch forward (greedy)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from nbdistributed_tpu.models import (forward, generate, init_params,
                                          tiny_config)

    base = tiny_config(dtype=jnp.float32, use_flash=False)
    mk = lambda **kw: type(base)(**{**base.__dict__, **kw})
    cfg_ref = mk(sliding_window=24)
    cfg_flash = mk(sliding_window=24, use_flash=True)
    cfg_full = mk()  # no window
    params = init_params(jax.random.PRNGKey(0), cfg_ref)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0,
                                base.vocab_size)

    lr = forward(params, tokens, cfg_ref)
    lf = forward(params, tokens, cfg_flash)
    np.testing.assert_allclose(np.asarray(lf), np.asarray(lr),
                               atol=2e-4, rtol=2e-4)
    # The window must actually bite: a 24-token window over 64 tokens
    # differs from full causal attention.
    lfull = forward(params, tokens, cfg_full)
    assert float(jnp.max(jnp.abs(lfull - lr))) > 1e-3

    # Windowed KV-cache generation == argmax of the windowed forward.
    prompt = tokens[:, :40]
    gen = generate(params, prompt, cfg_ref, max_new_tokens=1)
    nxt = jnp.argmax(forward(params, prompt, cfg_ref)[:, -1], -1)
    np.testing.assert_array_equal(np.asarray(gen[:, -1]),
                                  np.asarray(nxt))


def _run_fsdp_case(mesh_axes, tp_axis, optimizer, key0, key1):
    """Shared harness: one train step under fsdp_param_shardings on
    ``mesh_axes`` must match replicated training, with the big weights
    genuinely sharded across all devices of the mesh."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from nbdistributed_tpu.models import (fsdp_param_shardings,
                                          make_train_step)

    cfg = tiny_config(dtype=jnp.float32, use_flash=False)
    params = init_params(jax.random.PRNGKey(key0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(key1), (4, 16), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens}
    step = make_train_step(cfg, optimizer)
    ref_p, _, ref_loss = jax.jit(step)(params, optimizer.init(params),
                                       batch)

    n_dev = int(np.prod(list(mesh_axes.values())))
    m = mesh_mod.make_mesh(mesh_axes, devices=jax.devices()[:n_dev])
    rules = fsdp_param_shardings(cfg, tp_axis=tp_axis)
    p_s = jax.device_put(params, jax.tree_util.tree_map(
        lambda sp: NamedSharding(m, sp), rules))
    wq = p_s["layers"]["wq"]
    assert wq.addressable_shards[0].data.size * n_dev == wq.size,         wq.sharding
    tok_s = jax.device_put(tokens, NamedSharding(m, P("dp")))
    got_p, _, got_loss = jax.jit(step)(p_s, optimizer.init(p_s),
                                       {"tokens": tok_s})
    assert np.isclose(float(got_loss), float(ref_loss), atol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(got_p),
                    jax.tree_util.tree_leaves(ref_p)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


def test_fsdp_sharding_matches_replicated():
    """FSDP/ZeRO-3-style weight sharding: exact vs replicated, weights
    genuinely dp-sharded."""
    _run_fsdp_case({"dp": 4}, None, optax.adamw(1e-3), 0, 1)


def test_hsdp_fsdp_plus_tp_matches_replicated():
    """2-D weight sharding (FSDP over dp x Megatron over tp)."""
    _run_fsdp_case({"dp": 2, "tp": 2}, "tp", optax.sgd(1e-2), 2, 3)


def test_packed_documents_match_separate_forwards():
    """The whole packed-training contract in one test: a window
    holding two packed documents (segment mask + per-document RoPE
    positions) must produce, at each document's positions, EXACTLY
    the logits of forwarding that document alone — and the packed
    loss must equal the token-weighted mix of the per-document
    losses."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from nbdistributed_tpu.models import (forward, init_params, loss_fn,
                                          packed_positions, tiny_config)
    from nbdistributed_tpu.models.transformer import shifted_xent

    for use_flash in (False, True):
        cfg = tiny_config(dtype=jnp.float32, use_flash=use_flash)
        params = init_params(jax.random.PRNGKey(0), cfg)
        la, lb = 20, 12
        d0 = jax.random.randint(jax.random.PRNGKey(1), (1, la), 0,
                                cfg.vocab_size)
        d1 = jax.random.randint(jax.random.PRNGKey(2), (1, lb), 0,
                                cfg.vocab_size)
        packed = jnp.concatenate([d0, d1], axis=1)
        seg = jnp.concatenate([jnp.zeros((1, la), jnp.int32),
                               jnp.ones((1, lb), jnp.int32)], axis=1)
        pos = packed_positions(seg)
        np.testing.assert_array_equal(
            np.asarray(pos[0]),
            np.concatenate([np.arange(la), np.arange(lb)]))

        lp = forward(params, packed, cfg, pos, segment_ids=seg)
        l0 = forward(params, d0, cfg)
        l1 = forward(params, d1, cfg)
        np.testing.assert_allclose(np.asarray(lp[:, :la]),
                                   np.asarray(l0), atol=2e-5,
                                   rtol=2e-5,
                                   err_msg=f"doc0 flash={use_flash}")
        np.testing.assert_allclose(np.asarray(lp[:, la:]),
                                   np.asarray(l1), atol=2e-5,
                                   rtol=2e-5,
                                   err_msg=f"doc1 flash={use_flash}")

        # Packed loss == token-weighted mean of the per-doc losses
        # (the boundary target is excluded, so the target counts are
        # (la-1) and (lb-1)).
        packed_loss = float(loss_fn(params, {"tokens": packed,
                                             "segments": seg}, cfg))
        per0 = float(shifted_xent(l0, d0))
        per1 = float(shifted_xent(l1, d1))
        mix = (per0 * (la - 1) + per1 * (lb - 1)) / (la + lb - 2)
        np.testing.assert_allclose(packed_loss, mix, rtol=1e-5)


def test_pack_tokens_segments_roundtrip():
    from nbdistributed_tpu.utils.data import pack_tokens

    docs = [[1, 2, 3], [4, 5], [6, 7, 8, 9]]
    win, seg = pack_tokens(docs, 5, eos_id=0, return_segments=True)
    assert win.shape == seg.shape == (2, 5)
    np.testing.assert_array_equal(win[0], [1, 2, 3, 0, 4])
    np.testing.assert_array_equal(seg[0], [0, 0, 0, 0, 1])
    np.testing.assert_array_equal(win[1], [5, 0, 6, 7, 8])
    np.testing.assert_array_equal(seg[1], [1, 1, 2, 2, 2])
    # Padded trailing window inherits the final doc's segment.
    win2, seg2 = pack_tokens(docs, 4, eos_id=0, drop_remainder=False,
                             return_segments=True)
    assert win2.shape == seg2.shape == (3, 4)
    np.testing.assert_array_equal(seg2[-1], [2, 2, 2, 2])
