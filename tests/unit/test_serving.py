"""Continuous-batching decode server: staggered admission must be
bit-identical per request to standalone generate(), slots must recycle,
EOS must cut streams, and MoE configs must serve through row_mask.

Every test runs at two page geometries of the server's one pool (the
``block`` fixture): blocks of 4 tokens — a row is many pages, with page
boundaries inside every prompt — and blocks of 64 — a row is a table
of one page, the dense slot pool."""

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


@pytest.fixture(params=[4, 64], ids=lambda b: f"block{b}")
def block(request):
    return request.param


def solo(params, cfg, prompt, n):
    out = generate(params, jnp.asarray(prompt, jnp.int32)[None], cfg, n)
    return [int(t) for t in np.asarray(out)[0][len(prompt):]]


# The case at blocks of 8 came from test_paged_decode.py, which ran
# the same requests.
@pytest.mark.parametrize("block", [4, 8, 64], ids=lambda b: f"block{b}")
def test_staggered_admission_matches_solo_generate(setup, block):
    """Three requests of different lengths admitted at different times
    into a 2-slot pool: every request's greedy tokens must equal its
    standalone generate() run — occupancy, admission order and paging
    must be invisible to the numerics."""
    cfg, params = setup
    reqs = [([5, 9, 2], 7), ([7, 1, 3, 11, 4], 5), ([2, 2], 6)]
    srv = DecodeServer(params, cfg, max_batch=2, max_len=64, pad_to=4,
                       kv_block_tokens=block)

    r0 = srv.submit(*reqs[0])
    srv.step()
    r1 = srv.submit(*reqs[1])          # fills the second slot
    srv.step()
    r2 = srv.submit(*reqs[2])          # queues until a slot frees
    srv.run_until_done(max_steps=100)

    for rid, (prompt, n) in zip((r0, r1, r2), reqs):
        assert srv.outputs[rid] == solo(params, cfg, prompt, n), rid
    # Every block returned to the pool at finish.
    snap = srv.kv_snapshot()
    assert snap["used"] == 0 and snap["owners"] == {}


def test_slots_recycle_and_outputs_complete(setup, block):
    """More requests than slots: all finish, each with exactly its
    token budget (no EOS in play for random-init logits over a tiny
    vocab is not guaranteed — so disable EOS)."""
    cfg, params = setup
    srv = DecodeServer(params, cfg, max_batch=2, max_len=32, pad_to=4,
                       kv_block_tokens=block)
    rids = [srv.submit([i + 1, i + 2], 4) for i in range(5)]
    srv.run_until_done(max_steps=200)
    assert srv.done() and srv.n_active == 0
    for rid in rids:
        assert len(srv.outputs[rid]) == 4
    assert srv.finished == set(rids)


def test_eos_frees_slot_early(setup, block):
    """A request whose next greedy token IS the eos id must finish on
    that step with the eos included, freeing the slot."""
    cfg, params = setup
    prompt, n = [5, 9, 2], 8
    toks = solo(params, cfg, prompt, n)
    eos = toks[2]                       # force an early cut at step 3
    srv = DecodeServer(params, cfg, max_batch=1, max_len=64,
                       pad_to=4, eos_id=eos, kv_block_tokens=block)
    rid = srv.submit(prompt, n)
    srv.run_until_done(max_steps=50)
    got = srv.outputs[rid]
    assert got == toks[:got.index(eos) + 1]
    assert got[-1] == eos and len(got) <= n


def test_single_token_budget_finishes_at_admission(setup, block):
    cfg, params = setup
    srv = DecodeServer(params, cfg, max_batch=1, max_len=32, pad_to=4,
                       kv_block_tokens=block)
    rid = srv.submit([3, 1, 4], 1)
    assert srv.done()
    assert srv.outputs[rid] == solo(params, cfg, [3, 1, 4], 1)


def test_validation_errors(setup, block):
    cfg, params = setup
    with pytest.raises(ValueError, match="top_k"):
        DecodeServer(params, cfg, max_batch=1, max_len=32, top_k=0,
                     kv_block_tokens=block)
    with pytest.raises(ValueError, match="top_p"):
        DecodeServer(params, cfg, max_batch=1, max_len=32, top_p=0.0,
                     kv_block_tokens=block)
    srv = DecodeServer(params, cfg, max_batch=1, max_len=16, pad_to=4,
                       kv_block_tokens=block)
    with pytest.raises(ValueError, match="empty"):
        srv.submit([], 4)
    with pytest.raises(ValueError, match=">= 1"):
        srv.submit([1], 0)
    with pytest.raises(ValueError, match="exceeds max_len"):
        srv.submit([1] * 10, 10)


def test_sampled_mode_runs_and_respects_budget(setup, block):
    cfg, params = setup
    srv = DecodeServer(params, cfg, max_batch=2, max_len=32, pad_to=4,
                       temperature=1.0, top_k=8,
                       key=jax.random.PRNGKey(7), kv_block_tokens=block)
    rids = [srv.submit([4, 2], 5), srv.submit([9], 3)]
    srv.run_until_done(max_steps=50)
    assert [len(srv.outputs[r]) for r in rids] == [5, 3]
    for r in rids:
        assert all(0 <= t < cfg.vocab_size for t in srv.outputs[r])


def test_int8_cache_serving_matches_int8_generate(setup, block):
    """kv_quantized serving must equal kv_quantized generate per
    request (same quantized-cache numerics path)."""
    cfg, params = setup
    prompt, n = [5, 9, 2, 7], 6
    ref = generate(params, jnp.asarray(prompt, jnp.int32)[None], cfg,
                   n, kv_quantized=True)
    ref = [int(t) for t in np.asarray(ref)[0][len(prompt):]]
    srv = DecodeServer(params, cfg, max_batch=2, max_len=32, pad_to=4,
                       kv_quantized=True, kv_block_tokens=block)
    rid = srv.submit(prompt, n)
    srv.run_until_done(max_steps=50)
    assert srv.outputs[rid] == ref


def test_int4_params_serving_matches_int4_generate(setup, block):
    """Nibble-packed int4 weights serve through DecodeServer exactly
    as through standalone generate (the qlinear packed path under the
    server's paged cache)."""
    from nbdistributed_tpu.models import quantize_params4
    cfg, params = setup
    q4 = quantize_params4(params)
    prompt, n = [5, 9, 2, 7], 6
    ref = generate(q4, jnp.asarray(prompt, jnp.int32)[None], cfg,
                   n, kv_quantized=True)
    ref = [int(t) for t in np.asarray(ref)[0][len(prompt):]]
    srv = DecodeServer(q4, cfg, max_batch=2, max_len=32, pad_to=4,
                       kv_quantized=True, kv_block_tokens=block)
    rid = srv.submit(prompt, n)
    srv.run_until_done(max_steps=50)
    assert srv.outputs[rid] == ref


def test_token_mask_keeps_pads_out_of_expert_capacity(block):
    """forward_with_cache's token_mask: right-pad tokens routed
    through a tight-capacity MoE flood an expert's segment and evict
    real tokens' second-choice slots — with the mask, the padded
    prefill's last-real-token logits equal the unpadded run's; without
    it (seed pair pinned by a scan) they provably differ.  Over a dense
    cache (the library's loops) and over the server's paged pool, one
    row's table."""
    from nbdistributed_tpu.models import init_moe_model, tiny_moe_config
    from nbdistributed_tpu.models.generate import (forward_with_cache,
                                                   init_kv_cache)
    from nbdistributed_tpu.models.paged_kv import make_paged_pool
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
    n_pages = -(-80 // block)
    paged = {"block_table": jnp.arange(n_pages, dtype=jnp.int32)[None]}

    def run(toks, dense, **kw):
        cache, where = ((init_kv_cache(cfg, 1, 80), {}) if dense else
                        (make_paged_pool(cfg, n_pages, block), paged))
        return forward_with_cache(params, toks, cache, 0, cfg,
                                  last_index=idx, **where, **kw)[0]

    for dense in (True, False):
        ref = run(prompt[None], dense)
        masked = run(padded, dense, token_mask=mask)
        unmasked = run(padded, dense)
        # Masked pads change nothing vs the unpadded run (no real-token
        # drops at this size on either side)...
        np.testing.assert_allclose(np.asarray(masked), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)
        # ...while unmasked pads provably perturb the real tokens.
        assert float(jnp.max(jnp.abs(unmasked - ref))) > 0.1


def test_moe_long_prompt_exact_length_admission(block):
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
    srv = DecodeServer(params, cfg, max_batch=1, max_len=80, pad_to=64,
                       kv_block_tokens=block)
    rid = srv.submit(prompt, n)
    srv.run_until_done(max_steps=50)
    assert srv.outputs[rid] == ref


def test_release_evicts_and_guards_in_flight(setup, block):
    cfg, params = setup
    srv = DecodeServer(params, cfg, max_batch=1, max_len=32, pad_to=4,
                       kv_block_tokens=block)
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


def test_moe_family_serves(block):
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
    srv = DecodeServer(params, cfg, max_batch=2, max_len=32, pad_to=4,
                       kv_block_tokens=block)
    rid = srv.submit(prompt, n)
    srv.run_until_done(max_steps=50)
    assert srv.outputs[rid] == ref


def test_paged_server_on_mesh_matches_solo_generate(setup, block):
    """The pool over a dp×tp mesh of host devices (what a multi-device
    rank's ``serve_open`` builds): KV heads sharded over tp, the block
    axis replicated, each layer gathered through the table
    (``paged_kv.gather_layer``); staggered streams stay solo-exact."""
    from nbdistributed_tpu.models import param_shardings
    from nbdistributed_tpu.parallel import mesh as mesh_mod
    from nbdistributed_tpu.parallel.tensor_parallel import \
        apply_shardings
    cfg, params = setup
    mesh = mesh_mod.make_mesh({"dp": 2, "tp": 2},
                              devices=jax.devices()[:4])
    ps = apply_shardings(params, mesh, param_shardings(cfg))
    reqs = [([3, 1, 4, 1, 5, 9, 2, 6], 5), ([3, 1, 4, 8], 5),
            ([9, 9], 5)]
    srv = DecodeServer(ps, cfg, max_batch=2, max_len=32, pad_to=4,
                       mesh=mesh, kv_block_tokens=block)
    assert srv.kv_view_bytes > 0        # no kernel reads in place here
    rids = [srv.submit(p, n) for p, n in reqs]
    srv.run_until_done(max_steps=100)
    for rid, (p, n) in zip(rids, reqs):
        assert srv.outputs[rid] == solo(params, cfg, p, n), (rid, p)


# ---------------------------------------------------------------------
# chunked prefill admission

@pytest.mark.parametrize("L", [7, 12, 13])
def test_chunked_prefill_matches_solo(setup, L, block):
    """Chunked admission (chunk=4: exact-multiple, tail, and
    shorter-than-chunk prompts) must be invisible to the numerics —
    outputs equal solo generate and bucketed admission."""
    cfg, params = setup
    prompt = [int(t) for t in jax.random.randint(
        jax.random.PRNGKey(40 + L), (L,), 1, cfg.vocab_size)]
    n = 5
    ref = solo(params, cfg, prompt, n)
    srv = DecodeServer(params, cfg, max_batch=2, max_len=64, pad_to=4,
                       prefill_chunk=4, kv_block_tokens=block)
    rid = srv.submit(prompt, n)
    srv.run_until_done(max_steps=30)
    assert srv.outputs[rid] == ref


# The case at blocks of 8 came from test_paged_decode.py.
@pytest.mark.parametrize("block", [4, 8, 64], ids=lambda b: f"block{b}")
def test_interleaved_chunked_prefill_matches_solo(setup, block):
    """A long prompt streamed in 4-token chunks BETWEEN decode ticks
    of an already-active request: both streams bit-identical to their
    solo runs — the chunk boundary is KV-exact and interleaving
    changes latency shape only."""
    cfg, params = setup
    short, long = ([5, 9, 2], 6), ([7, 1, 3, 11, 4, 2, 8, 6, 1, 9,
                                    4, 4, 2, 7], 5)
    srv = DecodeServer(params, cfg, max_batch=2, max_len=32, pad_to=4,
                       kv_block_tokens=block, prefill_chunk=4,
                       interleave_prefill=True)
    r_short = srv.submit(*short)
    srv.step()                         # short is decoding
    r_long = srv.submit(*long)         # streams in one chunk per step
    assert srv.prefill_progress() == {r_long: (0, len(long[0]))}
    srv.run_until_done(max_steps=100)
    assert srv.outputs[r_short] == solo(params, cfg, *short)
    assert srv.outputs[r_long] == solo(params, cfg, *long)


def test_chunked_prefill_single_compile_shape(setup, block):
    """Every chunk segment shares one (1, chunk) program: admitting
    prompts of different lengths > chunk adds ONE prefill executable,
    where bucketed admission would mint one per bucket."""
    cfg, params = setup
    srv = DecodeServer(params, cfg, max_batch=2, max_len=64, pad_to=4,
                       prefill_chunk=4, kv_block_tokens=block)
    program = srv._prefill_fn.program
    if not hasattr(program, "_cache_size"):
        pytest.skip("jit cache introspection unavailable")
    r0 = srv.submit([int(t) for t in range(1, 10)], 2)    # L=9
    r1 = srv.submit([int(t) for t in range(1, 14)], 2)    # L=13
    srv.run_until_done(max_steps=20)
    assert program._cache_size() == 1
    assert len(srv.outputs[r0]) == 2 and len(srv.outputs[r1]) == 2


def test_chunked_prefill_rejected_for_moe(block):
    from nbdistributed_tpu.models import init_moe_model, tiny_moe_config
    cfg = tiny_moe_config(dtype=jnp.float32, use_flash=False)
    params = init_moe_model(jax.random.PRNGKey(4), cfg)
    with pytest.raises(ValueError, match="capacity-based"):
        DecodeServer(params, cfg, max_batch=1, max_len=32,
                     prefill_chunk=8, kv_block_tokens=block)
    with pytest.raises(ValueError, match="prefill_chunk"):
        DecodeServer(params, tiny_config(dtype=jnp.float32,
                                         use_flash=False),
                     max_batch=1, max_len=32, prefill_chunk=0,
                     kv_block_tokens=block)
