"""Paged KV-cache decode (ISSUE 17): the device half of the block
allocator.  A slot's table-selected blocks hold, token for token, what
its dense row would, so paged greedy serving must be BIT-IDENTICAL to solo
``generate()`` — with dense admission order, quantized caches, and
chunked/interleaved prefill all invisible to the numerics — while the
allocator-backed pool recycles blocks across requests.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nbdistributed_tpu.models import generate, init_params, tiny_config
from nbdistributed_tpu.models.serving import DecodeServer

# Heavy interpret-mode model tests: excluded from the fast
# product-path tier (`pytest -m "not slow"`).
pytestmark = [pytest.mark.unit, pytest.mark.serve, pytest.mark.slow]


@pytest.fixture(scope="module")
def setup():
    cfg = tiny_config(dtype=jnp.float32, use_flash=False)
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def solo(params, cfg, prompt, n, **kw):
    out = generate(params, jnp.asarray(prompt, jnp.int32)[None], cfg,
                   n, **kw)
    return [int(t) for t in np.asarray(out)[0][len(prompt):]]


def test_paged_staggered_matches_solo_generate(setup):
    """Staggered admission into a paged 2-slot pool: every request's
    greedy stream equals its standalone generate() run — paging must
    change capacity accounting only, never tokens."""
    cfg, params = setup
    reqs = [([5, 9, 2], 7), ([7, 1, 3, 11, 4], 5), ([2, 2], 6)]
    srv = DecodeServer(params, cfg, max_batch=2, max_len=32, pad_to=4,
                       kv_block_tokens=8)
    r0 = srv.submit(*reqs[0])
    srv.step()
    r1 = srv.submit(*reqs[1])
    srv.step()
    r2 = srv.submit(*reqs[2])          # queues until a slot frees
    srv.run_until_done(max_steps=100)
    for rid, (prompt, n) in zip((r0, r1, r2), reqs):
        assert srv.outputs[rid] == solo(params, cfg, prompt, n), rid
    # Every block returned to the pool at finish.
    snap = srv.kv_snapshot()
    assert snap["used"] == 0 and snap["owners"] == {}


def test_paged_block_starved_pool_recycles(setup):
    """A pool with only enough blocks for ONE worst-case request at a
    time: later submissions park as pending (the self-healing
    admission backstop) and admit as finishing requests free their
    blocks — all complete, all bit-exact."""
    cfg, params = setup
    reqs = [([i + 1, i + 2], 4) for i in range(4)]
    srv = DecodeServer(params, cfg, max_batch=2, max_len=16, pad_to=4,
                       kv_block_tokens=8,
                       kv_blocks=1)        # ceil((2+4)/8) = 1 block
    rids = [srv.submit(*r) for r in reqs]
    assert srv.kv_snapshot()["used"] == 1  # one admitted, three park
    srv.run_until_done(max_steps=200)
    for rid, (prompt, n) in zip(rids, reqs):
        assert srv.outputs[rid] == solo(params, cfg, prompt, n)
    assert srv.kv_snapshot()["used"] == 0


def test_paged_int8_kv_matches_int8_generate(setup):
    """Paged + int8-quantized KV: the quantized payload and its scales
    are written and read together, so the stream equals the dense
    int8 reference token for token (the quantized round-trip adds no
    further error)."""
    cfg, params = setup
    prompt, n = [5, 9, 2, 7], 6
    ref = solo(params, cfg, prompt, n, kv_quantized=True)
    srv = DecodeServer(params, cfg, max_batch=2, max_len=32, pad_to=4,
                       kv_quantized=True, kv_block_tokens=8)
    rid = srv.submit(prompt, n)
    srv.run_until_done(max_steps=50)
    assert srv.outputs[rid] == ref


def test_paged_interleaved_chunked_prefill_matches_solo(setup):
    """A long prompt streamed in 4-token chunks BETWEEN decode ticks
    of an already-active request: both streams bit-identical to their
    solo runs — the chunk boundary is KV-exact and interleaving
    changes latency shape only."""
    cfg, params = setup
    short, long = ([5, 9, 2], 6), ([7, 1, 3, 11, 4, 2, 8, 6, 1, 9,
                                    4, 4, 2, 7], 5)
    srv = DecodeServer(params, cfg, max_batch=2, max_len=32, pad_to=4,
                       kv_block_tokens=8, prefill_chunk=4,
                       interleave_prefill=True)
    r_short = srv.submit(*short)
    srv.step()                         # short is decoding
    r_long = srv.submit(*long)         # streams in one chunk per step
    srv.run_until_done(max_steps=100)
    assert srv.outputs[r_short] == solo(params, cfg, *short)
    assert srv.outputs[r_long] == solo(params, cfg, *long)


def test_cancel_frees_blocks_immediately(setup):
    """A cancelled mid-decode request must return its blocks NOW (a
    shed request cannot pin KV until its stream would have ended) and
    the freed blocks must admit the next request."""
    cfg, params = setup
    srv = DecodeServer(params, cfg, max_batch=1, max_len=16, pad_to=4,
                       kv_block_tokens=8, kv_blocks=1)
    r0 = srv.submit([5, 9], 6)         # 8 tokens = the whole pool
    srv.step()
    assert srv.kv_snapshot()["used"] == 1
    assert srv.cancel(r0) is True
    assert srv.kv_snapshot()["used"] == 0
    assert srv.cancel(r0) is False     # already finished: no-op
    r1 = srv.submit([3, 1], 4)
    srv.run_until_done(max_steps=50)
    assert srv.outputs[r1] == solo(params, cfg, [3, 1], 4)


def test_kv_snapshot_surface(setup):
    """The snapshot the heartbeat telemetry reads: paged servers
    report block occupancy with per-request owner counts; dense
    servers report None."""
    cfg, params = setup
    dense = DecodeServer(params, cfg, max_batch=1, max_len=16,
                         pad_to=4)
    assert dense.kv_snapshot() is None
    srv = DecodeServer(params, cfg, max_batch=2, max_len=16, pad_to=4,
                       kv_block_tokens=4)
    rid = srv.submit([5, 9, 2], 4)     # ceil((3+4)/4) = 2 blocks
    srv.step()
    snap = srv.kv_snapshot()
    assert snap["block_tokens"] == 4
    assert snap["blocks"] == 2 * (16 // 4)   # dense-capacity default
    assert snap["used"] == 2 and snap["owners"] == {str(rid): 2} \
        or snap["owners"] == {rid: 2}


def test_step_kernels_probe_only_traces(setup):
    """What serve_open reports: compiled Pallas kernels in the step
    program the server runs, dense or paged.  The CPU interprets
    kernels, so none here; and lowering must not consume the donated
    pool — the server serves afterwards as if never asked."""
    cfg, params = setup
    for kw in ({}, {"kv_block_tokens": 8}):
        srv = DecodeServer(params, cfg, max_batch=2, max_len=32,
                           pad_to=4, **kw)
        assert srv.step_kernels() == 0
        rid = srv.submit([5, 9, 2], 6)
        srv.run_until_done(max_steps=50)
        assert srv.outputs[rid] == solo(params, cfg, [5, 9, 2], 6)


def test_paged_validation(setup):
    cfg, params = setup
    with pytest.raises(ValueError, match="kv_block_tokens"):
        DecodeServer(params, cfg, max_batch=1, max_len=16,
                     kv_block_tokens=0)
    with pytest.raises(ValueError, match="kv_blocks"):
        DecodeServer(params, cfg, max_batch=1, max_len=16,
                     kv_blocks=4)
    with pytest.raises(ValueError, match="interleave_prefill"):
        DecodeServer(params, cfg, max_batch=1, max_len=16,
                     interleave_prefill=True)
