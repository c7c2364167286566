"""Paged KV-cache decode (ISSUE 17): the device half of the block
allocator.  A slot's table-selected blocks hold, token for token, what
a dense row would, so paged greedy serving must be BIT-IDENTICAL to solo
``generate()`` while the allocator-backed pool recycles blocks across
requests.  (Staggered admission and interleaved chunked prefill at
this file's blocks of 8 are cases of ``test_serving.py``'s tests.)
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
    """The snapshot the heartbeat telemetry reads: block occupancy
    with per-request owner counts."""
    cfg, params = setup
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
    program the server runs, a row one page (the default block) or
    several.  The CPU interprets kernels, so none here; and lowering
    must not consume the donated pool — the server serves afterwards
    as if never asked."""
    cfg, params = setup
    for kw in ({}, {"kv_block_tokens": 8}):
        srv = DecodeServer(params, cfg, max_batch=2, max_len=32,
                           pad_to=4, **kw)
        assert srv.kv_snapshot()["block_tokens"] == kw.get(
            "kv_block_tokens", 64)
        assert srv.step_kernels() == 0
        rid = srv.submit([5, 9, 2], 6)
        srv.run_until_done(max_steps=50)
        assert srv.outputs[rid] == solo(params, cfg, [5, 9, 2], 6)


def test_paged_validation(setup):
    cfg, params = setup
    with pytest.raises(ValueError, match="kv_block_tokens"):
        DecodeServer(params, cfg, max_batch=1, max_len=16,
                     kv_block_tokens=0)
    with pytest.raises(ValueError, match="interleave_prefill"):
        DecodeServer(params, cfg, max_batch=1, max_len=16,
                     interleave_prefill=True)
