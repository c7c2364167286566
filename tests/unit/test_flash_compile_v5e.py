"""The flash kernels at the tiles they derive, through the chip's own
compiler: ``jax.experimental.topologies`` describes a v5e that is not
attached, and lowering for it runs Mosaic (block-shape rules, lane
alignment of the dK/dV kernel's row slices, the VMEM limit each call
states from its arithmetic).  Interpret mode checks none of that.
Nothing executes; a machine where the topology cannot be described
skips."""

import jax
import jax.numpy as jnp
import pytest

pytestmark = pytest.mark.unit


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # no libtpu, or it is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    from nbdistributed_tpu.ops import attention as att
    monkeypatch.setattr(att, "_use_interpret", lambda: False)
    return att


# B, Sq, Sk, H, Hkv, D, window, packed, dtype: the training cell's call
# (and packed), a ragged GQA call that pads, a ring hop's Sq != Sk, and
# 16k tokens, whose K and V planes alone are the default scoped VMEM.
CASES = [
    (1, 4096, 4096, 32, 8, 128, 4096, False, jnp.bfloat16),
    (1, 4096, 4096, 32, 8, 128, 1024, True, jnp.bfloat16),
    (2, 1000, 1000, 9, 3, 64, None, True, jnp.float32),
    (1, 1024, 2048, 8, 2, 128, None, False, jnp.bfloat16),
    (1, 16384, 16384, 8, 2, 128, None, False, jnp.bfloat16),
]


@pytest.mark.parametrize("B,Sq,Sk,H,Hkv,D,window,packed,dtype", CASES)
def test_mosaic_takes_the_derived_tiles(one_chip, compiled_kernels, B, Sq,
                                        Sk, H, Hkv, D, window, packed,
                                        dtype):
    att = compiled_kernels
    sd = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                sharding=one_chip)
    q, kv = sd((B, Sq, H, D), dtype), sd((B, Sk, Hkv, D), dtype)
    args = (q, kv, kv) + ((sd((B, Sq), jnp.int32),) if packed else ())

    def loss(q, k, v, seg=None):
        return att.flash_attention(q, k, v, True, None, None, None,
                                   window, seg).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        *args).compile().as_text()
    for name in ("nbd_flash_fwd", "nbd_flash_bwd_dq", "nbd_flash_bwd_dkv"):
        assert name in text, name
    # the forward keeps the result the benchmark's roofline reader
    # finds it by: (out, logsumexp)
    pad = -(-Sq // 128) * 128
    out_t = {"bfloat16": "bf16", "float32": "f32"}[jnp.dtype(dtype).name]
    assert (f"({out_t}[{B * Hkv},{H // Hkv},{pad},{D}]"
            in text.replace(" ", "")), "forward's result signature"
