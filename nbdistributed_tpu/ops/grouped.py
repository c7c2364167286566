"""Grouped matmul over expert segments with tiles chosen from the
shapes: JAX's own Pallas kernel (``megablox.gmm``) under this repo's
tiling rule.

``jax.lax.ragged_dot`` lowers on the TPU to XLA's grouped-matmul kernel,
whose tiles must divide the operands: an expert of ``2688 x 1856`` (=
``21 x 128`` by ``14.5 x 128``) leaves it ``(256, 128, 128)``, some
20,000 grid steps of a third of a microsecond a call where the weights'
read is 0.8 ms (seen on the chip: 9.5% of the read roofline, 87% of the
busy time).  Here a tile spans the whole contraction and as many
output columns as ``BLOCK_BYTES`` of weights hold: a call is a few
hundred grid steps, each one block of an expert's matrix read once.

On the TPU this is the repo's grouped matmul at every width
(:func:`ragged_dot`, which ``parallel/expert.py`` calls for every
unquantized expert layer): XLA's kernel read SDAR's and JoyAI's
``2048 x 768`` experts, whole 256s both ways, at 31.7% to 49% of the
peak, where a tile here *is* one expert's 3 MiB matrix (82%).  Off the
TPU the interpreter would walk every test's experts tile by tile, and
``jax.lax.ragged_dot`` stays.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import decode

BLOCK_BYTES = 4 << 20       # of one weight tile (two are in flight)
ROW_TILE = 128


def _use_interpret():
    # One switch for a program's Pallas kernels: what forces the decode
    # kernels compiled (a sandbox compile for the TPU,
    # benchmarks/tests/compile_sizes.py) forces this one.
    return decode._use_interpret()


def ragged_dot(x, w, group_sizes):
    """``jax.lax.ragged_dot(x, w, group_sizes)``, on the TPU under
    :func:`grouped_matmul`: there the rows past the groups' total are
    undefined, not zeros."""
    if _use_interpret():
        return jax.lax.ragged_dot(x, w.astype(x.dtype), group_sizes)
    return grouped_matmul(x, w, group_sizes)


def _column_tile(k: int, n: int, itemsize: int) -> int:
    """The widest multiple of 128 that divides ``n`` and keeps a
    ``(k, tile)`` block of weights within :data:`BLOCK_BYTES`; ``n``
    itself where no such multiple exists (a test's sizes)."""
    fits = [t for t in range(128, n + 1, 128)
            if n % t == 0 and k * t * itemsize <= BLOCK_BYTES]
    return max(fits) if fits else n


@jax.custom_vjp
def grouped_matmul(x, w, group_sizes):
    """``x[rows of group g] @ w[g]`` for every group.  x (M, K), its
    rows sorted by group; w (G, K, N); group_sizes (G,) int32, summing
    to at most M -> (M, N) in x's dtype.  Rows past the groups' total
    are not computed: what they hold is undefined, and the caller masks
    them.  A Mosaic call: operands sharded over a mesh need a
    ``shard_map`` around it.  Its derivative is ``ragged_dot``'s."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm
    m, k = x.shape
    n = w.shape[2]
    pad = -m % ROW_TILE
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    out = gmm(x, w.astype(x.dtype), group_sizes.astype(jnp.int32),
              preferred_element_type=x.dtype,
              tiling=(ROW_TILE, k, _column_tile(k, n, x.dtype.itemsize)),
              interpret=_use_interpret())
    return out[:m] if pad else out


def _forward(x, w, group_sizes):
    return grouped_matmul(x, w, group_sizes), (x, w, group_sizes)


def _backward(saved, g):
    # XLA's transposes of the ragged product: the rows past the total
    # give and take nothing there either
    x, w, group_sizes = saved
    _, vjp = jax.vjp(lambda x, w: jax.lax.ragged_dot(
        x, w.astype(x.dtype), group_sizes), x, w)
    return (*vjp(g), None)


grouped_matmul.defvjp(_forward, _backward)
