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
"""

from __future__ import annotations

import jax.numpy as jnp

from ._common import use_interpret as _use_interpret

BLOCK_BYTES = 4 << 20       # of one weight tile (two are in flight)
ROW_TILE = 128


def xla_tiles_narrow(k: int, n: int) -> bool:
    """Whether experts ``k`` wide in and ``n`` wide inside should take
    :func:`grouped_matmul`: on the TPU, where a width that is not whole
    256s leaves XLA's kernel tiles of 128 (the module docstring).
    Elsewhere, and for widths that are whole 256s (``2048 x 768`` reads
    its weights at half the peak under XLA's kernel, five times what
    ``2688 x 1856`` reached), ``jax.lax.ragged_dot`` stays."""
    return not _use_interpret() and bool(k % 256 or n % 256)


def _column_tile(k: int, n: int, itemsize: int) -> int:
    """The widest multiple of 128 that divides ``n`` and keeps a
    ``(k, tile)`` block of weights within :data:`BLOCK_BYTES`; ``n``
    itself where no such multiple exists (a test's sizes)."""
    fits = [t for t in range(128, n + 1, 128)
            if n % t == 0 and k * t * itemsize <= BLOCK_BYTES]
    return max(fits) if fits else n


def grouped_matmul(x, w, group_sizes):
    """``x[rows of group g] @ w[g]`` for every group.  x (M, K), its
    rows sorted by group; w (G, K, N); group_sizes (G,) int32, summing
    to at most M -> (M, N) in x's dtype.  Rows past the groups' total
    are not computed: what they hold is undefined, and the caller masks
    them."""
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
