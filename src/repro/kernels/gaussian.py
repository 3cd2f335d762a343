"""Separable 5x5 Gaussian blur Pallas kernel (paper benchmark: Gaussian).

One Pallas call, one device op: the kernel reads its vertical halo
itself. Each grid step blurs a full-width block of ``bm`` output rows.
Its input is the matching ``(bm, W)`` block of the 2+2-row-halo'd image
plus the ``(8, W)`` block that follows it, of which the first four rows
are the block's lower context, so every input row is read once and at
most 8 rows more per block. The vertical taps combine rows of the two
blocks in VMEM, then a horizontal pass runs in registers over the full
width (zero-padded edges). The last 8-row block may run past the image;
only its first four rows, which are in it, are read.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ref import GAUSS_TAPS

#: rows of the trailing block that carries a block's lower halo
_HALO_BLOCK = 8


def _blur_kernel(x_ref, h_ref, o_ref):
    t = [float(x) for x in GAUSS_TAPS]
    bm, W = o_ref.shape
    rows = jnp.concatenate([x_ref[...], h_ref[...]], axis=0)
    vert = (t[0] * rows[0:bm] + t[1] * rows[1:bm + 1] +
            t[2] * rows[2:bm + 2] + t[3] * rows[3:bm + 3] +
            t[4] * rows[4:bm + 4])
    # horizontal pass within the full-width block (zero-padded edges)
    xp = jnp.pad(vert, ((0, 0), (2, 2)))
    o_ref[...] = (t[0] * xp[:, 0:W] + t[1] * xp[:, 1:W + 1] +
                  t[2] * xp[:, 2:W + 2] + t[3] * xp[:, 3:W + 3] +
                  t[4] * xp[:, 4:W + 4])


# Largest (rows x W) f32 block, in elements (2 MiB): the kernel holds an
# input and an output block and an 8-row halo block, each
# double-buffered, plus its block-sized temporaries, and all of it must
# fit the TPU's scoped VMEM. Wide images (5120 columns) get 64 rows.
_BLOCK_ELEMS = 1 << 19


def _rows_per_block(H: int, W: int, bm: int) -> int:
    """Rows of one block: ``bm`` cut to a multiple of 8, to ``H`` rounded
    up to 8, and to the largest power of two whose block fits
    ``_BLOCK_ELEMS``."""
    cap = _HALO_BLOCK
    while cap * 2 * W <= _BLOCK_ELEMS:
        cap *= 2
    bm = min(bm, cap, -(-H // _HALO_BLOCK) * _HALO_BLOCK)
    return max(bm - bm % _HALO_BLOCK, _HALO_BLOCK)


def _blur_blocks(padded: jax.Array, bm: int, interpret: bool) -> jax.Array:
    """Blur the interior of ``padded``: ``Hp`` rows, a multiple of ``bm``,
    with two rows of context above and below; returns ``(Hp, W)``."""
    Hp, W = padded.shape[0] - 4, padded.shape[1]
    step = bm // _HALO_BLOCK
    return pl.pallas_call(
        _blur_kernel,
        out_shape=jax.ShapeDtypeStruct((Hp, W), padded.dtype),
        grid=(Hp // bm,),
        in_specs=[pl.BlockSpec((bm, W), lambda i: (i, 0)),
                  pl.BlockSpec((_HALO_BLOCK, W),
                               lambda i: ((i + 1) * step, 0))],
        out_specs=pl.BlockSpec((bm, W), lambda i: (i, 0)),
        interpret=interpret,
    )(padded, padded)


def _blur(img: jax.Array, H: int, bm: int, interpret: bool) -> jax.Array:
    """The (H, W) blur of ``img``, which holds ``H`` rows between two
    rows of context above and below; the last block's pad rows are
    zero-filled and their results dropped."""
    bm = _rows_per_block(H, img.shape[1], bm)
    pm = (-H) % bm
    if pm:
        img = jnp.pad(img, ((0, pm), (0, 0)))
    out = _blur_blocks(img, bm, interpret)
    return out[:H] if pm else out


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def gaussian_blur(img: jax.Array, *, bm: int = 128,
                  interpret: bool = True) -> jax.Array:
    """5x5 separable Gaussian blur, zero padding. img: (H, W) float32."""
    return _blur(jnp.pad(img, ((2, 2), (0, 0))), img.shape[0], bm,
                 interpret)


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def gaussian_blur_halo(img: jax.Array, *, bm: int = 128,
                       interpret: bool = True) -> jax.Array:
    """Blur the interior of an already 2+2-row-halo'd image.

    The co-execution data plane hands each package its row range plus two
    rows of vertical context on either side (zero-filled beyond the full
    image), so this entry consumes the halo directly instead of re-padding:
    ``img`` is (H + 4, W) and the result is the (H, W) interior — the
    halo-aware twin of :func:`gaussian_blur` for split launches. Where
    ``H`` is a multiple of the row block, as the data plane's
    power-of-two buckets are, the program is the one Pallas op.
    """
    return _blur(img, img.shape[0] - 4, bm, interpret)
