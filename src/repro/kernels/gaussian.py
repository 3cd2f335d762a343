"""Separable 5x5 Gaussian blur Pallas kernel (paper benchmark: Gaussian).

TPU adaptation of the stencil: BlockSpec element offsets are multiples of
the block shape, so vertical halos cannot be expressed as overlapping
blocks. Instead the wrapper materializes the five vertically-shifted views
(zero-padded) — XLA fuses these into cheap slices — and the kernel fuses the
vertical tap combine with an in-register horizontal pass over a full-width
row block. One VMEM round trip per pixel, no halo exchange.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ref import GAUSS_TAPS


def _blur_kernel(s0, s1, s2, s3, s4, o_ref):
    t = [float(x) for x in GAUSS_TAPS]
    vert = (t[0] * s0[...] + t[1] * s1[...] + t[2] * s2[...] +
            t[3] * s3[...] + t[4] * s4[...])
    # horizontal pass within the full-width block (zero-padded edges)
    xp = jnp.pad(vert, ((0, 0), (2, 2)))
    W = vert.shape[1]
    o_ref[...] = (t[0] * xp[:, 0:W] + t[1] * xp[:, 1:W + 1] +
                  t[2] * xp[:, 2:W + 2] + t[3] * xp[:, 3:W + 3] +
                  t[4] * xp[:, 4:W + 4])


# Largest (rows x W) f32 block, in elements (1 MiB): the kernel holds six
# such blocks, double-buffered, plus its temporaries, and all of it must
# fit the TPU's scoped VMEM. Wide images (5120 columns) get fewer rows.
_BLOCK_ELEMS = 1 << 18


def _blur_blocks(padded: jax.Array, H: int, W: int, bm: int,
                 interpret: bool) -> jax.Array:
    """Run the blur over `padded` (H+4 rows incl. the 2+2 vertical halo).

    Returns the (H, W) interior result; rows past H in the last block are
    computed on zero padding and sliced off. ``bm`` is capped to the
    largest power of two (at least 8) whose block fits ``_BLOCK_ELEMS``.
    """
    cap = 8
    while cap * 2 * W <= _BLOCK_ELEMS:
        cap *= 2
    bm = min(bm, cap, H)
    pm = (-H) % bm
    padded = jnp.pad(padded, ((0, pm), (0, 0)))
    Hp = H + pm
    shifts = [jax.lax.dynamic_slice_in_dim(padded, d, Hp, axis=0)
              for d in range(5)]
    spec = pl.BlockSpec((bm, W), lambda i: (i, 0))
    out = pl.pallas_call(
        _blur_kernel,
        out_shape=jax.ShapeDtypeStruct((Hp, W), padded.dtype),
        grid=(Hp // bm,),
        in_specs=[spec] * 5,
        out_specs=spec,
        interpret=interpret,
    )(*shifts)
    return out[:H]


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def gaussian_blur(img: jax.Array, *, bm: int = 128,
                  interpret: bool = True) -> jax.Array:
    """5x5 separable Gaussian blur, zero padding. img: (H, W) float32."""
    H, W = img.shape
    return _blur_blocks(jnp.pad(img, ((2, 2), (0, 0))), H, W, bm, interpret)


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def gaussian_blur_halo(img: jax.Array, *, bm: int = 128,
                       interpret: bool = True) -> jax.Array:
    """Blur the interior of an already 2+2-row-halo'd image.

    The co-execution data plane hands each package its row range plus two
    rows of vertical context on either side (zero-filled beyond the full
    image), so this entry consumes the halo directly instead of re-padding:
    ``img`` is (H + 4, W) and the result is the (H, W) interior — the
    halo-aware twin of :func:`gaussian_blur` for split launches.
    """
    H = img.shape[0] - 4
    W = img.shape[1]
    return _blur_blocks(img, H, W, bm, interpret)
