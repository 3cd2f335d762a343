"""Gaussian: inputs, plain reference, comparison, operations and bytes.

The input is one ``(items, width)`` image of standard-normal pixels; the
seed and the input set's index draw it. A launch's index space is its
rows, so the data plane splits the image by rows and hands each piece
two rows of context above and below.

The reference blurs the whole image with the 5-tap binomial filter
``[1, 4, 6, 4, 1] / 16``, separably (columns, then rows), with zero
padding at the image's edges, in plain ``jax.numpy``: written here and
not imported from the program. The taps are summed in order, outer tap
first, as the program's kernel sums them.
"""
from __future__ import annotations

import re

import numpy as np

#: the program's Pallas kernel in the device trace's "XLA Ops" line: one
#: op per piece, named after the data plane's entry to the kernel
EVENT = re.compile(r"^%gaussian_blur_halo(\.\d+)? = ")

TAPS = np.array([1.0, 4.0, 6.0, 4.0, 1.0]) / 16.0


def kernel_options(cfg: dict) -> dict:
    """Options the program's registered kernel is built with."""
    return {}


def inputs(cfg: dict, seed: int, k: int) -> list[np.ndarray]:
    """Input set ``k`` of a run seeded with ``seed``: ``[img]``."""
    rng = np.random.default_rng([seed & (2**64 - 1), k])
    return [rng.standard_normal((int(cfg["items"]), int(cfg["width"])),
                                dtype=np.float32)]


def reference(cfg: dict, data: list[np.ndarray], dtype: str = "float32"
              ) -> np.ndarray:
    """The blurred image (f32), computed in ``dtype``."""
    import jax
    import jax.numpy as jnp

    def blur(img):
        img = img.astype(dtype)
        taps = jnp.asarray(TAPS, dtype)
        rows, width = img.shape
        pad = jnp.pad(img, ((2, 2), (0, 0)))
        vert = sum(taps[d] * pad[d:d + rows] for d in range(5))
        pad = jnp.pad(vert, ((0, 0), (2, 2)))
        return sum(taps[d] * pad[:, d:d + width]
                   for d in range(5)).astype(jnp.float32)

    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(blur)(data[0]))


def compare(cfg: dict, got: np.ndarray, want: np.ndarray) -> dict:
    """``max_abs_err``: the largest absolute difference of a pixel."""
    if got.shape != want.shape:
        return {"max_abs_err": float("inf")}
    return {"max_abs_err": float(np.max(np.abs(got - want)))}


def ops(cfg: dict, data: list[np.ndarray], want: np.ndarray) -> float:
    """Floating-point operations one launch needs: ``ops_per_pixel``
    (5 multiplies and 4 adds in each pass) for every output pixel."""
    return float(cfg["ops_per_pixel"]) * want.size


def nbytes(cfg: dict, data: list[np.ndarray], want: np.ndarray) -> float:
    """Bytes one launch must move: its image and its output, once."""
    return float(data[0].nbytes + want.nbytes)
