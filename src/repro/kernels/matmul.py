"""Tiled MXU matmul Pallas kernel (paper benchmark: MatMul).

Grid (M/bm, N/bn, K/bk) with K innermost — TPU grids execute the last axis
sequentially, so the f32 VMEM scratch accumulator carries across K steps.
Block shapes are MXU-aligned (multiples of 128 in the contracting/lane
dims). This is the TPU-native re-think of the AMD APP SDK OpenCL kernel:
local-memory tiles become explicit VMEM BlockSpecs and the inner product is
a single 128x128 systolic pass per block pair.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # on the TPU a DEFAULT-precision f32 dot takes one bf16 pass (about 1%
    # error at K=4864), so f32 operands ask for the f32 contraction;
    # Mosaic accepts HIGHEST only for f32
    f32 = a_ref.dtype == jnp.float32
    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            precision=jax.lax.Precision.HIGHEST if f32
                            else None,
                            preferred_element_type=jnp.float32)

    @pl.when(ik == pl.num_programs(2) - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def matmul(a: jax.Array, b: jax.Array, *, bm: int = 256, bn: int = 256,
           bk: int = 512, interpret: bool = True) -> jax.Array:
    """C = A @ B. a: (M, K), b: (K, N); M/N/K padded to block multiples."""
    M, K = a.shape
    K2, N = b.shape
    assert K == K2, (a.shape, b.shape)
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)

    pm, pn, pk = (-M) % bm, (-N) % bn, (-K) % bk
    if pm or pk:
        a = jnp.pad(a, ((0, pm), (0, pk)))
    if pk or pn:
        b = jnp.pad(b, ((0, pk), (0, pn)))
    Mp, Kp = a.shape
    _, Np = b.shape

    out = pl.pallas_call(
        _matmul_kernel,
        out_shape=jax.ShapeDtypeStruct((Mp, Np), a.dtype),
        grid=(Mp // bm, Np // bn, Kp // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(a, b)
    return out[:M, :N]
