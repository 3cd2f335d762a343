"""Chunked gated linear attention / SSD Pallas kernel.

One kernel serves two sequence mixers of the model zoo:
  * Mamba-2 SSD (zamba2-7b): scalar per-step decay a_t = exp(-softplus(dt)·A)
  * mLSTM (xlstm-1.3b): forget-gate decay (the exp-input-gate stabilizer is
    applied by the model layer on top of the kernel's linear recurrence)

Recurrence: S_t = d_t · S_{t-1} + k_tᵀ v_t ;  o_t = q_t · S_t, with
d_t = exp(log_decay_t). The chunked form processes C timesteps per grid
step: an intra-chunk causal part (masked (C×C) matmul on the MXU) plus an
inter-chunk part through the carried state S — which lives in VMEM scratch
and persists across the sequential chunk axis of the TPU grid. This is the
textbook TPU adaptation of GPU chunked-scan kernels: the sequential-grid
guarantee replaces the inter-block atomics/barriers a CUDA implementation
needs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gla_kernel(q_ref, k_ref, v_ref, ld_ref, o_ref, state_ref, *,
                chunk: int):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    q = q_ref[0].astype(jnp.float32)          # (C, Dk)
    k = k_ref[0].astype(jnp.float32)          # (C, Dk)
    v = v_ref[0].astype(jnp.float32)          # (C, Dv)
    ld = ld_ref[0].astype(jnp.float32)        # (1, C) log decays

    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # inclusive cumsum (1, C) as a product with the upper-triangular ones
    # matrix: Mosaic has no cumsum lowering; HIGHEST keeps it exact in f32
    cum = jax.lax.dot_general(ld, (row <= col).astype(jnp.float32),
                              (((1,), (0,)), ((), ())),
                              precision=jax.lax.Precision.HIGHEST,
                              preferred_element_type=jnp.float32)
    total = cum[0, chunk - 1]                 # log decay over whole chunk

    # intra-chunk: A_ij = q_i·k_j · exp(cum_i - cum_j) for i >= j
    # (each key k_j is decayed by every step after j up to i, inclusive of
    #  step i's decay because S is updated before the readout)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (C, C)
    ci = jnp.transpose(cum)                   # (C, 1)
    gamma = jnp.exp(ci - cum)                 # (C, C) = exp(cum_i - cum_j)
    a = jnp.where(row >= col, s * gamma, 0.0)
    intra = jax.lax.dot_general(a, v, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)

    # inter-chunk: queries read the carried state decayed to their step
    q_dec = q * jnp.exp(ci)                   # (C, Dk) · exp(cum_i)
    inter = jax.lax.dot_general(q_dec, state_ref[...],
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)

    o_ref[0] = (intra + inter).astype(o_ref.dtype)

    # state update: S ← exp(total)·S + Σ_j exp(total - cum_j) k_jᵀ v_j
    k_dec = k * jnp.exp(total - cum).reshape(chunk, 1)
    state_ref[...] = jnp.exp(total) * state_ref[...] + jax.lax.dot_general(
        k_dec, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def linear_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     log_decay: jax.Array, *, chunk: int = 128,
                     interpret: bool = True) -> jax.Array:
    """q, k: (BH, T, Dk); v: (BH, T, Dv); log_decay: (BH, T) (entries ≤ 0).

    Returns (BH, T, Dv). T is padded to a chunk multiple (padded steps use
    decay 1 and zero k/v, which leaves the recurrence untouched).
    """
    BH, T, Dk = q.shape
    Dv = v.shape[-1]
    chunk = min(chunk, T)
    pt = (-T) % chunk
    if pt:
        q = jnp.pad(q, ((0, 0), (0, pt), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pt), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pt), (0, 0)))
        log_decay = jnp.pad(log_decay, ((0, 0), (0, pt)))
    Tp = T + pt
    # (BH, 1, Tp) with (1, 1, chunk) blocks: the block's last two dims are
    # then 1 (the array's own extent) and chunk (a multiple of 128, or all
    # of Tp), which is the tiling Mosaic accepts on the TPU
    ld = log_decay.reshape(BH, 1, Tp)

    out = pl.pallas_call(
        functools.partial(_gla_kernel, chunk=chunk),
        out_shape=jax.ShapeDtypeStruct((BH, Tp, Dv), q.dtype),
        grid=(BH, Tp // chunk),
        in_specs=[
            pl.BlockSpec((1, chunk, Dk), lambda h, c: (h, c, 0)),
            pl.BlockSpec((1, chunk, Dk), lambda h, c: (h, c, 0)),
            pl.BlockSpec((1, chunk, Dv), lambda h, c: (h, c, 0)),
            pl.BlockSpec((1, 1, chunk), lambda h, c: (h, 0, c)),
        ],
        out_specs=pl.BlockSpec((1, chunk, Dv), lambda h, c: (h, c, 0)),
        scratch_shapes=[pltpu.VMEM((Dk, Dv), jnp.float32)],
        interpret=interpret,
    )(q, k, v, ld)
    return out[:, :T]
