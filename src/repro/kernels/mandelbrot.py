"""Mandelbrot escape-iteration Pallas kernel (paper benchmark: Mandelbrot).

The data-dependent `while_loop` terminates a block as soon as *all* of its
lanes have escaped — this is the TPU rendering of the benchmark's
irregularity: blocks over the fractal interior run the full iteration
budget, background blocks exit after a handful of steps. Package runtimes
therefore vary with data content exactly as the paper's Fig. 1 requires,
which is what the dynamic schedulers exploit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _mandel_kernel(cre_ref, cim_ref, o_ref, *, max_iter: int):
    cre = cre_ref[...]
    cim = cim_ref[...]

    # Mosaic constraints on the loop carry: each vector carry must start
    # from a value derived from the input block (a splat constant such as
    # jnp.zeros_like gets a replicated layout the loop body cannot
    # relayout into), and the alive mask is carried as f32 — a bool carry
    # fails to legalize the loop's yield.
    def cond(st):
        i, _, _, _, alive = st
        return (i < max_iter) & jnp.any(alive > 0.0)

    def body(st):
        i, zr, zi, it, alive = st
        zr2, zi2 = zr * zr, zi * zi
        alive = jnp.where(zr2 + zi2 <= 4.0, alive, 0.0)
        live = alive > 0.0
        zr_n = zr2 - zi2 + cre
        zi_n = 2.0 * zr * zi + cim
        zr = jnp.where(live, zr_n, zr)
        zi = jnp.where(live, zi_n, zi)
        it = it + alive
        return i + 1, zr, zi, it, alive

    zero = cre - cre
    st = (jnp.int32(0), zero, zero, zero, zero + 1.0)
    _, _, _, it, _ = jax.lax.while_loop(cond, body, st)
    o_ref[...] = it


@functools.partial(jax.jit, static_argnames=("max_iter", "bm", "interpret"))
def mandelbrot(cre: jax.Array, cim: jax.Array, *, max_iter: int = 64,
               bm: int = 128, interpret: bool = True) -> jax.Array:
    """Escape iterations (f32) for points cre + i*cim; any equal shapes."""
    shape = cre.shape
    n = cre.size
    lanes = 128
    rows = -(-n // lanes)
    bm = min(bm, rows)
    pr = (-rows) % bm

    def prep(x):
        flat = jnp.pad(x.reshape(-1), (0, rows * lanes - n),
                       constant_values=4.0)  # pad escapes immediately
        return jnp.pad(flat.reshape(rows, lanes), ((0, pr), (0, 0)),
                       constant_values=4.0)

    grid_rows = rows + pr
    out = pl.pallas_call(
        functools.partial(_mandel_kernel, max_iter=max_iter),
        out_shape=jax.ShapeDtypeStruct((grid_rows, lanes), jnp.float32),
        grid=(grid_rows // bm,),
        in_specs=[pl.BlockSpec((bm, lanes), lambda i: (i, 0))] * 2,
        out_specs=pl.BlockSpec((bm, lanes), lambda i: (i, 0)),
        interpret=interpret,
    )(prep(cre), prep(cim))
    return out.reshape(-1)[:n].reshape(shape)
