"""Jit'd public wrappers + the typed co-executable kernels.

Two surfaces live here:

* ``<name>_op(...)`` — jit-friendly wrappers with implementation dispatch
  along the :data:`KERNEL_IMPLS` axis: ``impl="pallas"`` runs the Pallas
  kernel (interpret mode off-TPU), ``impl="xla"`` the jit-compiled jnp
  oracle (the production XLA lowering), ``impl="ref"`` the eager oracle.
  The default is backend-aware (:func:`default_impl`): Pallas on TPU,
  XLA elsewhere — so importing code never pays interpret-mode cost by
  accident.
* the paper's six benchmarks as **typed co-executable kernels**
  (:class:`~repro.core.dataplane.CoexecKernel`): each declares its
  per-argument partition semantics — SPLIT along an axis (with a halo for
  the Gaussian stencil), BROADCAST for whole-array operands (MatMul's
  ``B``, Ray's sphere scene) — and an output slot, and registers in the
  :mod:`repro.api.registry` kernel registry next to the schedulers and
  workloads. Third-party kernels register the same way, without editing
  core; resolve any of them with ``repro.api.build_kernel(name)`` and
  hand the result straight to ``CoexecutorRuntime.launch`` /
  ``CoexecEngine.submit``.

Each registration also carries a demo-input generator
(``repro.api.kernel_demo_inputs``) so the serving benchmarks and the
USM-vs-BUFFERS parity tests can drive every registered kernel without
per-kernel glue. The pre-registry ``package_kernel(name)`` if-chain (and
later its deprecation shim) is gone: the registry is the only entry
point.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dataplane import (ArgRole, ArgSpec, CoexecKernel,
                                  OutputSpec)

from . import ref
from .flash_attention import flash_attention
from .gaussian import gaussian_blur, gaussian_blur_halo
from .linear_attention import linear_attention
from .mandelbrot import mandelbrot
from .matmul import matmul
from .rap import rap
from .raytrace import demo_spheres, raytrace
from .taylor import taylor_sin

#: The implementation-variant axis every wrapper / registered kernel
#: understands. "pallas" = the hand-written Pallas body (interpret mode
#: off-TPU), "xla" = the jit-compiled jnp oracle (the production XLA
#: path), "ref" = the eager jnp oracle (bitwise ground truth).
KERNEL_IMPLS = ("pallas", "xla", "ref")


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def default_impl() -> str:
    """The backend-aware default variant: Pallas on TPU, XLA elsewhere.

    Off-TPU the Pallas bodies only run in interpret mode — orders of
    magnitude slower than the compiled oracle — so nothing should select
    them implicitly.
    """
    return "pallas" if _on_tpu() else "xla"


def resolve_impl(impl: str | None = None) -> str:
    """Canonicalize an impl request to one of :data:`KERNEL_IMPLS`.

    Args:
        impl: ``None`` / ``""`` / ``"auto"`` resolve via
            :func:`default_impl`; otherwise must be a member of
            :data:`KERNEL_IMPLS`.

    Returns:
        The canonical implementation name.

    Raises:
        ValueError: unknown implementation name.
    """
    if impl in (None, "", "auto"):
        return default_impl()
    if impl not in KERNEL_IMPLS:
        raise ValueError(f"unknown kernel impl {impl!r}; choose from "
                         f"{('auto',) + KERNEL_IMPLS}")
    return impl


@functools.lru_cache(maxsize=None)
def _jit_oracle(ref_fn: Callable, kw_items: tuple) -> Callable:
    # one compiled entry per (oracle, static-options) pair — jitting a
    # fresh partial per call would recompile every time
    return jax.jit(functools.partial(ref_fn, **dict(kw_items)))


def _dispatch(pallas_fn: Callable, ref_fn: Callable, impl: str | None,
              *a, **kw):
    impl = resolve_impl(impl)
    if impl == "ref":
        return ref_fn(*a, **kw)
    if impl == "xla":
        return _jit_oracle(ref_fn, tuple(sorted(kw.items())))(*a)
    return pallas_fn(*a, interpret=not _on_tpu(), **kw)


def matmul_op(a, b, *, impl: str | None = None, **kw):
    return _dispatch(matmul, ref.matmul, impl, a, b, **kw)


def gaussian_op(img, *, impl: str | None = None, **kw):
    return _dispatch(gaussian_blur, ref.gaussian_blur, impl, img, **kw)


def taylor_op(x, *, impl: str | None = None, **kw):
    return _dispatch(taylor_sin, ref.taylor_sin, impl, x, **kw)


def mandelbrot_op(cre, cim, *, impl: str | None = None, **kw):
    return _dispatch(mandelbrot, ref.mandelbrot, impl, cre, cim, **kw)


def raytrace_op(dx, dy, dz, spheres, *, impl: str | None = None, **kw):
    return _dispatch(raytrace, ref.raytrace, impl, dx, dy, dz, spheres, **kw)


def rap_op(values, lengths, *, impl: str | None = None, **kw):
    return _dispatch(rap, ref.rap, impl, values, lengths, **kw)


def flash_attention_op(q, k, v, *, impl: str | None = None, **kw):
    return _dispatch(flash_attention, ref.attention, impl, q, k, v, **kw)


def linear_attention_op(q, k, v, log_decay, *, impl: str | None = None,
                        **kw):
    return _dispatch(linear_attention, ref.linear_attention, impl,
                     q, k, v, log_decay, **kw)


# ---------------------------------------------------------------------------
# Typed co-executable kernels (registered; paper Listing-1 benchmarks)
# ---------------------------------------------------------------------------
# Factories are memoized so repeated build_kernel() calls return the same
# CoexecKernel object — the engines' jit caches and fusion keys hash on it.
# Each factory takes the `impl` axis; the public entry resolves "auto"
# before hitting the cache, so build_kernel("taylor") and
# build_kernel("taylor", impl=default_impl()) share one object.

_GAUSS_DEMO_W = 96        # demo image width (rows are the index space)
_MATMUL_DEMO_K = 32       # demo inner dim; B is (K, N2)
_MATMUL_DEMO_N2 = 24
_RAP_DEMO_L = 48          # demo candidate-resource count per row


def _impl_axis(inner: Callable) -> Callable:
    """Wrap a cached factory so its ``impl`` option resolves "auto" first.

    ``inner`` is the ``lru_cache``d builder keyed on the *canonical* impl
    name; resolving before the cache keeps the memoization contract
    (same options -> same kernel object) intact across the auto default.
    """
    @functools.wraps(inner)
    def factory(*, impl: str = "auto", **options) -> CoexecKernel:
        return inner(impl=resolve_impl(impl), **options)
    return factory


@functools.lru_cache(maxsize=None)
def _taylor_kernel_impl(*, impl: str, terms: int = 12) -> CoexecKernel:
    """Taylor-series sin over a split 1-D array (regular, compute-bound)."""
    if impl == "pallas":
        def fn(offset, x, _terms=int(terms)):
            return taylor_sin(x, terms=_terms, interpret=not _on_tpu())
    else:
        def fn(offset, x, _terms=int(terms)):
            return ref.taylor_sin(x, terms=_terms)

    return CoexecKernel("taylor", fn, (ArgSpec("x"),), OutputSpec())


_taylor_kernel = _impl_axis(_taylor_kernel_impl)


def _taylor_inputs(n: int, rng) -> list:
    return [rng.uniform(-2, 2, n).astype(np.float32)]


@functools.lru_cache(maxsize=None)
def _gaussian_kernel_impl(*, impl: str) -> CoexecKernel:
    """Separable 5x5 blur; rows split with a 2-row zero-filled halo.

    The data plane hands each package its row range plus two rows of
    context on either side (zeros beyond the image, as in the
    reference's zero padding), so co-executed output matches
    :func:`repro.kernels.ref.gaussian_blur` on the full image exactly.
    The Pallas variant consumes the same halo'd chunk through
    :func:`~repro.kernels.gaussian.gaussian_blur_halo`, one device op
    whose blocks read their own halo rows.
    """
    if impl == "pallas":
        def fn(offset, img):
            return gaussian_blur_halo(img, interpret=not _on_tpu())
    else:
        def fn(offset, img):
            taps = jnp.asarray(ref.GAUSS_TAPS, dtype=img.dtype)
            rows = img.shape[0] - 4                # drop the 2+2 halo
            vert = sum(taps[d] * img[d:d + rows, :] for d in range(5))
            padded = jnp.pad(vert, ((0, 0), (2, 2)))
            W = vert.shape[1]
            return sum(taps[d] * padded[:, d:d + W] for d in range(5))

    return CoexecKernel("gaussian", fn, (ArgSpec("img", halo=2),),
                        OutputSpec(trailing=lambda ins: (ins[0].shape[1],)))


_gaussian_kernel = _impl_axis(_gaussian_kernel_impl)


def _gaussian_inputs(n: int, rng) -> list:
    return [rng.normal(size=(n, _GAUSS_DEMO_W)).astype(np.float32)]


@functools.lru_cache(maxsize=None)
def _matmul_kernel_impl(*, impl: str) -> CoexecKernel:
    """Row-split MatMul: A splits by rows, B broadcasts whole.

    The broadcast declaration is the protocol's point: the runtime knows
    ``B`` is not indexed by the launch's index space, so the USM plane
    shares it and the BUFFERS plane stages it per package (the paper's
    accessor-per-command-group cost), instead of the old contract that
    silently sliced every input by rows. The Pallas variant runs the
    tiled MXU kernel on each package's row block against the broadcast B.
    """
    if impl == "pallas":
        def fn(offset, a_rows, b):
            return matmul(a_rows, b, interpret=not _on_tpu())
    else:
        def fn(offset, a_rows, b):
            return ref.matmul(a_rows, b)

    return CoexecKernel(
        "matmul", fn,
        (ArgSpec("a"), ArgSpec("b", role=ArgRole.BROADCAST)),
        OutputSpec(trailing=lambda ins: (ins[1].shape[1],)))


_matmul_kernel = _impl_axis(_matmul_kernel_impl)


def _matmul_inputs(n: int, rng) -> list:
    return [rng.normal(size=(n, _MATMUL_DEMO_K)).astype(np.float32),
            rng.normal(size=(_MATMUL_DEMO_K,
                             _MATMUL_DEMO_N2)).astype(np.float32)]


@functools.lru_cache(maxsize=None)
def _mandelbrot_kernel_impl(*, impl: str,
                            max_iter: int = 64) -> CoexecKernel:
    """Escape iterations over split coordinate arrays (irregular)."""
    if impl == "pallas":
        def fn(offset, cre, cim, _it=int(max_iter)):
            return mandelbrot(cre, cim, max_iter=_it,
                              interpret=not _on_tpu())
    else:
        def fn(offset, cre, cim, _it=int(max_iter)):
            return ref.mandelbrot(cre, cim, max_iter=_it)

    return CoexecKernel("mandelbrot", fn,
                        (ArgSpec("cre"), ArgSpec("cim")), OutputSpec())


_mandelbrot_kernel = _impl_axis(_mandelbrot_kernel_impl)


def _mandelbrot_inputs(n: int, rng) -> list:
    return [rng.uniform(-2.2, 0.8, n).astype(np.float32),
            rng.uniform(-1.4, 1.4, n).astype(np.float32)]


@functools.lru_cache(maxsize=None)
def _ray_kernel_impl(*, impl: str) -> CoexecKernel:
    """Ray tracing: split ray directions, broadcast sphere scene.

    The scene is a trailing BROADCAST argument with a default (the demo
    scene), so both ``launch(n, kernel, [dx, dy, dz])`` and an explicit
    ``[dx, dy, dz, spheres]`` work.
    """
    if impl == "pallas":
        def fn(offset, dx, dy, dz, spheres):
            return raytrace(dx, dy, dz, spheres,
                            interpret=not _on_tpu())
    else:
        def fn(offset, dx, dy, dz, spheres):
            return ref.raytrace(dx, dy, dz, spheres)

    return CoexecKernel(
        "ray", fn,
        (ArgSpec("dx"), ArgSpec("dy"), ArgSpec("dz"),
         ArgSpec("spheres", role=ArgRole.BROADCAST,
                 default=lambda: np.asarray(demo_spheres()))),
        OutputSpec())


_ray_kernel = _impl_axis(_ray_kernel_impl)


def _ray_inputs(n: int, rng) -> list:
    dx, dy = rng.uniform(-0.4, 0.4, (2, n)).astype(np.float32)
    dz = np.sqrt(np.maximum(1 - dx**2 - dy**2, 0.5)).astype(np.float32)
    return [dx, dy, dz]


@functools.lru_cache(maxsize=None)
def _rap_kernel_impl(*, impl: str) -> CoexecKernel:
    """Resource-allocation rows: values and lengths split together."""
    if impl == "pallas":
        def fn(offset, values, lengths):
            return rap(values, lengths, interpret=not _on_tpu())
    else:
        def fn(offset, values, lengths):
            return ref.rap(values, lengths)

    return CoexecKernel("rap", fn,
                        (ArgSpec("values"), ArgSpec("lengths")),
                        OutputSpec())


_rap_kernel = _impl_axis(_rap_kernel_impl)


def _rap_inputs(n: int, rng) -> list:
    return [rng.normal(size=(n, _RAP_DEMO_L)).astype(np.float32),
            rng.integers(0, _RAP_DEMO_L, size=n).astype(np.int32)]


def _register_builtin_kernels() -> None:
    """Idempotently register the paper's six kernels (import side)."""
    from repro.api.registry import register_kernel

    register_kernel("taylor", _taylor_kernel, fields=("terms", "impl"),
                    demo_inputs=_taylor_inputs, overwrite=True)
    register_kernel("gaussian", _gaussian_kernel, fields=("impl",),
                    demo_inputs=_gaussian_inputs, overwrite=True)
    register_kernel("matmul", _matmul_kernel, fields=("impl",),
                    demo_inputs=_matmul_inputs, overwrite=True)
    register_kernel("mandelbrot", _mandelbrot_kernel,
                    fields=("max_iter", "impl"),
                    demo_inputs=_mandelbrot_inputs, overwrite=True)
    register_kernel("ray", _ray_kernel, fields=("impl",),
                    demo_inputs=_ray_inputs, overwrite=True)
    register_kernel("rap", _rap_kernel, fields=("impl",),
                    demo_inputs=_rap_inputs, overwrite=True)


_register_builtin_kernels()
