"""Coexecution Units (paper Fig. 2a).

A *Coexecution Unit* owns one execution resource and a management thread that
talks to the Commander loop. Two substrates implement the same interface:

* ``SimUnit`` — used by the discrete-event simulator: a calibrated relative
  speed plus an irregularity exponent (`alpha`) modeling how much the unit
  suffers on computationally heavy items (branch divergence on the paper's
  iGPU). Reproduces the paper's scheduler dynamics deterministically.
* ``JaxUnit`` — real execution: dispatches jitted package kernels onto a
  ``jax.Device`` asynchronously (JAX's async dispatch stream plays the role
  of the oneAPI DAG) and reports completion when the output buffer is ready.

Package kernels keep the signature ``fn(offset, chunk_inputs...) ->
chunk_out``; *how* the chunks reach the unit (zero-copy USM views vs
staged per-package buffers, padding to size buckets) is decided by the
data plane (:mod:`repro.core.dataplane`), which drives :meth:`JaxUnit.
dispatch`. The unit itself only owns the device and the per-kernel jit
cache.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Any, Callable, Optional, Sequence

import numpy as np

try:  # jax is always present in this repo, but keep the DES importable alone
    import jax
    import jax.numpy as jnp
except ImportError:  # pragma: no cover
    jax = None
    jnp = None


@dataclasses.dataclass
class SimUnit:
    """Discrete-event-simulated device for scheduler evaluation.

    speed   — work-items/second on unit-weight data.
    alpha   — irregularity exponent: cost(item) = weight**alpha / speed.
              alpha > 1 models divergence-sensitive devices (the paper's
              iGPU on Ray/Mandelbrot); alpha = 1 is cost ∝ weight.
    setup_s — one-time init before the unit joins the Commander loop
              (queue/context creation; the paper's initialization phase).
    kind    — energy-model class ("cpu" / "gpu" / "tpu").
    """

    name: str
    kind: str
    speed: float
    alpha: float = 1.0
    setup_s: float = 2e-3

    def package_seconds(self, weights_prefix: Optional[np.ndarray],
                        offset: int, size: int) -> float:
        """Compute time for items [offset, offset+size)."""
        if weights_prefix is None:  # regular kernel: every item costs 1
            return size / self.speed
        w = weights_prefix[offset + size] - weights_prefix[offset]
        return float(w) / self.speed


class JaxUnit:
    """A real Coexecution Unit backed by a jax.Device.

    The engine's management thread drives the unit through the launch's
    data plane (:meth:`~repro.core.dataplane.DataPlane.execute`), which
    stages inputs per the configured memory model and calls
    :meth:`dispatch`; dispatch is asynchronous and completion is detected
    by blocking on the output buffer, mirroring the event-driven
    collection of the paper.
    """

    def __init__(self, name: str, device: "jax.Device", *, kind: str = "cpu",
                 speed_hint: float = 1.0):
        self.name = name
        self.kind = kind
        self.device = device
        self.speed_hint = float(speed_hint)
        self._compiled: dict[Any, Any] = {}
        self._aot: dict[Any, Any] = {}  # guarded-by: _lock
        self._lock = threading.Lock()

    def compiled(self, fn: Callable) -> Any:
        """The unit's cached ``jax.jit`` entry for one kernel body.

        One jit per kernel; distinct chunk shapes cache one executable
        each inside it (the data plane pads packages to power-of-two
        size buckets, bounding compilations to O(log total)).
        Computation placement follows the committed inputs. Locked: one
        unit may be shared by several engines, whose worker threads race
        on first-compile of the same kernel.
        """
        with self._lock:
            got = self._compiled.get(fn)
            if got is None:
                got = jax.jit(fn)
                self._compiled[fn] = got
        return got

    # -- execution ---------------------------------------------------------
    def dispatch(self, fn: Callable, offset: int,
                 args: Sequence[Any]) -> Any:
        """Asynchronously launch ``fn(offset, *args)`` on this unit.

        The args are whatever the launch's data plane staged (host views
        under USM, device-put buffers under BUFFERS). Dispatch runs
        under ``jax.default_device(self.device)`` so *uncommitted* host
        arrays (the USM plane's zero-copy views) still execute on this
        unit's device — committed BUFFERS operands already carry their
        placement. The kernel sees the real offset for index-dependent
        work such as Mandelbrot pixel coordinates. Returns the (not yet
        materialized) output array; the caller blocks on it to observe
        completion.
        """
        with jax.default_device(self.device):
            exe = None
            try:
                key = (fn, tuple((tuple(a.shape), np.dtype(a.dtype).str)
                                 for a in args))
            except (AttributeError, TypeError):
                key = None
            if key is not None:
                with self._lock:
                    exe = self._aot.get(key)
            if exe is not None:
                return exe(jnp.int32(offset), *args)
            return self.compiled(fn)(jnp.int32(offset), *args)

    def prewarm(self, fn: Callable, args: Sequence[Any]) -> None:
        """Ahead-of-time compile ``fn`` for one argument-shape bucket.

        Lowers and compiles the jitted kernel against the bucket's
        shapes/dtypes *without executing it* (safe for kernels whose
        bodies do host callbacks), and parks the executable where
        :meth:`dispatch` finds it — so the first real dispatch of this
        bucket skips XLA compilation and none of it lands in a package's
        timeline. Memoized per ``(kernel, shapes, dtypes)``: later
        launches presenting the same compile bucket skip straight
        through.

        Args:
            fn: the kernel body (same object :meth:`dispatch` receives).
            args: arguments of the bucket's shapes/dtypes; values are
                irrelevant and nothing is computed from them.
        """
        avals = [jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype)
                 for a in args]
        key = (fn, tuple((tuple(v.shape), np.dtype(v.dtype).str)
                         for v in avals))
        with self._lock:
            if key in self._aot:
                return
        with jax.default_device(self.device):
            exe = self.compiled(fn).lower(
                jax.ShapeDtypeStruct((), np.int32), *avals).compile()
        with self._lock:
            self._aot.setdefault(key, exe)
