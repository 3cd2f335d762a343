"""Spans the real engine emits into the JAX profiler's trace.

Each span is a ``jax.profiler.TraceAnnotation``: it lands on the
profiler's host plane, on the same timeline as the device's "XLA Ops"
line, under its bare name, with its keyword ids as event stats. With the
profiler off a span costs about a microsecond, so the engine always
emits them. Only the real engine's threads emit spans; the
discrete-event simulator runs in virtual time and emits none.

The ids: ``launch`` is the launch id (``LaunchHandle.launch_id``; a
fused batch's own id for its packages), ``unit`` the Coexecution Unit's
index, ``pkg`` the package's ``seq``; ``coexec.submit`` carries the
``tenant`` and the launch's ``items``; ``coexec.fill``, one for each
split argument of each piece, the piece's ``rows`` (items along the
split axis) and the argument's ``halo``.
"""
from __future__ import annotations

import jax

__all__ = ["SPANS", "span"]

#: every span the program emits, from the entry down to the data plane
SPANS = ("coexec.submit", "coexec.lock", "coexec.pull", "coexec.park",
         "coexec.stage", "coexec.fill", "coexec.issue", "coexec.collect",
         "coexec.retire")


def span(name: str, **ids) -> jax.profiler.TraceAnnotation:
    """A context manager that records ``name`` with ``ids`` as a span."""
    return jax.profiler.TraceAnnotation(name, **ids)
