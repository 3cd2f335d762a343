"""Typed kernel protocol + the real engine's data plane (paper §3.1).

The paper's headline observation is that co-execution gets *cheaper* under
unified shared memory: with USM every Coexecution Unit reads from and
writes into one logical allocation, so result collection is a no-op
(Fig. 2b), whereas per-package Buffers pay an explicit staging copy in and
a copy-back out for every package. Until this module, that distinction
lived only in the DES cost model — the real engine always staged the same
way and merely *labelled* launches USM or BUFFERS.

Two pieces make the distinction real:

* **`CoexecKernel`** — the typed kernel ABI. A kernel declares its
  per-argument partition semantics instead of being a positional closure:
  each argument is either ``SPLIT`` (sliced along a declared axis by the
  package range, optionally with a zero-filled ``halo`` for stencils) or
  ``BROADCAST`` (every unit sees the whole array — MatMul's ``B`` operand,
  Ray's sphere scene), plus an output slot describing dtype and trailing
  shape. This is EngineCL's kernel/data API (arXiv:1805.02755) crossed
  with Celerity-style per-argument access semantics (arXiv:2505.06022):
  the runtime, not the kernel author, decides data movement.
* **Data planes** — one strategy object per
  :class:`~repro.core.memory.MemoryModel`, selected by the engine from its
  spec. :class:`UsmDataPlane` hands units zero-copy host views of the
  shared arrays and lands results directly in the shared output container;
  :class:`BuffersDataPlane` stages each package's slices with
  ``jax.device_put``, dispatches on the staged buffers, and copies results
  back through a per-package buffer before merging. Both are instrumented:
  every launch carries :class:`DataPlaneCounters` (dispatches, device
  executions, H2D/D2H staging copies and bytes) surfaced in
  :class:`~repro.core.engine.LaunchStats`, so ``MemorySpec`` finally
  selects observable behavior end-to-end.

Both planes run a package as at most two exact-cover *pieces*
(:func:`pieces`), each padded to a power-of-two compile bucket that
:meth:`DataPlane.prewarm` has already built, so the bucket pad of a
package no longer doubles what crosses between host and device.

On this CPU-only substrate "device memory" and host memory coincide, so
the USM plane's zero-copy claim is literal (numpy views over the shared
allocation) while the BUFFERS plane really performs the extra copies the
paper charges that model for.
"""
from __future__ import annotations

import dataclasses
import enum
import logging
import threading
import time
from typing import Any, Callable, Optional, Sequence

import numpy as np

from . import tracing
from .memory import MemoryModel

_log = logging.getLogger(__name__)

try:  # jax is always present in this repo, but keep the DES importable alone
    import jax
    import jax.numpy as jnp
except ImportError:  # pragma: no cover
    jax = None
    jnp = None

__all__ = [
    "ArgRole", "ArgSpec", "OutputSpec", "CoexecKernel", "as_coexec_kernel",
    "DataPlaneCounters", "LaunchPlan", "DataPlane", "UsmDataPlane",
    "BuffersDataPlane", "make_plane", "pieces",
]


class ArgRole(enum.Enum):
    """How the data plane moves one kernel argument (per-argument access)."""

    SPLIT = "split"
    BROADCAST = "broadcast"


@dataclasses.dataclass(frozen=True)
class ArgSpec:
    """Partition semantics of one kernel argument.

    Attributes:
        name: argument name (documentation + error messages).
        role: ``SPLIT`` — sliced to the package range along ``axis``;
            ``BROADCAST`` — the whole array reaches every unit.
        axis: the split axis (``SPLIT`` only).
        halo: extra items on both sides of a split slice, zero-filled
            outside the index space (stencil kernels; ``SPLIT`` only).
        default: zero-arg factory for an argument the caller may omit
            (``BROADCAST`` only — e.g. Ray's demo sphere scene).
    """

    name: str
    role: ArgRole = ArgRole.SPLIT
    axis: int = 0
    halo: int = 0
    default: Optional[Callable[[], np.ndarray]] = None

    def __post_init__(self) -> None:
        if self.halo < 0:
            raise ValueError(f"halo must be >= 0, got {self.halo}")
        if self.role is ArgRole.BROADCAST and self.halo:
            raise ValueError(f"arg {self.name!r}: halo is a SPLIT property")
        if self.role is ArgRole.SPLIT and self.default is not None:
            raise ValueError(
                f"arg {self.name!r}: defaults are for BROADCAST args "
                f"(split args define the index space)")


@dataclasses.dataclass(frozen=True)
class OutputSpec:
    """Output slot of a kernel: dtype + trailing shape past the index axis.

    Attributes:
        dtype: numpy dtype of the output container.
        trailing: trailing dims after the split axis — a literal tuple, or
            a callable ``fn(inputs) -> tuple`` for input-dependent shapes
            (MatMul's ``(B.shape[1],)``).
    """

    dtype: Any = np.float32
    trailing: Any = ()

    def trailing_shape(self, inputs: Sequence[np.ndarray]) -> tuple:
        """Resolve the trailing dims for concrete inputs.

        Args:
            inputs: the launch's (bound) input arrays.

        Returns:
            The trailing shape tuple.
        """
        if callable(self.trailing):
            return tuple(self.trailing(inputs))
        return tuple(self.trailing)


@dataclasses.dataclass(frozen=True)
class CoexecKernel:
    """A co-executable kernel: compute body + declared data semantics.

    The compute body keeps the paper's package signature
    ``fn(offset, *chunks) -> chunk_out`` (offset is the package's global
    start, for index-dependent kernels), but the *chunks* are now produced
    by the data plane according to :attr:`args` instead of being uniform
    axis-0 slices: split args arrive as package slices (plus halo),
    broadcast args arrive whole.

    Instances are hashable (the engine's jit cache and fusion coalescing
    key on them) and callable with the legacy package signature, so a
    ``CoexecKernel`` drops in anywhere a positional closure was accepted.
    """

    name: str
    fn: Callable
    args: tuple[ArgSpec, ...]
    out: OutputSpec = OutputSpec()

    @property
    def all_split(self) -> bool:
        """True when every arg is a plain axis-0 split with no halo."""
        return all(a.role is ArgRole.SPLIT and a.axis == 0 and a.halo == 0
                   for a in self.args)

    def bind(self, inputs: Sequence[np.ndarray]) -> list:
        """Fill omitted trailing defaults and return the full input list.

        Args:
            inputs: caller-supplied arrays, shortest-prefix order.

        Returns:
            One array per declared argument.

        Raises:
            ValueError: wrong argument count (missing args without a
                default, or extras).
        """
        bound = list(inputs)
        for spec in self.args[len(bound):]:
            if spec.default is None:
                raise ValueError(
                    f"kernel {self.name!r} takes {len(self.args)} args "
                    f"({', '.join(a.name for a in self.args)}); "
                    f"got {len(inputs)}")
            bound.append(np.asarray(spec.default()))
        if len(bound) > len(self.args):
            raise ValueError(
                f"kernel {self.name!r} takes {len(self.args)} args "
                f"({', '.join(a.name for a in self.args)}); "
                f"got {len(inputs)}")
        return bound

    def alloc_out(self, total: int,
                  inputs: Sequence[np.ndarray]) -> np.ndarray:
        """Allocate the host output container for a launch.

        Args:
            total: launch index-space size.
            inputs: the launch's input arrays (for input-dependent
                trailing shapes).

        Returns:
            A zeroed ``(total, *trailing)`` array of the declared dtype.
        """
        trailing = self.out.trailing_shape(self.bind(inputs))
        return np.zeros((total, *trailing), dtype=self.out.dtype)

    def __call__(self, offset, *chunks):
        """Legacy package-signature call: ``kernel(offset, *chunks)``."""
        filled = list(chunks)
        for spec in self.args[len(filled):]:
            if spec.default is None:
                break
            filled.append(np.asarray(spec.default()))
        return self.fn(offset, *filled)


def as_coexec_kernel(fn: Callable, num_args: int) -> CoexecKernel:
    """Wrap a positional package closure in the typed protocol.

    The compatibility adapter for pre-protocol kernels: every argument is
    treated as a plain axis-0 split, which is exactly what the engine did
    for all inputs before per-argument semantics existed.

    Args:
        fn: legacy package kernel ``fn(offset, *chunks) -> chunk_out``.
        num_args: how many input arrays the kernel takes.

    Returns:
        An equivalent :class:`CoexecKernel` with all-``SPLIT`` args.
    """
    if isinstance(fn, CoexecKernel):
        return fn
    args = tuple(ArgSpec(f"arg{i}") for i in range(num_args))
    return CoexecKernel(getattr(fn, "__name__", "kernel"), fn, args)


# ---------------------------------------------------------------------------
# Instrumentation
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class DataPlaneCounters:
    """Copy/dispatch accounting of one launch (or one simulated run).

    Attributes:
        dispatches: packages issued to the units, one per package.
        pieces: device executions those packages ran as (see
            :func:`pieces`); equals ``dispatches`` when no package split.
        h2d_copies: explicit host→device staging copies (``device_put``
            of a piece's slice, or of a package's broadcast operand).
            Zero under USM.
        h2d_bytes: bytes moved by those staging copies.
        d2h_copies: explicit device→host copy-backs through a per-piece
            buffer before the merge. Zero under USM (results land in the
            shared container directly).
        d2h_bytes: bytes moved by those copy-backs.
        halo_bytes: the part of ``h2d_bytes`` that is halo rows, the
            context a stencil's split slice carries on both sides (zero
            for kernels with no halo, and under USM).
    """

    dispatches: int = 0
    pieces: int = 0
    h2d_copies: int = 0
    h2d_bytes: int = 0
    d2h_copies: int = 0
    d2h_bytes: int = 0
    halo_bytes: int = 0

    @property
    def staging_copies(self) -> int:
        """Total explicit staging copies (H2D + D2H) this launch paid."""
        return self.h2d_copies + self.d2h_copies

    def snapshot(self) -> "DataPlaneCounters":
        """An independent copy (for freezing into launch stats)."""
        return dataclasses.replace(self)

    def split(self, n: int) -> list["DataPlaneCounters"]:
        """Divide these counters into ``n`` shares that sum to the whole.

        Used when a fused batch's shared accounting is attributed to its
        member launches: each member gets an even integer share (the
        division remainder lands on the first members), so summing
        member stats never overcounts the batch's real copies/dispatches.

        Args:
            n: number of shares (the fused member count).

        Returns:
            ``n`` counter objects whose fields sum to this object's.
        """
        shares = [DataPlaneCounters() for _ in range(n)]
        for field in dataclasses.fields(self):
            total = getattr(self, field.name)
            base, rem = divmod(int(total), n)
            for i, share in enumerate(shares):
                setattr(share, field.name, base + (1 if i < rem else 0))
        return shares

    def to_dict(self) -> dict:
        """Plain-dict form for JSON benchmark artifacts."""
        return dataclasses.asdict(self)


def _item_bytes(arr, axis: int) -> int:
    """Bytes of one index along ``axis`` of ``arr``."""
    shape = np.shape(arr)
    return np.dtype(arr.dtype).itemsize * int(
        np.prod(shape[:axis] + shape[axis + 1:]))


class LaunchPlan:
    """Per-launch data-plane state: bound kernel, arrays, counters.

    Built once per submit by :meth:`DataPlane.plan`; worker threads share
    it (counter updates are lock-protected, the arrays are only read and
    the output container is written in disjoint package ranges).
    ``launch_id`` is the owning launch's id, set by the engine once the
    launch has one, for the data plane's spans. ``item_bytes`` is what
    one index of the split arguments and the output holds, and ``least``
    the smallest bucket :meth:`DataPlane.prewarm` built; :func:`pieces`
    reads both.
    """

    __slots__ = ("kernel", "inputs", "out", "total", "counters",
                 "launch_id", "item_bytes", "least", "_lock")

    def __init__(self, kernel: CoexecKernel, inputs: list, out: np.ndarray,
                 total: int):
        self.kernel = kernel
        self.inputs = inputs
        self.out = out
        self.total = int(total)
        self.counters = DataPlaneCounters()
        self.launch_id = -1
        self.item_bytes = _item_bytes(out, 0) + sum(
            _item_bytes(arr, spec.axis)
            for spec, arr in zip(kernel.args, inputs)
            if spec.role is ArgRole.SPLIT)
        self.least = 1
        self._lock = threading.Lock()

    def pieces(self, pkg) -> list[tuple[int, int, int]]:
        """:func:`pieces` of one package of this launch."""
        return pieces(pkg.size, self.item_bytes, self.least)

    def add(self, **deltas: int) -> None:
        """Atomically bump counter fields by the given deltas."""
        with self._lock:
            for key, delta in deltas.items():
                setattr(self.counters, key, getattr(self.counters, key)
                        + int(delta))


# ---------------------------------------------------------------------------
# Data planes
# ---------------------------------------------------------------------------

def _bucket(size: int) -> int:
    """Next power of two — bounds jit compilations to O(log total)."""
    b = 1
    while b < size:
        b <<= 1
    return b


#: Pad bytes a split has to save before it pays for one more dispatch,
#: one more H2D copy and one more D2H copy. Measured on one TPU v5e with
#: ``scripts/transfer_probe.py --packages``: alone, a dispatch takes
#: 0.55-0.62 ms, an H2D copy 0.64-0.82 ms + bytes at 6.3-6.6 GB/s and a
#: D2H copy 0.06-0.48 ms + bytes at 5.6-6.3 GB/s; inside a package the
#: copies' round trips overlap, and a second piece that leaves out
#: almost no pad costs 0.65-0.95 ms. Each pad byte left out saves about
#: 0.5 ns: its host zero-fill, its copy, and the kernel's work on it.
#: 0.8 ms / 0.5 ns = 1.6 MB, where the measured package times cross.
_MIN_SPLIT_BYTES = 1_600_000


def pieces(size: int, bytes_per_item: float,
           least: int = 1) -> list[tuple[int, int, int]]:
    """Exact-cover pieces of one package, each on a power-of-two bucket.

    A power-of-two ``size`` is one piece. Otherwise the pieces are the
    largest power of two ``2**a <= size`` and the rest in its own
    bucket, provided that saves at least :data:`_MIN_SPLIT_BYTES` of
    pad against the whole package's bucket; else ``size`` is one piece
    in ``_bucket(size)``. No bucket is below ``least`` or above
    ``max(_bucket(size), least)``, so every one is a bucket that the
    launch's :meth:`DataPlane.prewarm` builds.

    Args:
        size: items of the package.
        bytes_per_item: bytes one item moves: the split arguments' and
            the output's (``LaunchPlan.item_bytes``).
        least: smallest bucket a piece may take.

    Returns:
        ``(start, piece_size, bucket)`` per piece, ``start`` relative to
        the package's offset, in order.
    """
    whole = _bucket(size)
    top = whole if whole == size else whole >> 1
    if top == size or top < least:
        return [(0, size, max(whole, least))]
    rest = size - top
    tail = max(_bucket(rest), least)
    saved = (whole - top - tail) * bytes_per_item
    if saved <= 0 or saved < _MIN_SPLIT_BYTES:
        return [(0, size, whole)]
    return [(0, top, top), (top, rest, tail)]


def _split_view(arr: np.ndarray, spec: ArgSpec, offset: int, size: int,
                total: int) -> np.ndarray:
    """The package slice of one split arg, halo zero-filled at the edges."""
    lo = offset - spec.halo
    hi = offset + size + spec.halo
    lo_pad, hi_pad = max(0, -lo), max(0, hi - total)
    index = [slice(None)] * arr.ndim
    index[spec.axis] = slice(max(lo, 0), min(hi, total))
    view = arr[tuple(index)]
    if lo_pad or hi_pad:
        pad = [(0, 0)] * arr.ndim
        pad[spec.axis] = (lo_pad, hi_pad)
        view = np.pad(view, pad)
    return view


def _fill_split(buf: np.ndarray, arr: np.ndarray, spec: ArgSpec,
                offset: int, size: int, total: int) -> None:
    """Assemble one split chunk in place in a reused staging buffer.

    Writes exactly the values :func:`_piece_chunks` would produce for
    the same piece — interior slice, zero-filled halo at the edges,
    zero bucket pad — into ``buf`` (whose split-axis extent must already
    be ``bucket + 2*halo``), so the BUFFERS plane's staged operands stay
    bitwise identical to the USM plane's without a fresh pad allocation
    per piece. Only what the slice does not overwrite is zeroed.
    """
    lo = offset - spec.halo
    hi = offset + size + spec.halo
    lo_pad = max(0, -lo)
    index = [slice(None)] * arr.ndim
    index[spec.axis] = slice(max(lo, 0), min(hi, total))
    view = arr[tuple(index)]
    end = lo_pad + view.shape[spec.axis]
    dst = [slice(None)] * buf.ndim
    for part in (slice(0, lo_pad), slice(end, None)):
        dst[spec.axis] = part
        buf[tuple(dst)] = 0
    dst[spec.axis] = slice(lo_pad, end)
    buf[tuple(dst)] = view


def _piece_chunks(plan: LaunchPlan, offset: int, size: int, bucket: int):
    """Yield ``(spec, chunk)`` per argument for one piece.

    Split args are sliced to the piece's range (plus halo) and
    zero-padded up to the piece's power-of-two bucket; broadcast args
    pass through whole. Both data planes cut the same pieces on the same
    buckets — compile-shape management (bounding XLA recompilation),
    which is what makes USM-vs-BUFFERS results bitwise identical (the
    same executable runs on the same values).
    """
    for spec, arr in zip(plan.kernel.args, plan.inputs):
        if spec.role is ArgRole.SPLIT:
            chunk = _split_view(arr, spec, offset, size, plan.total)
            if bucket > size:
                pad = [(0, 0)] * chunk.ndim
                pad[spec.axis] = (0, bucket - size)
                chunk = np.pad(chunk, pad)
        else:
            chunk = arr
        yield spec, chunk


class DataPlane:
    """Data-movement strategy for one memory model (template class).

    Subclasses implement :meth:`_stage` (how a package's pieces' inputs
    reach the unit) and :meth:`_collect` (how their results land in the
    launch's output container); :meth:`execute` runs the shared dispatch
    protocol and timestamps the package. A package is one pull, one
    retire and one set of stamps however many pieces it runs as.
    """

    model: MemoryModel

    def plan(self, kernel: CoexecKernel, inputs: Sequence[np.ndarray],
             out: np.ndarray, total: int) -> LaunchPlan:
        """Bind a launch's arrays to the kernel's declared arguments.

        Args:
            kernel: the typed kernel being launched.
            inputs: caller-supplied input arrays (defaults are filled).
            out: host output container (written along axis 0).
            total: launch index-space size.

        Returns:
            The launch's :class:`LaunchPlan`.

        Raises:
            ValueError: wrong argument count, or a split argument whose
                extent along its axis does not match ``total``.
        """
        bound = kernel.bind(inputs)
        for spec, arr in zip(kernel.args, bound):
            if spec.role is not ArgRole.SPLIT:
                continue
            extent = int(np.asarray(arr).shape[spec.axis])
            if extent != total:
                raise ValueError(
                    f"kernel {kernel.name!r} arg {spec.name!r} is SPLIT "
                    f"along axis {spec.axis} with extent {extent}, but the "
                    f"launch index space is {total}")
        return LaunchPlan(kernel, bound, out, total)

    def execute(self, unit, plan: LaunchPlan, pkg) -> None:
        """Run one package end to end on `unit` and commit its output.

        The serial (``pipeline_depth=1``) composition of the three
        pipeline phases: :meth:`stage` the inputs, :meth:`issue` the
        kernel, :meth:`complete` the result. Sets ``pkg.t_launch`` /
        ``pkg.t_complete`` / ``pkg.t_collected`` and updates the plan's
        counters; the caller sets ``pkg.t_issue``.

        Args:
            unit: the :class:`~repro.core.units.JaxUnit` executing it.
            plan: the launch's data-plane state.
            pkg: the :class:`~repro.core.package.Package` to run.
        """
        args = self.stage(unit, plan, pkg)
        out_dev = self.issue(unit, plan, pkg, args)
        self.complete(unit, plan, pkg, out_dev)

    def stage(self, unit, plan: LaunchPlan, pkg) -> list:
        """Phase 1 — materialize the package's inputs for ``unit``.

        Pure host-side work (slicing, padding, ``device_put`` under
        BUFFERS); safe to run while an earlier package of the same unit
        is still computing on the device.

        Args:
            unit: the unit the package will run on.
            plan: the launch's data-plane state.
            pkg: the package whose inputs to materialize.

        Returns:
            ``(offset, size, args)`` per piece, ``offset`` global, for
            :meth:`issue`.
        """
        with tracing.span("coexec.stage", launch=plan.launch_id,
                          unit=pkg.unit, pkg=pkg.seq):
            return self._stage(unit, plan, pkg)

    def issue(self, unit, plan: LaunchPlan, pkg, staged: list) -> list:
        """Phase 2 — dispatch the package's pieces asynchronously.

        Stamps ``pkg.t_launch`` and counts one dispatch and its pieces,
        but does *not* wait for the device: the returned handles are
        un-materialized device values whose completion :meth:`complete`
        later awaits, so the caller may overlap further staging with the
        compute. Each piece runs at its own global offset.

        Args:
            unit: the executing unit.
            plan: the launch's data-plane state.
            pkg: the package being dispatched.
            staged: the pieces from :meth:`stage`.

        Returns:
            ``(offset, size, out_dev)`` per piece, in flight.
        """
        plan.add(dispatches=1, pieces=len(staged))
        pkg.t_launch = time.perf_counter()
        outs = []
        with tracing.span("coexec.issue", launch=plan.launch_id,
                          unit=pkg.unit, pkg=pkg.seq, pieces=len(staged)):
            for offset, size, args in staged:
                out_dev = unit.dispatch(plan.kernel.fn, offset, args)
                self._issued(out_dev)
                outs.append((offset, size, out_dev))
        return outs

    def complete(self, unit, plan: LaunchPlan, pkg, outs: list) -> None:
        """Phase 3 — await the device and land the output.

        Blocks on every piece's completion event, stamps
        ``pkg.t_complete``, collects each piece's items into the plan's
        output container and stamps ``pkg.t_collected``.

        Args:
            unit: the unit that ran the package.
            plan: the launch's data-plane state.
            pkg: the package to complete.
            outs: the in-flight pieces from :meth:`issue`.

        Raises:
            TypeError: a piece's output has no ``block_until_ready`` —
                an unknown output type the async path cannot synchronize
                on (a silent no-sync here would hand :meth:`_collect`
                a result that may still be materializing).
        """
        for _, _, out_dev in outs:
            sync = getattr(out_dev, "block_until_ready", None)
            if sync is None:
                raise TypeError(
                    f"kernel {plan.kernel.name!r} returned "
                    f"{type(out_dev).__name__!r}, which has no "
                    f"block_until_ready; the pipelined data plane cannot "
                    f"synchronize on it (kernels must return a jax array)")
            sync()
        pkg.t_complete = time.perf_counter()
        with tracing.span("coexec.collect", launch=plan.launch_id,
                          unit=pkg.unit, pkg=pkg.seq):
            self._collect(plan, pkg, outs)
        pkg.t_collected = time.perf_counter()

    def prewarm(self, units: Sequence, plan: LaunchPlan,
                granularity: int) -> None:
        """Compile every package bucket on every unit before dispatch.

        Package pieces are padded to power-of-two compile buckets (see
        :func:`pieces`), so a launch over ``plan.total`` items can only
        ever present ``O(log total)`` distinct input shapes.
        Tracing + compiling each of them here, at plan-build time, keeps
        JIT compile time out of the package timeline — a bucket's first
        dispatch would otherwise charge the compile to the unit and
        poison the dynamic (hguided / work-stealing) speed estimates.
        Warm-up results are discarded; counters are not touched.

        Warm-up is best-effort: a kernel that fails to trace or compile
        is logged at WARNING with the compiler's message and left for the
        real dispatch path, whose error handling fails the launch through
        its handle — pre-warming must not turn a launch failure into a
        submit-time exception.

        Args:
            units: the engine's units (each warms its own jit cache).
            plan: the launch whose kernel/input shapes to warm.
            granularity: package alignment — the smallest bucket is
                ``_bucket(granularity)``.
        """
        bucket = plan.least = _bucket(max(int(granularity), 1))
        top = _bucket(plan.total)
        while True:
            args = []
            for spec, arr in zip(plan.kernel.args, plan.inputs):
                if spec.role is ArgRole.SPLIT:
                    shape = list(np.asarray(arr).shape)
                    shape[spec.axis] = bucket + 2 * spec.halo
                    args.append(np.zeros(tuple(shape),
                                         np.asarray(arr).dtype))
                else:
                    args.append(arr)
            for unit in units:
                try:
                    unit.prewarm(plan.kernel.fn, args)
                except Exception as exc:
                    # boundary: a compiler refusal must be visible here,
                    # but the launch fails through its handle, not submit
                    _log.warning(
                        "pre-warm of kernel %r on unit %s failed at bucket "
                        "%d; its first dispatch will compile (or fail "
                        "through its handle): %s",
                        plan.kernel.name, unit.name, bucket, exc,
                        exc_info=True)
                    return
            if bucket >= top:
                break
            bucket <<= 1

    # -- subclass hooks ----------------------------------------------------
    def _stage(self, unit, plan: LaunchPlan, pkg) -> list:
        raise NotImplementedError

    def _issued(self, out_dev) -> None:
        """Called on each piece's output as soon as it is dispatched."""

    def _collect(self, plan: LaunchPlan, pkg, outs: list) -> None:
        raise NotImplementedError


class UsmDataPlane(DataPlane):
    """Unified-shared-memory data plane: zero staging copies.

    Every unit computes directly on host views of the shared input
    arrays (split args are numpy slices of the one allocation; broadcast
    args are passed whole), and the result is written straight into the
    launch's shared output container — the paper's "collection is free"
    USM semantics (Fig. 2b). No ``device_put``, no copy-back buffer:
    ``h2d_copies == d2h_copies == 0`` by construction. (Both planes cut
    a package into the same pieces and pad each to a power-of-two
    compile bucket — shape management shared with BUFFERS, see
    :func:`_piece_chunks` — which is not data movement and is not
    counted.)
    """

    model = MemoryModel.USM

    def _stage(self, unit, plan: LaunchPlan, pkg) -> list:
        return [(pkg.offset + start, size,
                 [chunk for _, chunk in _piece_chunks(
                     plan, pkg.offset + start, size, bucket)])
                for start, size, bucket in plan.pieces(pkg)]

    def _collect(self, plan: LaunchPlan, pkg, outs: list) -> None:
        # in-place landing in the one shared allocation — the USM no-op
        # collection (no intermediate per-package buffer is materialized)
        for offset, size, out_dev in outs:
            plan.out[offset:offset + size] = out_dev[:size]


class BuffersDataPlane(DataPlane):
    """Per-package buffers data plane: explicit staging in, copy-back out.

    Each piece's split slices are staged with ``jax.device_put`` to the
    unit's device, and each package's broadcast operands once, shared by
    its pieces (buffers are per-package in this model, as in the paper's
    SYCL Buffers mode where accessors are re-created for every command
    group); each piece's result is copied back into a host buffer — the
    copy starts as soon as the piece is dispatched — and then merged
    into the output container. Every copy increments the plan's
    counters. Staged values are identical to the USM plane's chunks
    (same pieces, slice + halo + bucket pad as :func:`_piece_chunks`,
    assembled in place), which is what makes USM-vs-BUFFERS results
    *bitwise* identical for a fixed package structure — the same
    executables run on the same values; only the data movement differs.

    Split-argument staging goes through a per-unit scratch pool: the
    host buffer a piece's slice is assembled in is keyed by
    ``(unit, shape, dtype)`` — one compile bucket, one allocation — and
    returned to the pool when the package collects, instead of a fresh
    pad allocation per ``device_put``. A package in flight holds its
    scratch exclusively, so pipelined staging of package *k+1* can never
    overwrite buffers package *k* is still computing on. The pool is
    reuse of *allocations*, not of data movement: every piece still
    pays its per-argument H2D copy and its D2H copy-back. The fill of
    each split argument's scratch, halo rows included, is one
    ``coexec.fill`` span; the halo rows' bytes are counted apart in
    ``halo_bytes``.
    """

    model = MemoryModel.BUFFERS

    def __init__(self):
        # free scratch per (unit, shape, dtype); leased scratch per
        # in-flight (plan, package) until its collect returns it
        self._scratch: dict[tuple, list] = {}   # guarded-by: _pool_lock
        self._leases: dict[tuple, list] = {}    # guarded-by: _pool_lock
        self._pool_lock = threading.Lock()

    def _borrow(self, unit, shape: tuple, dtype) -> tuple:
        key = (id(unit), tuple(shape), np.dtype(dtype).str)
        with self._pool_lock:
            free = self._scratch.get(key)
            buf = free.pop() if free else None
        if buf is None:
            buf = np.empty(tuple(shape), dtype)
        return key, buf

    def _stage(self, unit, plan: LaunchPlan, pkg) -> list:
        copies = nbytes = 0
        shared = {}
        for i, (spec, arr) in enumerate(zip(plan.kernel.args, plan.inputs)):
            if spec.role is ArgRole.BROADCAST:
                shared[i] = jax.device_put(arr, unit.device)
                copies, nbytes = copies + 1, nbytes + np.asarray(arr).nbytes
        staged, lease = [], []
        halo_bytes = 0
        for start, size, bucket in plan.pieces(pkg):
            args = []
            for i, (spec, arr) in enumerate(zip(plan.kernel.args,
                                                plan.inputs)):
                if i in shared:
                    args.append(shared[i])
                    continue
                shape = list(arr.shape)
                shape[spec.axis] = bucket + 2 * spec.halo
                key, buf = self._borrow(unit, shape, arr.dtype)
                # each argument's copy starts before the next one's fill
                with tracing.span("coexec.fill", launch=plan.launch_id,
                                  unit=pkg.unit, pkg=pkg.seq, rows=size,
                                  halo=spec.halo):
                    _fill_split(buf, arr, spec, pkg.offset + start, size,
                                plan.total)
                args.append(jax.device_put(buf, unit.device))
                lease.append((key, buf))
                copies, nbytes = copies + 1, nbytes + buf.nbytes
                halo_bytes += 2 * spec.halo * _item_bytes(buf, spec.axis)
            staged.append((pkg.offset + start, size, args))
        plan.add(h2d_copies=copies, h2d_bytes=nbytes, halo_bytes=halo_bytes)
        if lease:
            with self._pool_lock:
                self._leases[(id(plan), pkg.seq)] = lease
        return staged

    def _issued(self, out_dev) -> None:
        # start the copy-back now, so a second piece adds its bytes but
        # not a second round trip
        start = getattr(out_dev, "copy_to_host_async", None)
        if start is not None:
            start()

    def _collect(self, plan: LaunchPlan, pkg, outs: list) -> None:
        # copy-back through a separate per-piece buffer, then merge
        nbytes = 0
        for offset, size, out_dev in outs:
            host = np.asarray(out_dev)
            nbytes += host.nbytes
            plan.out[offset:offset + size] = host[:size]
        plan.add(d2h_copies=len(outs), d2h_bytes=nbytes)
        with self._pool_lock:
            for key, buf in self._leases.pop((id(plan), pkg.seq), ()):
                self._scratch.setdefault(key, []).append(buf)


_PLANES = {MemoryModel.USM: UsmDataPlane(),
           MemoryModel.BUFFERS: BuffersDataPlane()}


def make_plane(model: MemoryModel) -> DataPlane:
    """The data plane implementing one memory model.

    Args:
        model: USM or BUFFERS.

    Returns:
        The (stateless, shared) :class:`DataPlane` instance.

    Raises:
        KeyError: unknown memory model.
    """
    return _PLANES[model]
