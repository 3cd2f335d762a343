"""The CoexecKernel protocol + USM/BUFFERS data plane (acceptance tests).

* every registered kernel runs on the real engine under both memory
  models with **bitwise-identical** results;
* USM performs **zero** staging copies (the counters prove it) while
  BUFFERS pays per-package H2D/D2H — strictly more;
* per-argument semantics do what they declare (broadcast operands are
  not sliced, halos reproduce the monolithic stencil exactly, outputs
  allocate from the declared slot);
* the kernel registry behaves like the scheduler/workload registries:
  introspection, strict option validation, third-party registration
  (the retired ``package_kernel`` shim is gone — see tests/test_api.py);
* exact-cover pieces: a package runs as at most two pre-warmed
  power-of-two pieces that cover it exactly, both planes cut the same
  pieces, and the bytes a launch moves stay near its own items.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _propcheck import given, settings, st

from repro.api import (CoexecSpec, build_kernel, kernel_demo_inputs,
                       kernel_names, kernel_plugin, register_kernel,
                       registry_listing, temporary_plugins)
from repro.core import (ArgRole, ArgSpec, CoexecEngine, CoexecKernel,
                        CoexecutorRuntime, OutputSpec, dataplane)
from repro.core.dataplane import _bucket, pieces
from repro.kernels import ref

PAPER_KERNELS = ("gaussian", "mandelbrot", "matmul", "rap", "ray", "taylor")
N = 700          # deliberately not a power of two (uneven package sizes)


def base_spec(memory: str = "usm", policy: str = "hguided") -> CoexecSpec:
    return (CoexecSpec.builder()
            .policy(policy)
            .units(count=2, kinds=("cpu", "cpu"), speed_hints=(0.4, 0.6))
            .dist(0.4)
            .memory(memory)
            .build())


@pytest.fixture(scope="module")
def shared_units():
    """One unit set for the whole module (warm jit caches across tests)."""
    return base_spec().build_units()


@pytest.fixture
def split_every_package(monkeypatch):
    """Split whenever a split saves any pad (test sizes save little)."""
    monkeypatch.setattr(dataplane, "_MIN_SPLIT_BYTES", 0)


def run_engine(memory, kernel, inputs, units, policy="hguided"):
    spec = base_spec(memory, policy)
    with CoexecEngine.from_spec(spec, units=units) as engine:
        sched = spec.build_scheduler(N, len(units))
        h = engine.submit(sched, kernel, inputs, kernel.alloc_out(N, inputs))
        out = h.result(timeout=120)
    return out.copy(), h.stats


# ---------------------------------------------------------------------------
# Acceptance: bitwise USM-vs-BUFFERS parity + counter assertions
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", PAPER_KERNELS)
def test_usm_buffers_bitwise_parity_every_kernel(name, shared_units):
    # dyn16's package structure is deterministic and identical across the
    # two runs, so the same executables see the same values — any output
    # difference would be the data plane's fault (the thing under test).
    # (hguided splits are request-order-dependent, and XLA codegen may
    # contract FMAs differently per chunk shape.)
    kernel = build_kernel(name)
    inputs = kernel_demo_inputs(name, N, seed=7)
    usm_out, usm_stats = run_engine("usm", kernel, inputs, shared_units,
                                    policy="dyn16")
    buf_out, buf_stats = run_engine("buffers", kernel, inputs, shared_units,
                                    policy="dyn16")

    assert np.array_equal(usm_out, buf_out), (
        f"{name}: USM and BUFFERS results differ")
    # USM: zero staging copies, by construction
    assert usm_stats.data.h2d_copies == 0
    assert usm_stats.data.d2h_copies == 0
    assert usm_stats.data.h2d_bytes == 0 and usm_stats.data.d2h_bytes == 0
    # BUFFERS: one D2H per package, one H2D per (package, argument)
    assert buf_stats.data.d2h_copies == buf_stats.num_packages
    assert buf_stats.data.h2d_copies == \
        buf_stats.num_packages * len(kernel.args)
    # strictly fewer staging copies under USM (the paper's USM advantage)
    assert usm_stats.data.staging_copies < buf_stats.data.staging_copies
    # dispatch counts agree with the package log on both planes
    assert usm_stats.data.dispatches == usm_stats.num_packages
    assert buf_stats.data.dispatches == buf_stats.num_packages


def test_memory_spec_reaches_engine_plane(shared_units):
    """MemorySpec selects the engine's actual data plane, not a label."""
    from repro.core.dataplane import BuffersDataPlane, UsmDataPlane

    usm = CoexecEngine.from_spec(base_spec("usm"), units=shared_units)
    buf = CoexecEngine.from_spec(base_spec("buffers"), units=shared_units)
    assert isinstance(usm.plane, UsmDataPlane)
    assert isinstance(buf.plane, BuffersDataPlane)


# ---------------------------------------------------------------------------
# Per-argument semantics
# ---------------------------------------------------------------------------

def test_broadcast_operand_is_not_sliced(shared_units):
    """MatMul's B reaches the kernel whole — the declaration at work."""
    kernel = build_kernel("matmul")
    a, b = kernel_demo_inputs("matmul", N, seed=3)
    out, stats = run_engine("usm", kernel, [a, b], shared_units)
    np.testing.assert_allclose(out, a @ b, rtol=1e-5, atol=1e-5)
    assert out.shape == (N, b.shape[1])     # trailing from the declaration


def test_gaussian_halo_matches_monolithic_reference(shared_units):
    """Split-with-halo reproduces the whole-image stencil bit for bit
    (zero fill beyond the image edges, like the reference's padding)."""
    kernel = build_kernel("gaussian")
    (img,) = kernel_demo_inputs("gaussian", N, seed=11)
    out, _ = run_engine("usm", kernel, [img], shared_units, policy="dyn8")
    want = np.asarray(ref.gaussian_blur(jnp.asarray(img)))
    np.testing.assert_allclose(out, want, rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("name", ("gaussian", "mandelbrot"))
def test_static_split_matches_unsplit_reference_and_counts_halo_bytes(
        name, shared_units, split_every_package):
    """The paper's static policy, 0.4/0.6 over two units, BUFFERS: 256
    rows on one unit and 384 on the other, run as 256 + 128 pieces, give
    the whole-image reference exactly. Each piece carries its 2 + 2 halo
    rows, which ``halo_bytes`` counts inside ``h2d_bytes``; mandelbrot
    has no halo, and USM counts no copies, so both count none."""
    rows = 640
    kernel = build_kernel(name)
    inputs = kernel_demo_inputs(name, rows, seed=13)
    runs = {}
    for memory in ("buffers", "usm"):
        spec = base_spec(memory, "static")
        with CoexecEngine.from_spec(spec, units=shared_units) as engine:
            h = engine.submit(spec.build_scheduler(rows, len(shared_units)),
                              kernel, inputs, kernel.alloc_out(rows, inputs))
            runs[memory] = (h.result(timeout=120).copy(), h.stats)
    out, stats = runs["buffers"]
    assert sorted(p.size for p in stats.packages) == [256, 384]
    d = stats.data
    assert d.pieces == 3
    # jitted, as the kernel is: XLA fuses both the same way
    whole = jax.jit(ref.gaussian_blur if name == "gaussian"
                    else ref.mandelbrot)
    want = np.asarray(whole(*map(jnp.asarray, inputs)))
    assert np.array_equal(out, want)
    if name == "gaussian":
        width = inputs[0].shape[1]
        assert d.halo_bytes == d.pieces * 4 * width * 4
        assert d.h2d_bytes == (256 + 256 + 128 + 3 * 4) * width * 4
    else:
        assert d.halo_bytes == 0
    assert np.array_equal(runs["usm"][0], out)
    assert runs["usm"][1].data.halo_bytes == 0


def test_halo_bytes_survive_split_snapshot_and_to_dict():
    from repro.core import DataPlaneCounters

    c = DataPlaneCounters(pieces=3, h2d_bytes=100, halo_bytes=7)
    assert sum(s.halo_bytes for s in c.split(3)) == 7
    assert c.snapshot().halo_bytes == 7 and c.snapshot() is not c
    assert c.to_dict()["halo_bytes"] == 7


def test_ray_broadcast_default_fills_missing_scene(shared_units):
    """Ray's sphere scene is a trailing BROADCAST default: both arities
    work and agree."""
    from repro.kernels import demo_spheres

    kernel = build_kernel("ray")
    dx, dy, dz = kernel_demo_inputs("ray", N, seed=5)
    out3, _ = run_engine("usm", kernel, [dx, dy, dz], shared_units,
                         policy="dyn8")
    out4, _ = run_engine("usm", kernel,
                         [dx, dy, dz, np.asarray(demo_spheres())],
                         shared_units, policy="dyn8")
    np.testing.assert_array_equal(out3, out4)


def test_runtime_allocates_output_from_declaration():
    """launch(out=None) with a typed kernel uses its declared out slot."""
    kernel = build_kernel("rap")
    vals, lens = kernel_demo_inputs("rap", 256, seed=1)
    with CoexecutorRuntime.from_spec(base_spec()) as rt:
        out = rt.launch(256, kernel, [vals, lens])
    assert out.shape == (256,) and out.dtype == np.float32
    want = np.asarray(ref.rap(jnp.asarray(vals), jnp.asarray(lens)))
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)


def test_split_extent_mismatch_raises(shared_units):
    kernel = build_kernel("taylor")
    spec = base_spec()
    with CoexecEngine.from_spec(spec, units=shared_units) as engine:
        with pytest.raises(ValueError, match="index space"):
            engine.submit(spec.build_scheduler(N, 2), kernel,
                          [np.zeros(N + 1, np.float32)],
                          np.zeros(N, np.float32))


def test_argspec_validation():
    with pytest.raises(ValueError, match="halo"):
        ArgSpec("x", role=ArgRole.BROADCAST, halo=2)
    with pytest.raises(ValueError, match="BROADCAST"):
        ArgSpec("x", default=lambda: np.zeros(3))
    kernel = CoexecKernel("k", lambda off, x: x, (ArgSpec("x"),),
                          OutputSpec())
    with pytest.raises(ValueError, match="takes 1 args"):
        kernel.bind([np.zeros(3), np.zeros(3)])


# ---------------------------------------------------------------------------
# Registry: introspection, validation, plugins, shim
# ---------------------------------------------------------------------------

def test_builtin_kernels_registered():
    assert set(kernel_names()) >= set(PAPER_KERNELS)


def test_kernel_factories_are_memoized():
    """Same options ⇒ same object: jit caches and fusion keys stay warm."""
    assert build_kernel("taylor") is build_kernel("taylor")
    assert build_kernel("taylor", terms=8) is build_kernel("taylor", terms=8)
    assert build_kernel("taylor") is not build_kernel("taylor", terms=8)


def test_unknown_kernel_and_options_rejected():
    with pytest.raises(KeyError):
        build_kernel("nope")
    with pytest.raises(ValueError, match="trems"):
        build_kernel("taylor", trems=8)     # misspelled, named in the error
    with pytest.raises(KeyError):
        CoexecSpec.builder().workload("taylor", kernel="nope").build()


def test_registry_listing_covers_all_three_registries():
    listing = registry_listing()
    assert "schedulers:" in listing
    assert "workloads:" in listing
    assert "kernels:" in listing
    assert "img[split+halo2]" in listing            # gaussian's declaration
    assert "b[broadcast]" in listing                # matmul's declaration
    assert "spheres[broadcast=default]" in listing  # ray's default scene


def test_third_party_kernel_plugin_end_to_end(shared_units):
    """A kernel registered without core edits runs on the engine."""
    def factory(scale=2.0):
        def fn(offset, x, _s=float(scale)):
            return x * _s

        return CoexecKernel("doubler", fn, (ArgSpec("x"),), OutputSpec())

    with temporary_plugins():
        register_kernel("doubler", factory, fields=("scale",),
                        demo_inputs=lambda n, rng:
                        [rng.normal(size=n).astype(np.float32)])
        assert "doubler" in kernel_names()
        kernel = build_kernel("doubler", scale=3.0)
        (x,) = kernel_demo_inputs("doubler", N, seed=2)
        out, stats = run_engine("usm", kernel, [x], shared_units)
        np.testing.assert_allclose(out, x * 3.0)
        assert stats.data.staging_copies == 0
        with pytest.raises(ValueError, match="already registered"):
            register_kernel("doubler", factory)
    assert "doubler" not in kernel_names()          # scope restored


def test_workload_spec_resolves_kernel():
    assert CoexecSpec().workload.resolve_kernel() == "taylor"
    wl = CoexecSpec.builder().workload("mandelbrot").build().workload
    assert wl.resolve_kernel() == "mandelbrot"
    wl = CoexecSpec.builder().workload("mandelbrot",
                                       kernel="rap").build().workload
    assert wl.resolve_kernel() == "rap"
    assert wl.build_kernel() is build_kernel("rap")


def test_registry_kernel_is_callable_with_package_signature():
    kernel = build_kernel("taylor")
    assert kernel is build_kernel("taylor")      # factories memoize
    # callable with the package signature ``fn(offset, *chunks)``
    x = np.linspace(-1, 1, 64, dtype=np.float32)
    np.testing.assert_allclose(np.asarray(kernel(0, x)), np.sin(x),
                               rtol=1e-3, atol=1e-4)
    with pytest.raises(KeyError):
        build_kernel("nope")


def test_registry_listing_survives_option_requiring_factory():
    """--list must not crash on a factory with a required option."""
    def needs_options(scale):            # no default: factory() raises
        return CoexecKernel("scaled", lambda off, x, _s=scale: x * _s,
                            (ArgSpec("x"),), OutputSpec())

    with temporary_plugins():
        register_kernel("scaled", needs_options, fields=("scale",))
        listing = registry_listing()
    assert "scaled" in listing
    assert "factory needs options" in listing


def test_usm_dispatch_places_on_the_units_device():
    """Uncommitted USM views still execute on the unit's device (the
    engine's co-execution claim would silently serialize otherwise)."""
    import jax

    from repro.core import counits_from_devices

    (unit,) = counits_from_devices(jax.local_devices()[:1])
    out = unit.dispatch(lambda off, x: x * 2.0, 0,
                        [np.ones(8, np.float32)])
    assert list(out.devices()) == [unit.device]


def test_fused_member_counters_sum_to_batch_totals():
    """Summing fused members' stats must not overcount the batch."""
    from repro.core import CoexecEngine, DataPlaneCounters
    from repro.api import build_scheduler

    c = DataPlaneCounters(dispatches=2, h2d_copies=7, d2h_copies=2)
    shares = c.split(3)
    assert sum(s.dispatches for s in shares) == 2
    assert sum(s.h2d_copies for s in shares) == 7
    assert sum(s.d2h_copies for s in shares) == 2

    spec = base_spec("buffers")
    units = spec.build_units()
    k = 8
    data = [np.full(256, i, np.float32) for i in range(k)]

    def kernel(offset, chunk):           # one object: launches can fuse
        return chunk * 2.0

    with CoexecEngine(units, spec=spec.replace(
            admission=spec.admission.replace(
                fuse=True, fuse_threshold=1024,
                fuse_wait_s=0.5))) as engine:
        handles = [engine.submit(build_scheduler("dyn4", 256, 2),
                                 kernel, [data[i]],
                                 np.zeros(256, np.float32))
                   for i in range(k)]
        outs = [h.result(timeout=120) for h in handles]
    for i, out in enumerate(outs):
        np.testing.assert_allclose(out, data[i] * 2.0)
    fused = [h for h in handles if h.stats.num_packages == 1]
    assert len(fused) >= 2                       # fusion actually happened
    total_dispatch = sum(h.stats.data.dispatches for h in handles)
    # the batch dispatched at most one package per unit plus any unfused
    # stragglers — far fewer than k launches' worth; summing member
    # shares recovers the true total instead of k x batch
    assert total_dispatch <= 2 * len(units) + (k - len(fused)) * 4


# ---------------------------------------------------------------------------
# DES counter surface matches the real one
# ---------------------------------------------------------------------------

def test_sim_counters_mirror_memory_model():
    from repro.core import SimUnit, Workload, simulate

    wl = Workload(name="reg", total=2048, bytes_in_per_item=4.0,
                  bytes_out_per_item=4.0, working_set_bytes=8.0 * 2048)
    units = [SimUnit("cpu", "cpu", speed=1e5),
             SimUnit("gpu", "gpu", speed=2e5)]
    for mem, copies in (("usm", 0), ("buffers", 1)):
        spec = CoexecSpec.builder().policy("dyn8").memory(mem).build()
        r = simulate(None, units, wl, spec=spec)
        assert r.data.dispatches == r.num_packages
        assert r.data.h2d_copies == copies * r.num_packages
        assert r.data.d2h_copies == copies * r.num_packages
        if mem == "buffers":
            assert r.data.h2d_bytes > 0 and r.data.d2h_bytes > 0


# ---------------------------------------------------------------------------
# Exact-cover pieces
# ---------------------------------------------------------------------------

def _check_pieces(parts, size, least=1):
    assert 1 <= len(parts) <= 2
    at = 0
    for start, piece, bucket in parts:
        assert start == at and piece > 0
        assert bucket >= piece and bucket & (bucket - 1) == 0
        assert least <= bucket <= max(_bucket(size), least)
        at += piece
    assert at == size


@pytest.mark.parametrize("min_split", [0, None])
@pytest.mark.parametrize("size", [1, 3, 256, 700, 1 << 20, (1 << 20) + 256,
                                  3 << 19, (1 << 24) + 96, 16_872_192])
def test_pieces_cover_the_package_exactly(size, min_split, monkeypatch):
    if min_split is not None:
        monkeypatch.setattr(dataplane, "_MIN_SPLIT_BYTES", min_split)
    parts = pieces(size, 12.0)
    _check_pieces(parts, size)
    if size & (size - 1) == 0:
        assert parts == [(0, size, size)]          # never split
    if len(parts) == 2:
        top = parts[0][1]
        assert top & (top - 1) == 0 and top <= size < 2 * top
        saved = (_bucket(size) - top - parts[1][2]) * 12.0
        assert saved > 0 and saved >= dataplane._MIN_SPLIT_BYTES


def test_pieces_split_only_where_the_saved_pad_pays():
    # 16.87M items sit in a 33.55M bucket: 2**24 + 2**17 saves ~200 MB
    assert pieces(16_872_192, 12.0) == [(0, 1 << 24, 1 << 24),
                                        (1 << 24, 94_976, 1 << 17)]
    # 3 * 2**7 saves 128 items (1.5 kB) of pad: not worth a dispatch
    assert pieces(384, 12.0) == [(0, 384, 512)]
    # a 96-item rest still takes the smallest pre-warmed bucket, as does
    # a last package smaller than the granularity
    assert pieces((1 << 20) + 96, 12.0, least=256)[1] == (1 << 20, 96, 256)
    assert pieces(96, 12.0, least=256) == [(0, 96, 256)]


@settings(max_examples=60, deadline=None)
@given(total=st.integers(256, 1 << 22), grain_log2=st.integers(0, 10),
       speed=st.floats(0.1, 0.9))
def test_piece_buckets_are_ones_prewarm_builds(total, grain_log2, speed):
    """Every piece of every package of an hguided launch runs on a
    bucket ``prewarm`` compiles: from ``_bucket(granularity)`` up to
    ``_bucket(total)``."""
    from repro.core import HGuidedScheduler

    grain = min(1 << grain_log2, total)
    sched = HGuidedScheduler(total, 2, speeds=[speed, 1 - speed],
                             granularity=grain)
    saved = dataplane._MIN_SPLIT_BYTES
    dataplane._MIN_SPLIT_BYTES = 0
    try:
        unit = 0
        while (pkg := sched.next_package(unit)) is not None:
            parts = pieces(pkg.size, 8.0, least=_bucket(grain))
            _check_pieces(parts, pkg.size, _bucket(grain))
            for _, _, bucket in parts:
                assert _bucket(grain) <= bucket <= _bucket(total)
            unit ^= 1
    finally:
        dataplane._MIN_SPLIT_BYTES = saved


#: the one-chip mandelbrot cell: 70.3M items, granularity 256, hints
#: 0.4/0.6, 8 B in and 4 B out per item
CELL_ITEMS, CELL_GRAIN, CELL_HINTS = 70_300_000, 256, (0.4, 0.6)


def test_cell_hguided_sequence_moves_at_most_1_10x_its_items():
    """The DES replays the one-chip cell's hguided packages at full size
    with the real rule: what crosses is within 10% of the launch's own
    12 B an item (it was 1.56x on the whole-package buckets), and some
    packages split."""
    from repro.core import SimUnit, Workload, simulate

    wl = Workload(name="mandelbrot-t1", total=CELL_ITEMS,
                  bytes_in_per_item=8.0, bytes_out_per_item=4.0,
                  working_set_bytes=12.0 * CELL_ITEMS)
    units = [SimUnit(f"tpu{i}", "tpu", speed=1e9 * h)
             for i, h in enumerate(CELL_HINTS)]
    spec = (CoexecSpec.builder().policy("hguided").granularity(CELL_GRAIN)
            .units(count=2, speed_hints=CELL_HINTS).dist(CELL_HINTS[0])
            .memory("buffers").build())
    r = simulate(None, units, wl, spec=spec)
    moved = r.data.h2d_bytes + r.data.d2h_bytes
    assert moved <= 1.10 * 12 * CELL_ITEMS, moved / (12 * CELL_ITEMS)
    assert r.data.pieces > r.data.dispatches == r.num_packages


def test_real_plane_on_the_cell_sequence_at_reduced_size(monkeypatch):
    """The real BUFFERS plane runs the cell's hguided package sequence,
    at 1/64 of its items with the split threshold scaled alike: it moves
    at most 1.10x the launch's bytes, some packages split, and the
    escape counts equal the reference's bit for bit."""
    import jax

    from repro.core import HGuidedScheduler, counits_from_devices
    from repro.core.dataplane import make_plane
    from repro.core.memory import MemoryModel

    scale = 64
    total = CELL_ITEMS // scale
    monkeypatch.setattr(dataplane, "_MIN_SPLIT_BYTES",
                        dataplane._MIN_SPLIT_BYTES / scale)
    kernel = build_kernel("mandelbrot")
    cre, cim = kernel_demo_inputs("mandelbrot", total, seed=3)
    (unit,) = counits_from_devices(jax.local_devices()[:1])
    plane = make_plane(MemoryModel.BUFFERS)
    out = kernel.alloc_out(total, [cre, cim])
    plan = plane.plan(kernel, [cre, cim], out, total)
    plane.prewarm([unit], plan, CELL_GRAIN)
    sched = HGuidedScheduler(total, 2, speeds=list(CELL_HINTS),
                             granularity=CELL_GRAIN)
    turn = 0
    while (pkg := sched.next_package(turn)) is not None:
        plane.execute(unit, plan, pkg)
        turn ^= 1
    c = plan.counters
    assert c.h2d_bytes + c.d2h_bytes <= 1.10 * 12 * total
    assert c.pieces > c.dispatches
    want = np.asarray(ref.mandelbrot(jnp.asarray(cre), jnp.asarray(cim)))
    assert np.array_equal(out, want)


@pytest.mark.parametrize("name", PAPER_KERNELS)
def test_split_packages_bitwise_usm_buffers_reference(
        name, shared_units, monkeypatch):
    """With every package split in two, USM and BUFFERS agree bit for
    bit, and with the reference: the same launch run on whole-package
    buckets (and mandelbrot's and rap's plain references). Gaussian's
    halo crosses each piece boundary. MatMul's dot sums over the shared
    axis, whose result XLA may round per chunk shape, so it is held to
    ``a @ b`` within float tolerance there."""
    kernel = build_kernel(name)
    inputs = kernel_demo_inputs(name, N, seed=7)
    whole, _ = run_engine("usm", kernel, inputs, shared_units,
                          policy="dyn16")
    monkeypatch.setattr(dataplane, "_MIN_SPLIT_BYTES", 0)
    usm_out, usm_stats = run_engine("usm", kernel, inputs, shared_units,
                                    policy="dyn16")
    buf_out, buf_stats = run_engine("buffers", kernel, inputs, shared_units,
                                    policy="dyn16")
    assert usm_stats.data.pieces == buf_stats.data.pieces \
        == 2 * buf_stats.num_packages                 # 44-item packages
    assert np.array_equal(usm_out, buf_out)
    if name == "matmul":
        np.testing.assert_allclose(buf_out, inputs[0] @ inputs[1],
                                   rtol=1e-5, atol=1e-5)
    else:
        assert np.array_equal(buf_out, whole)
    if name in ("mandelbrot", "rap"):
        want = getattr(ref, name)(*map(jnp.asarray, inputs))
        assert np.array_equal(buf_out, np.asarray(want))
    # one D2H per piece; one H2D per piece per split arg, plus broadcasts
    n_split = sum(a.role is ArgRole.SPLIT for a in kernel.args)
    d = buf_stats.data
    assert d.d2h_copies == d.pieces
    assert d.h2d_copies == d.pieces * n_split + d.dispatches * (
        len(kernel.args) - n_split)


def test_broadcast_staged_once_per_package(shared_units,
                                           split_every_package):
    """MatMul's B is staged once per package and shared by its pieces."""
    kernel = build_kernel("matmul")
    a, b = kernel_demo_inputs("matmul", N, seed=3)
    out, stats = run_engine("buffers", kernel, [a, b], shared_units,
                            policy="dyn16")
    d = stats.data
    assert d.pieces > d.dispatches == stats.num_packages
    assert d.h2d_copies == d.pieces + d.dispatches      # A per piece, B once
    assert d.h2d_bytes == (d.dispatches * b.nbytes
                           + sum(a[:1].nbytes * bucket
                                 for p in stats.packages
                                 for _, _, bucket in pieces(
                                     p.size, a[:1].nbytes
                                     + out[:1].nbytes)))
    np.testing.assert_allclose(out, a @ b, rtol=1e-5, atol=1e-5)

