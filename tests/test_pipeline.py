"""Pipelined per-unit dispatch (stage / issue / complete overlap).

Acceptance for the pipelining tentpole and its satellites:

* **bitwise depth parity** — every registered kernel produces
  byte-identical results at ``pipeline_depth`` 2 and 4 vs the serial
  depth-1 path, on both data planes, under all four package schedulers
  (pipelining changes *when* packages move, never what they compute);
* **depth-invariant structure** — a propcheck property that
  ``(seed, policy, depth)`` never changes the DES package cover or the
  ``DataPlaneCounters`` totals (scheduler decisions must not observe
  the pipeline);
* **kill mid-pipeline** — a unit dying with a full pipeline in flight
  has *all* of its in-flight packages disowned and re-issued exactly
  once, with covers and counter totals identical to an undisturbed run;
* **compile warm-up** (satellite) — ``JaxUnit.prewarm`` AOT-compiles
  without executing the kernel body;
  ``CoexecEngine.submit`` warms every package bucket before dispatch;
* **exact park wait** (satellite) — an idle engine holding a staged
  fusion group wakes at the ripen deadline, not on a coarse poll;
* **loud sync guard** (satellite) — a kernel whose output cannot be
  synchronized on (no ``block_until_ready``) fails the launch with a
  ``TypeError`` instead of silently serializing the pipeline.
"""
import time

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.api import CoexecSpec, build_kernel, build_scheduler, \
    kernel_demo_inputs
from repro.core import (CoexecEngine, FailurePlan, MemoryCosts, SimUnit,
                        Workload, as_coexec_kernel, replay_trace_cluster,
                        simulate, synthesize_trace, validate_cover)

from _propcheck import given, settings, st

PAPER_KERNELS = ("gaussian", "mandelbrot", "matmul", "rap", "ray", "taylor")
POLICIES = ("static", "dyn16", "hguided", "work_stealing")
N = 700          # deliberately not a power of two (uneven package sizes)


def spec_for(memory: str, policy: str, depth: int) -> CoexecSpec:
    return (CoexecSpec.builder()
            .policy(policy)
            .units(count=2, kinds=("cpu", "cpu"), speed_hints=(0.4, 0.6),
                   pipeline_depth=depth)
            .dist(0.4)
            .memory(memory)
            .build())


@pytest.fixture(scope="module")
def shared_units():
    """One unit set for the whole module (warm jit caches across tests)."""
    return spec_for("usm", "dyn16", 1).build_units()


def run_engine(memory, policy, depth, kernel, inputs, units):
    spec = spec_for(memory, policy, depth)
    with CoexecEngine.from_spec(spec, units=units) as engine:
        assert engine.pipeline_depth == depth
        sched = spec.build_scheduler(N, len(units))
        h = engine.submit(sched, kernel, inputs, kernel.alloc_out(N, inputs))
        out = h.result(timeout=120)
    return out.copy(), h.stats


# ---------------------------------------------------------------------------
# Acceptance: bitwise depth parity, every kernel x plane x policy
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("memory", ("usm", "buffers"))
@pytest.mark.parametrize("name", PAPER_KERNELS)
def test_depth_bitwise_parity_every_kernel(name, memory, shared_units):
    """depth ∈ {2, 4} is byte-identical to depth 1 for every registered
    kernel on both data planes under every scheduler whose package
    cover is deterministic (static / dyn16 / work_stealing — identical
    packaging means identical executables seeing identical values, so
    any difference would be the pipeline's fault). HGuided covers are
    request-order-dependent, which already perturbs XLA's per-chunk FMA
    contraction at depth 1 (see tests/test_dataplane.py) — there the
    depth axis is held to numerical equivalence plus the exact-cover
    invariant."""
    kernel = build_kernel(name)
    inputs = kernel_demo_inputs(name, N, seed=7)
    for policy in POLICIES:
        base, base_stats = run_engine(memory, policy, 1, kernel, inputs,
                                      shared_units)
        for depth in (2, 4):
            out, stats = run_engine(memory, policy, depth, kernel, inputs,
                                    shared_units)
            if policy == "hguided":
                np.testing.assert_allclose(base, out, rtol=1e-5,
                                           atol=1e-5)
            else:
                assert np.array_equal(base, out), (
                    f"{name}/{memory}/{policy}: depth {depth} differs "
                    f"from serial")
            validate_cover(stats.packages, N)
            if policy == "dyn16":   # fixed ceil-split: exact counters
                assert stats.num_packages == base_stats.num_packages
                assert stats.data.dispatches == base_stats.data.dispatches
                assert stats.data.h2d_copies == base_stats.data.h2d_copies
                assert stats.data.d2h_copies == base_stats.data.d2h_copies


# ---------------------------------------------------------------------------
# Propcheck: (seed, policy, depth) never changes covers or counter totals
# ---------------------------------------------------------------------------

def _sim_run(seed: int, policy: str, depth: int):
    rng = np.random.default_rng(seed)
    total = 2048 + 256 * int(rng.integers(0, 8))
    weights = None
    if rng.integers(0, 2):
        w = rng.uniform(0.2, 1.8, total)
        weights = w / w.mean()
    wl = Workload(name=f"prop{seed}", total=total, bytes_in_per_item=4.0,
                  bytes_out_per_item=4.0, working_set_bytes=8.0 * total,
                  weights=weights)
    units = [SimUnit("cpu", "cpu", speed=4e5 * 0.4),
             SimUnit("gpu", "gpu", speed=4e5 * 0.6, alpha=1.3)]
    kw = ({"speeds": [0.4, 0.6]}
          if policy in ("static", "hguided", "work_stealing") else {})
    sched = build_scheduler(policy, total, 2, granularity=16, **kw)
    spec = CoexecSpec.builder().pipeline_depth(depth).build()
    return simulate(sched, units, wl, spec=spec), total


@given(seed=st.integers(0, 10**6), policy=st.sampled_from(POLICIES),
       depth=st.integers(2, 4))
@settings(max_examples=12, deadline=None)
def test_sim_structure_is_depth_invariant(seed, policy, depth):
    """The DES models the overlap in *time* only: package covers,
    per-unit attribution and DataPlaneCounters totals are identical to
    the serial run for any (seed, policy, depth)."""
    r1, total = _sim_run(seed, policy, 1)
    rd, _ = _sim_run(seed, policy, depth)
    validate_cover(rd.packages, total)
    cover = lambda r: sorted((p.unit, p.offset, p.size) for p in r.packages)
    assert cover(rd) == cover(r1)
    assert rd.data == r1.data
    assert rd.host_busy_s == pytest.approx(r1.host_busy_s)
    # pipelining can only help the modeled makespan, never hurt it
    assert rd.total_s <= r1.total_s + 1e-12


# ---------------------------------------------------------------------------
# Kill mid-pipeline: every in-flight package re-issued exactly once
# ---------------------------------------------------------------------------

def test_kill_mid_pipeline_reissues_all_inflight_exactly_once():
    """A unit dying with a full pipeline (depth 2 => 2 packages in
    flight) has both disowned and re-issued exactly once; covers and
    counter totals stay bitwise identical to an undisturbed run."""
    trace = synthesize_trace(60, 40.0, tenants=4, items=4096,
                             item_jitter=0.8, slo_ms=200.0, seed=3)
    units = [SimUnit(f"u{i}", "cpu", speed=20_000.0, setup_s=1e-3)
             for i in range(4)]
    spec = CoexecSpec.builder().pipeline_depth(2).build()
    r0 = replay_trace_cluster(trace, units, admission="wfq", spec=spec)
    plan = FailurePlan(timeline=((0.2, "kill:3"),))
    r1 = replay_trace_cluster(trace, units, admission="wfq", spec=spec,
                              plan=plan)
    assert r1.kills == [(0.2, 3)]
    # the dead unit held a full pipeline: >= 2 attempts were lost and
    # re-issued; exactly once each (nothing lost, nothing duplicated)
    assert r1.reissued >= 2
    assert r1.lost == 0 and r1.duplicated == 0
    assert r1.completed == r0.completed == len(trace)
    assert r1.covers() == r0.covers()
    assert r1.data_totals() == r0.data_totals()


# ---------------------------------------------------------------------------
# Satellite: compile warm-up is AOT and never runs the kernel body
# ---------------------------------------------------------------------------

def test_prewarm_compiles_without_executing_or_charging_busy(shared_units):
    calls = []

    def body(off, chunk):
        def host(c):
            calls.append(1)
            return np.asarray(c) * 2.0
        return jax.pure_callback(
            host, jax.ShapeDtypeStruct(chunk.shape, chunk.dtype), chunk)

    unit = shared_units[0]
    args = [np.ones(64, np.float32)]
    unit.prewarm(body, args)
    assert calls == [], "prewarm must not execute the kernel body"
    # the warmed executable computes the same thing the jit path does
    out = unit.dispatch(body, 0, args)
    out.block_until_ready()
    assert calls, "dispatch after prewarm never ran the kernel"
    np.testing.assert_array_equal(np.asarray(out), args[0] * 2.0)
    # memoized: warming the same bucket again is a no-op
    unit.prewarm(body, args)


def test_submit_prewarms_every_bucket_before_dispatch(shared_units):
    """The engine warms each power-of-two package bucket at submit time,
    so the first dispatch of every bucket runs a precompiled executable
    (XLA compile time never lands in the SpeedBoard's samples)."""
    kernel = as_coexec_kernel(lambda off, c: c * 3.0, 1)  # fresh fn object
    inputs = [np.random.default_rng(0).normal(size=N).astype(np.float32)]
    warmed0 = {id(u): len(u._aot) for u in shared_units}
    out, stats = run_engine("usm", "dyn16", 2, kernel, inputs, shared_units)
    for u in shared_units:
        assert len(u._aot) > warmed0[id(u)], (
            f"{u.name}: submit left no ahead-of-time executables")
    validate_cover(stats.packages, N)


@pytest.mark.parametrize("memory", ("usm", "buffers"))
def test_split_packages_pipelined_bitwise_and_prewarmed(memory, shared_units,
                                                        monkeypatch):
    """At depth 2, packages split into two pieces give the serial
    output bit for bit (retire completes each package's pieces in issue
    order, each at its own global offset), and no unit builds a program
    after the pre-warm: the
    ahead-of-time executables stay as the first submit left them, and
    the jit fallback never compiles."""
    from repro.core import dataplane

    monkeypatch.setattr(dataplane, "_MIN_SPLIT_BYTES", 0)
    def at_index(off, c):        # each item adds its global index
        return c * 3.0 + (jnp.arange(c.shape[0]) + off).astype(c.dtype)

    kernel = as_coexec_kernel(at_index, 1)
    inputs = [np.random.default_rng(5).normal(size=N).astype(np.float32)]
    base, base_stats = run_engine(memory, "dyn16", 1, kernel, inputs,
                                  shared_units)
    warmed = {id(u): set(u._aot) for u in shared_units}
    out, stats = run_engine(memory, "dyn16", 2, kernel, inputs,
                            shared_units)
    assert stats.data.pieces > stats.data.dispatches == stats.num_packages
    assert stats.data.pieces == base_stats.data.pieces
    assert np.array_equal(base, out)
    np.testing.assert_allclose(
        out, inputs[0] * 3.0 + np.arange(N, dtype=np.float32), rtol=1e-6)
    for u in shared_units:
        assert set(u._aot) == warmed[id(u)], u.name
        assert u.compiled(kernel.fn)._cache_size() == 0, u.name


# ---------------------------------------------------------------------------
# Satellite: exact park wait — ripen deadlines, not a coarse poll
# ---------------------------------------------------------------------------

def test_idle_engine_flushes_fusion_group_within_ripen_window(shared_units):
    """A staged fusion group left alone on an idle engine is flushed by
    a worker waking at the ripen deadline. The pre-pipelining park loop
    polled every 100 ms, so a 30 ms window could not complete before
    ~100 ms; the exact wait must finish well under that."""
    spec = (CoexecSpec.builder()
            .policy("dyn16")
            .units(count=2, kinds=("cpu", "cpu"), speed_hints=(0.4, 0.6),
                   pipeline_depth=2)
            .dist(0.4)
            .fuse(True, threshold=4096, limit=8, wait_s=0.03)
            .build())
    kernel = build_kernel("taylor")
    inputs = kernel_demo_inputs("taylor", 256, seed=1)
    with CoexecEngine.from_spec(spec, units=shared_units) as engine:
        sched = spec.build_scheduler(256, 2)
        t0 = time.perf_counter()
        h = engine.submit(sched, kernel, inputs,
                          kernel.alloc_out(256, inputs))
        out = h.result(timeout=30)
        elapsed = time.perf_counter() - t0
    assert out is not None
    assert elapsed < 0.09, (
        f"fusion window (30 ms) took {elapsed * 1e3:.0f} ms to flush — "
        f"workers are polling instead of waiting on the ripen deadline")


# ---------------------------------------------------------------------------
# Satellite: outputs the plane cannot synchronize on fail loudly
# ---------------------------------------------------------------------------

def test_unsyncable_kernel_output_raises_loudly(shared_units):
    """A kernel returning something without ``block_until_ready`` (here
    a tuple) must fail the launch with a TypeError naming the contract —
    never fall back to a silent host sync that would serialize the
    pipeline unnoticed."""
    tuple_kernel = as_coexec_kernel(lambda off, c: (c * 2.0,), 1)
    data = np.ones(128, np.float32)
    spec = spec_for("usm", "dyn16", 2)
    with CoexecEngine.from_spec(spec, units=shared_units) as engine:
        sched = spec.build_scheduler(128, 2)
        h = engine.submit(sched, tuple_kernel, [data],
                          np.zeros(128, np.float32))
        with pytest.raises(TypeError, match="block_until_ready"):
            h.result(timeout=30)
