"""Spans on the profiler's timeline, and the launch's first-issue stamp.

The real engine runs two units on a small mandelbrot launch (serial, and
pipelined at depth 2) under ``jax.profiler``; the trace's host plane is
read back and held against the packages' own stamps. The DES drives the
same control plane in virtual time and must emit no span at all.
"""
import collections
import glob
import time

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.api import (AdmissionSpec, CoexecSpec, build_kernel,
                       build_scheduler, kernel_demo_inputs)
from repro.core import (AdmissionConfig, CoexecEngine, ExecutionLoop,
                        MemoryCosts, MemoryModel, SimUnit, Workload,
                        counits_from_devices, tracing)
from repro.core.sim import SimBackend, _SimLaunchState

N = 4096
GRAIN = 256
PACKAGE_SPANS = ("coexec.stage", "coexec.issue", "coexec.collect")
#: slack (s) between a stamp and the span it bounds, beyond the clock
#: offset's own uncertainty: a stamp and a span's edge are two reads of
#: two clocks, a few statements apart
SLACK_S = 200e-6


def _spec(depth: int) -> CoexecSpec:
    return (CoexecSpec.builder()
            .policy("hguided")
            .units(count=2, kinds=("cpu", "cpu"), speed_hints=(0.4, 0.6),
                   pipeline_depth=depth)
            .dist(0.4)
            .memory("buffers")
            .build())


def _anchor(events: list) -> tuple[float, float]:
    """Bounds on (trace clock - perf_counter), in seconds, from one
    ``tracing.anchor`` span bracketed by two ``perf_counter`` reads."""
    before = time.perf_counter()
    with jax.profiler.TraceAnnotation("tracing.anchor", i=len(events)):
        after = time.perf_counter()
    events.append((before, after))
    return before, after


def _read(trace_dir: str) -> list[tuple[str, float, float, dict]]:
    """Every host event of the one trace: ``(name, start_s, end_s, ids)``."""
    files = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    assert len(files) == 1, files
    out = []
    for plane in ProfileData.from_file(files[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                out.append((e.name, e.start_ns * 1e-9,
                            (e.start_ns + e.duration_ns) * 1e-9,
                            dict(e.stats)))
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Two small launches through ``launch_async`` (depth 1 and depth 2)
    recorded by the profiler; returns the handles and the clock offset
    bounds (trace clock minus ``perf_counter``)."""
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    kernel = build_kernel("mandelbrot")
    inputs = kernel_demo_inputs("mandelbrot", N, seed=3)
    units = counits_from_devices(jax.local_devices()[:1] * 2,
                                 kinds=["cpu", "cpu"],
                                 speed_hints=[0.4, 0.6])
    # compile outside the trace: the traced runs then hold only the
    # engine's own work
    with _spec(1).runtime(units=units) as rt:
        rt.launch(N, kernel, inputs, granularity=GRAIN)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    brackets: list = []
    handles = {}
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        for _ in range(5):
            _anchor(brackets)
        for depth in (1, 2):
            with _spec(depth).runtime(units=units) as rt:
                handles[depth] = rt.launch_async(N, kernel, inputs,
                                                 granularity=GRAIN,
                                                 tenant=f"d{depth}")
                handles[depth].result(timeout=120)
        for _ in range(5):
            _anchor(brackets)
    finally:
        jax.profiler.stop_trace()
    events = _read(trace_dir)
    anchors = sorted((ids["i"], s) for name, s, _, ids in events
                     if name == "tracing.anchor")
    assert len(anchors) == len(brackets)
    # each anchor's start lies between its two perf_counter reads
    lo = max(s - after for (_, s), (_, after) in zip(anchors, brackets))
    hi = min(s - before for (_, s), (before, _) in zip(anchors, brackets))
    assert lo <= hi + SLACK_S, (lo, hi)
    return handles, events, (lo, hi)


def _spans(events, name):
    return [(s, e, ids) for n, s, e, ids in events if n == name]


def test_every_span_in_SPANS_is_emitted(traced):
    _, events, _ = traced
    names = {n for n, *_ in events}
    assert set(tracing.SPANS) <= names, set(tracing.SPANS) - names
    assert {n for n in names if n.startswith("coexec.")} == \
        set(tracing.SPANS)
    submits = _spans(events, "coexec.submit")
    assert sorted(ids["tenant"] for *_, ids in submits) == ["d1", "d2"]
    assert all(ids["items"] == N for *_, ids in submits)
    for name in ("coexec.lock", "coexec.pull", "coexec.park"):
        assert {ids["unit"] for *_, ids in _spans(events, name)} == {0, 1}


def _own(events, depth):
    """The events of the depth's engine: each engine numbers its launches
    from 0, and the depth-1 engine shut down before depth 2 submitted."""
    split = next(s for n, s, _, ids in events
                 if n == "coexec.submit" and ids["tenant"] == "d2")
    return [ev for ev in events if (ev[1] < split) == (depth == 1)]


@pytest.mark.parametrize("depth", [1, 2])
def test_package_spans_carry_ids_and_lie_inside_the_package(traced, depth):
    handles, events, (lo, hi) = traced
    h = handles[depth]
    by_key = collections.defaultdict(dict)
    for name in PACKAGE_SPANS + ("coexec.retire",):
        for s, e, ids in _spans(_own(events, depth), name):
            # the issue span also says how many pieces the package ran as
            extra = {"pieces"} if name == "coexec.issue" else set()
            assert set(ids) == {"launch", "unit", "pkg"} | extra, (name, ids)
            assert ids.get("pieces", 1) in (1, 2), (name, ids)
            if ids["launch"] == h.launch_id:
                key = (ids["unit"], ids["pkg"])
                assert name not in by_key[key], f"{name} twice for {key}"
                by_key[key][name] = (s, e)
    pkgs = h.stats.packages
    assert len(pkgs) > 2
    assert set(by_key) == {(p.unit, p.seq) for p in pkgs}
    for p in pkgs:
        got = by_key[(p.unit, p.seq)]
        assert set(got) == set(PACKAGE_SPANS) | {"coexec.retire"}
        # span edges on the perf_counter clock, widest reading
        for name in PACKAGE_SPANS:
            s, e = got[name]
            assert s - lo >= p.t_issue - SLACK_S, (name, p)
            assert e - hi <= p.t_collected + SLACK_S, (name, p)
        # and each phase where its stamps put it
        assert got["coexec.issue"][0] - lo >= p.t_launch - SLACK_S
        assert got["coexec.collect"][0] - lo >= p.t_complete - SLACK_S
        assert got["coexec.stage"][1] <= got["coexec.issue"][0]
        assert got["coexec.issue"][1] <= got["coexec.collect"][0]
        assert got["coexec.collect"][1] <= got["coexec.retire"][0]


def test_issue_spans_count_the_packages_served(traced):
    handles, events, _ = traced
    served = sum(h.stats.num_packages for h in handles.values())
    assert len(_spans(events, "coexec.issue")) == served
    assert len(_spans(events, "coexec.retire")) == served


def _check_fills(events, h, halo, split_args):
    """One ``coexec.fill`` per split argument of each piece of ``h``'s
    launch, each inside its package's ``coexec.stage``; for each
    argument, their rows cover the packages."""
    stages = {(ids["unit"], ids["pkg"]): (s, e)
              for s, e, ids in _spans(events, "coexec.stage")
              if ids["launch"] == h.launch_id}
    fills = [(s, e, ids) for s, e, ids in _spans(events, "coexec.fill")
             if ids["launch"] == h.launch_id]
    assert len(fills) == split_args * h.stats.data.pieces > 0
    for s, e, ids in fills:
        assert set(ids) == {"launch", "unit", "pkg", "rows", "halo"}, ids
        assert ids["halo"] == halo
        lo, hi = stages[(ids["unit"], ids["pkg"])]
        assert lo <= s <= e <= hi, (ids, (s, e), (lo, hi))
    assert sum(ids["rows"] for *_, ids in fills) == split_args * sum(
        p.size for p in h.stats.packages)
    return fills


@pytest.mark.parametrize("depth", [1, 2])
def test_fill_spans_one_a_piece_inside_the_stage(traced, depth):
    """Mandelbrot splits two arguments, so two fills a piece."""
    handles, events, _ = traced
    _check_fills(_own(events, depth), handles[depth], halo=0, split_args=2)


def test_fill_spans_of_a_halo_split_into_pieces(tmp_path, monkeypatch):
    """The Gaussian under static 0.4/0.6, each package split in two where
    it can be: 256 rows on one unit, 256 + 128 on the other, one fill a
    piece, each carrying the kernel's 2-row halo."""
    from repro.core import dataplane

    monkeypatch.setattr(dataplane, "_MIN_SPLIT_BYTES", 0)
    rows = 640
    kernel = build_kernel("gaussian")
    inputs = kernel_demo_inputs("gaussian", rows, seed=5)
    units = counits_from_devices(jax.local_devices()[:1] * 2,
                                 kinds=["cpu", "cpu"],
                                 speed_hints=[0.4, 0.6])
    spec = _spec(1).replace(scheduler=_spec(1).scheduler.replace(
        policy="static"))
    with spec.runtime(units=units) as rt:
        rt.launch(rows, kernel, inputs)        # compile outside the trace
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            h = rt.launch_async(rows, kernel, inputs)
            h.result(timeout=120)
        finally:
            jax.profiler.stop_trace()
    fills = _check_fills(_read(str(tmp_path)), h, halo=2, split_args=1)
    assert sorted(ids["rows"] for *_, ids in fills) == [128, 256, 256]


def test_engine_launch_stats_stamp_submit_before_first_issue(traced):
    handles, _, _ = traced
    for h in handles.values():
        st = h.stats
        assert 0 < st.t_submit <= st.t_first_issue
        assert st.t_first_issue == min(p.t_issue for p in st.packages)
        assert st.t_first_issue - st.t_submit < st.total_s


def _affine(offset, chunk):
    import jax.numpy as jnp

    return chunk * 2.0 + jnp.arange(chunk.shape[0], dtype=jnp.float32) \
        + offset


def test_fused_members_stamp_submit_before_first_issue():
    units = counits_from_devices(jax.local_devices()[:1] * 2,
                                 kinds=["cpu", "cpu"],
                                 speed_hints=[0.4, 0.6])
    cfg = AdmissionConfig(fuse=True, fuse_threshold=1024, fuse_wait_s=0.5)
    spec = CoexecSpec(admission=AdmissionSpec.from_config(cfg))
    total = 512
    with CoexecEngine(units, spec=spec) as engine:
        handles = [engine.submit(build_scheduler("dyn8", total, 2), _affine,
                                 [np.full(total, i, np.float32)],
                                 np.zeros(total, np.float32))
                   for i in range(6)]
        for h in handles:
            h.result(timeout=120)
        assert engine.admission.fused_members >= 2
    for h in handles:
        st = h.stats
        assert 0 < st.t_submit <= st.t_first_issue
        assert st.t_first_issue <= st.packages[0].t_issue


def _sim_entries(n: int, total: int, fuse: bool):
    entries = []
    for i in range(n):
        entry = _SimLaunchState(
            i, build_scheduler("dyn8", total, 2),
            Workload("par", total, 4.0, 4.0, 1e4), tenant=f"t{i}",
            t_submit=0.01 * i)
        if fuse:
            entry.fuse_key = ("par", total)
        entries.append(entry)
    return entries


@pytest.mark.parametrize("fuse", [False, True])
def test_des_stamps_submit_before_first_issue_and_emits_no_span(
        fuse, tmp_path):
    units = [SimUnit("u0", "cpu", speed=1000.0, setup_s=1e-3),
             SimUnit("u1", "cpu", speed=1000.0, setup_s=1e-3)]
    cfg = AdmissionConfig(fuse=fuse, fuse_threshold=1024, fuse_wait_s=0.05)
    backend = SimBackend(units, MemoryModel.BUFFERS, MemoryCosts())
    loop = ExecutionLoop(backend, [u.name for u in units], cfg)
    entries = _sim_entries(6, 512, fuse)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        backend.run(loop, entries)
    finally:
        jax.profiler.stop_trace()
    assert [n for n, *_ in _read(str(tmp_path))
            if n.startswith("coexec.")] == []
    assert all(e.stats is not None for e in entries)
    assert any(e.fused for e in entries) == fuse
    for e in entries:
        st = e.stats
        assert st.t_submit == e.t_submit
        assert st.t_submit <= st.t_first_issue
        assert st.t_first_issue <= min(p.t_issue for p in st.packages)
