"""DES ↔ real-runtime parity: same policy + speeds ⇒ same package count
and exact cover on both `simulate` (virtual time) and `CoexecEngine`
(real threads), for a regular and an irregular workload.

Parity is asserted for the policies whose package count is serve-order
independent: `static` (one package per nonzero share), `dynamic` (fixed
ceil-split), and `work_stealing` (chunks are seeded up front and steals
never split them). `hguided` sizes depend on request order, so only the
cover invariant is checked there.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.api import CoexecSpec, build_scheduler
from repro.core import (CoexecEngine, MemoryModel, SimUnit,
                        counits_from_devices, dataplane, simulate,
                        validate_cover, Workload)

TOTAL = 4096
SPEEDS = [0.4, 0.6]
GRAN = 16

COUNT_STABLE = ["static", "dyn16", "work_stealing"]


def regular_workload():
    return Workload(name="reg", total=TOTAL, bytes_in_per_item=4.0,
                    bytes_out_per_item=4.0, working_set_bytes=8.0 * TOTAL)


def irregular_workload():
    w = np.linspace(0.2, 1.8, TOTAL)
    return Workload(name="irr", total=TOTAL, bytes_in_per_item=4.0,
                    bytes_out_per_item=4.0, working_set_bytes=8.0 * TOTAL,
                    weights=w / w.mean())


def sim_units():
    return [SimUnit("cpu", "cpu", speed=4e5 * SPEEDS[0]),
            SimUnit("gpu", "gpu", speed=4e5 * SPEEDS[1], alpha=1.3)]


def real_units():
    return counits_from_devices(jax.local_devices()[:1] * 2,
                                kinds=["cpu", "cpu"], speed_hints=SPEEDS)


def sched(policy):
    kw = {}
    if policy in ("static", "hguided", "work_stealing"):
        kw["speeds"] = list(SPEEDS)
    return build_scheduler(policy, TOTAL, 2, granularity=GRAN, **kw)


def irregular_kernel(offset, chunk):
    # cost grows with the item's weight position — real irregularity
    idx = jnp.arange(chunk.shape[0], dtype=jnp.float32) + offset
    acc = chunk
    for _ in range(3):
        acc = jnp.sin(acc) + idx * 1e-4
    return acc


@pytest.mark.parametrize("policy", COUNT_STABLE)
@pytest.mark.parametrize("workload_fn", [regular_workload,
                                         irregular_workload])
def test_package_count_and_cover_parity(policy, workload_fn):
    wl = workload_fn()
    r = simulate(sched(policy), sim_units(), wl)
    validate_cover(r.packages, TOTAL)

    data = np.random.default_rng(0).normal(size=TOTAL).astype(np.float32)
    kernel = ((lambda off, c: c * 2.0) if wl.weights is None
              else irregular_kernel)
    with CoexecEngine(real_units()) as engine:
        h = engine.submit(sched(policy), kernel, [data],
                          np.zeros(TOTAL, np.float32))
        h.result(timeout=120)
    validate_cover(h.stats.packages, TOTAL)
    assert h.stats.num_packages == r.num_packages, (
        f"{policy}/{wl.name}: engine issued {h.stats.num_packages} "
        f"packages, DES {r.num_packages}")


@pytest.mark.parametrize("workload_fn", [regular_workload,
                                         irregular_workload])
def test_hguided_cover_parity(workload_fn):
    """HGuided package sizes are order-dependent; parity holds for the
    cover invariant and for both paths terminating with all work issued."""
    wl = workload_fn()
    r = simulate(sched("hguided"), sim_units(), wl)
    validate_cover(r.packages, TOTAL)

    data = np.zeros(TOTAL, np.float32)
    with CoexecEngine(real_units()) as engine:
        h = engine.submit(sched("hguided"), lambda off, c: c + 1.0, [data],
                          np.zeros(TOTAL, np.float32), adaptive=False)
        out = h.result(timeout=120)
    np.testing.assert_allclose(out, 1.0)
    validate_cover(h.stats.packages, TOTAL)


@pytest.mark.parametrize("memory", [MemoryModel.USM, MemoryModel.BUFFERS])
def test_work_stealing_memory_models_parity(memory):
    """Both memory models preserve the count/cover parity (the memory model
    changes data movement and per-package costs, never the package
    structure), and the DES models the same per-package staging copies
    the real data plane counts."""
    wl = regular_workload()
    r = simulate(sched("work_stealing"), sim_units(), wl, memory=memory)
    data = np.arange(TOTAL, dtype=np.float32)
    spec = CoexecSpec.builder().memory(memory.value).build()
    with CoexecEngine.from_spec(spec, units=real_units()) as engine:
        h = engine.submit(sched("work_stealing"), lambda off, c: c * 3.0,
                          [data], np.zeros(TOTAL, np.float32))
        out = h.result(timeout=120)
    np.testing.assert_allclose(out, data * 3.0)
    assert h.stats.num_packages == r.num_packages
    # counter parity: per-package copy structure matches across substrates
    # (the sim charges one H2D + one D2H per package under BUFFERS; the
    # real plane pays one H2D per argument — one here — plus one D2H)
    assert h.stats.data.dispatches == r.data.dispatches
    assert h.stats.data.pieces == r.data.pieces
    assert (h.stats.data.h2d_copies == r.data.h2d_copies) and \
        (h.stats.data.d2h_copies == r.data.d2h_copies)


@pytest.mark.parametrize("memory", [MemoryModel.USM, MemoryModel.BUFFERS])
def test_des_and_engine_agree_on_pieces(memory, monkeypatch):
    """With every package split, the DES cuts the same exact-cover
    pieces as the real data plane for the same package sequence (work
    stealing's is fixed up front): the same dispatches, pieces, copies
    and bytes, on the same pre-warmed buckets."""
    monkeypatch.setattr(dataplane, "_MIN_SPLIT_BYTES", 0)
    wl = regular_workload()
    r = simulate(sched("work_stealing"), sim_units(), wl, memory=memory)
    data = np.arange(TOTAL, dtype=np.float32)
    spec = CoexecSpec.builder().memory(memory.value).build()
    with CoexecEngine.from_spec(spec, units=real_units()) as engine:
        h = engine.submit(sched("work_stealing"), lambda off, c: c * 3.0,
                          [data], np.zeros(TOTAL, np.float32))
        out = h.result(timeout=120)
    np.testing.assert_allclose(out, data * 3.0)
    assert sorted((p.offset, p.size) for p in h.stats.packages) == \
        sorted((p.offset, p.size) for p in r.packages)
    real, des = h.stats.data, r.data
    assert des.pieces > des.dispatches
    assert (real.dispatches, real.pieces) == (des.dispatches, des.pieces)
    assert (real.h2d_copies, real.h2d_bytes, real.d2h_copies,
            real.d2h_bytes) == (des.h2d_copies, des.h2d_bytes,
                                des.d2h_copies, des.d2h_bytes)
