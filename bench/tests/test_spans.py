"""Tests of the readers of the program's spans and stamps, and of the
four-chip cell, on the CPU.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
from __future__ import annotations

import os
import pathlib
import subprocess
import sys
import types

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import cells, load, run, xplane  # noqa: E402

MANDEL = "mandelbrot-t1.hguided.buffers"
FOUR = "mandelbrot-t1.hguided.buffers.4chip"
READERS = ("issue_ms", "control_ms", "lock_wait_ms", "submit_ms",
           "admit_wait_ms")

#: host spans of a made-up window (ns): two packages, three lock waits
HOST = [("bench.window", 0, 10_000_000),
        ("coexec.submit", 0, 9_000_000), ("coexec.submit", 0, 3_000_000),
        ("coexec.lock", 100_000, 150_000), ("coexec.lock", 900_000, 1_000_000),
        ("coexec.lock", 2_000_000, 2_250_000),
        ("coexec.pull", 150_000, 350_000), ("coexec.pull", 1_000_000,
                                            1_100_000),
        ("coexec.park", 1_100_000, 1_900_000),
        ("coexec.issue", 400_000, 1_400_000),
        ("coexec.issue", 1_500_000, 2_000_000),
        ("coexec.retire", 2_250_000, 2_550_000),
        ("coexec.issue.other", 0, 5_000_000)]


def _observed(trace, launches) -> run.Observed:
    cell = cells.load(MANDEL)
    return run.Observed(cell=cell, window=load.Window(0.0, 1.0, launches, []),
                        builds=[], trace=trace, peaks=None, input_sets=[],
                        answers={})


def _launch(t_submit, t_first_issue, error=None):
    x = load.Launch(0, "t0", 0, 1, t_submit, t_submit + 1.0, error=error)
    x.stats = types.SimpleNamespace(t_submit=t_submit,
                                    t_first_issue=t_first_issue)
    return x


def test_span_readers_give_the_values_worked_by_hand():
    trace = xplane.Trace(window=(0, 10_000_000), ops={0: []}, host=HOST)
    obs = _observed(trace, [_launch(1.0, 1.002), _launch(2.0, 2.0005),
                            _launch(3.0, 9.0, error="never returned")])
    readers = {name: cells.reader(name) for name in READERS}
    got = {name: r(obs) for name, r in readers.items()}
    assert got["issue_ms"] == pytest.approx((1.0 + 0.5) / 2)
    # pulls 0.2 + 0.1 ms and one retire 0.3 ms, over two packages
    assert got["control_ms"] == pytest.approx((0.2 + 0.1 + 0.3) / 2)
    assert got["lock_wait_ms"] == pytest.approx((0.05 + 0.1 + 0.25) / 2)
    assert got["submit_ms"] == pytest.approx((9.0 + 3.0) / 2)
    # the failed launch is not served, so not counted
    assert got["admit_wait_ms"] == pytest.approx((2.0 + 0.5) / 2)


def test_span_readers_give_nothing_without_a_trace_or_without_spans():
    obs = _observed(None, [_launch(1.0, 1.002)])
    for name in READERS:
        assert cells.reader(name)(obs) is None, name
    # a program without the spans or the stamps, as before they existed
    bare = xplane.Trace(window=(0, 10), ops={0: []},
                        host=[("bench.window", 0, 10)])
    old = load.Launch(0, "t0", 0, 1, 1.0, 2.0)
    old.stats = types.SimpleNamespace(total_s=1.0)
    obs = _observed(bare, [old])
    for name in READERS:
        assert cells.reader(name)(obs) is None, name


def test_four_chip_cell_loads_with_every_metric_that_lists_it():
    cell = cells.load(FOUR)
    bench = cells.benchmark()
    assert cell.chips == 4
    assert cell.config["name"] == "mandelbrot-t1"
    assert {m["name"] for m in cell.end_to_end} == {"items_per_s",
                                                    "setup_s"}
    assert {m["name"] for m in cell.per_layer} == \
        {m["name"] for m in bench["per_layer"]}
    assert set(READERS) <= set(cell.readers)
    units = cell.traffic["spec"]["units"]
    assert units["count"] is None
    assert "speed_hints" not in units and "dist" not in units
    assert cell.traffic["spec"]["scheduler"]["policy"] == "hguided"


def test_four_chip_spec_builds_a_unit_per_device():
    """On four virtual CPU devices the cell's spec builds four units, one
    a device, with equal hints, and serves a small launch correctly."""
    code = f"""
import sys
sys.path[:0] = [{str(ROOT)!r}, {str(ROOT / 'src')!r}]
import numpy as np
from bench import cells, run
cell = cells.load({FOUR!r})
cell.config = dict(cell.config, items=1 << 14, row_width=128)
spec = run.build_spec(cell)
units = spec.build_units()
assert len(units) == 4, units
assert len({{u.device.id for u in units}}) == 4
assert {{u.speed_hint for u in units}} == {{1.0}}
res = run.run_cell(cell, 2**31 + 7, 0.3, False)
assert res["correct"] and res["failed"] == 0, res
print("ok", len(units))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    got = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=300)
    assert got.returncode == 0, got.stderr[-3000:]
    assert got.stdout.split() == ["ok", "4"]
