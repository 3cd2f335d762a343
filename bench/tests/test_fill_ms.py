"""Tests of the ``fill_ms`` reader, on the CPU.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
from __future__ import annotations

import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import cells, load, run, xplane  # noqa: E402

GAUSS = "gaussian-t1.static.buffers"


def _observed(host) -> run.Observed:
    trace = None if host is None else xplane.Trace(
        window=(0, 10_000_000), ops={0: []}, host=host)
    return run.Observed(cell=cells.load(GAUSS),
                        window=load.Window(0.0, 1.0, [], []), builds=[],
                        trace=trace, peaks=None, input_sets=[], answers={})


def test_fill_ms_is_the_fill_time_per_package():
    # two packages, three pieces: 1.5 + 0.5 ms on one, 1.0 ms on the other
    host = [("bench.window", 0, 10_000_000),
            ("coexec.stage", 0, 3_000_000),
            ("coexec.fill", 100_000, 1_600_000),
            ("coexec.fill", 2_000_000, 2_500_000),
            ("coexec.issue", 3_000_000, 3_200_000),
            ("coexec.stage", 4_000_000, 5_500_000),
            ("coexec.fill", 4_100_000, 5_100_000),
            ("coexec.issue", 5_500_000, 5_600_000)]
    assert cells.reader("fill_ms")(_observed(host)) == pytest.approx(
        (1.5 + 0.5 + 1.0) / 2)


def test_fill_ms_gives_nothing_without_a_trace_or_without_its_spans():
    read = cells.reader("fill_ms")
    assert read(_observed(None)) is None
    # a program without the span, as before it existed
    assert read(_observed([("bench.window", 0, 10),
                           ("coexec.issue", 1, 2)])) is None


def test_the_gaussian_cell_reports_every_metric_of_the_one_chip_cell():
    cell, mandel = cells.load(GAUSS), cells.load(
        "mandelbrot-t1.hguided.buffers")
    assert cell.chips == 1 and cell.config["items"] == 5120
    assert cell.ref.EVENT.search("%gaussian_blur_halo.1 = f32[2048,5120]")
    assert not cell.ref.EVENT.search("%fusion = f32[2048,5120]")
    assert set(cell.readers) == set(mandel.readers)
    assert {m["name"] for m in cell.end_to_end} == {"items_per_s",
                                                    "setup_s"}
    assert cell.traffic["spec"]["scheduler"]["policy"] == "static"
