"""Tests of the ``moved_ratio`` reader, on the CPU.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
from __future__ import annotations

import pathlib
import sys
import types

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import cells, load, run  # noqa: E402

MANDEL = "mandelbrot-t1.hguided.buffers"


def _launch(input_set, h2d, d2h, error=None):
    x = load.Launch(0, "t0", input_set, 1000, 0.0, 1.0, error=error)
    x.stats = types.SimpleNamespace(data=types.SimpleNamespace(
        h2d_bytes=h2d, d2h_bytes=d2h))
    return x


def _observed(launches) -> run.Observed:
    """Two input sets of 1000 points: 8000 B in, 4000 B out a launch."""
    pts = np.zeros(1000, np.float32)
    return run.Observed(
        cell=cells.load(MANDEL), window=load.Window(0.0, 1.0, launches, []),
        builds=[], trace=None, peaks=None,
        input_sets=[[pts, pts], [pts, pts]], answers={0: pts, 1: pts})


def test_moved_ratio_is_the_mean_of_bytes_moved_over_bytes_needed():
    read = cells.reader("moved_ratio")
    obs = _observed([
        _launch(0, 9_000, 4_200),      # 13,200 / 12,000 = 1.1
        _launch(1, 10_000, 5_600),     # 15,600 / 12,000 = 1.3
        # a failed launch is not served, so not counted
        _launch(0, 90_000, 0, error="never returned")])
    assert read(obs) == pytest.approx((1.1 + 1.3) / 2)


def test_moved_ratio_gives_nothing_where_no_copy_was_counted():
    read = cells.reader("moved_ratio")
    # USM counts no copies; a window that served nothing has no launch
    assert read(_observed([_launch(0, 0, 0)])) is None
    assert read(_observed([_launch(0, 9_000, 4_200, error="late")])) is None
