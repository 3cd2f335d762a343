"""``chip_smoke.py`` refuses to report anything off the chip.

The script proves the engine runs on a TPU, so on any other platform —
including JAX's silent fall-back to the CPU — it must exit non-zero,
name the platform it found, and never print its ``ok`` line. The same
holds where the script stands alone, without the repository.
"""
import importlib.util
import json
import os
import pathlib
import shutil
import subprocess
import sys

import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
SCRIPT = REPO / "chip_smoke.py"


def _run(script: pathlib.Path, *args: str) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, str(script), *args],
                          capture_output=True, text=True, timeout=300,
                          env=env, cwd=script.parent)


def _said_ok(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            doc = json.loads(line)
        except ValueError:
            continue
        if isinstance(doc, dict) and doc.get("ok"):
            return True
    return False


@pytest.mark.parametrize("args", [(), ("--four-chips",)])
def test_refuses_the_cpu_and_names_it(args):
    proc = _run(SCRIPT, *args)
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr
    assert not _said_ok(proc.stdout)


def test_fails_without_the_repository(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, lone)
    proc = _run(lone)
    assert proc.returncode != 0
    assert not _said_ok(proc.stdout)


def _module():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_table1_square_sides_are_lane_aligned():
    smoke = _module()
    # gaussian 26.2M items -> 5120^2; matmul 23.7M -> 4864^2
    assert smoke.lane_side(262 * 10**5) == 5120
    assert smoke.lane_side(237 * 10**5) == 4864


def test_compare_is_exact_for_mandelbrot_and_tolerant_for_ray():
    smoke = _module()
    want = np.arange(8, dtype=np.float32)
    assert smoke.compare("mandelbrot", want.copy(), want)[0]
    off = want.copy()
    off[3] += 1e-6 * max(off[3], 1.0)
    assert not smoke.compare("mandelbrot", off, want)[0]
    assert smoke.compare("ray", off, want)[0]
    bad = want.copy()
    bad[0] = np.nan
    assert not smoke.compare("ray", bad, want)[0]
    assert not smoke.compare("ray", want[:4], want)[0]


def test_compare_holds_matmul_to_its_largest_entry():
    smoke = _module()
    want = np.linspace(-100, 100, 64).astype(np.float32)
    near = want + np.float32(1e-4)        # f32 summation-order noise
    assert smoke.compare("matmul", near, want)[0]
    far = want * np.float32(1.01)         # a bf16-pass product
    assert not smoke.compare("matmul", far, want)[0]
